"""The grouped expert GEMM's device time in a traced window.

The program's grouped GEMM (``repro.kernels.matmul.grouped_matmul``) runs
as a Pallas kernel whose device operations are named ``grouped_matmul.<n>``,
or as XLA's ragged dot, whose operations are named ``ragged-dot-<...>``.
A program without either gives the readers nothing to read.
"""
from __future__ import annotations

import re

from bench.lib import trace as tr

GMM_OPS = re.compile(r"^(grouped_matmul|ragged-dot)")


def gmm_seconds(trace: tr.Trace) -> float:
    """Device seconds of the grouped GEMM's operations inside the window,
    averaged over the chips."""
    lo, hi = trace.window
    return sum(max(0.0, min(e, hi) - max(s, lo)) for c in trace.chips
               for n, s, e in trace.ops[c] if GMM_OPS.search(n)
               ) / len(trace.chips)
