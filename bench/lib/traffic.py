"""Seeded traffic from the parameters of a workload file.

One general generator: a workload names a length distribution, a token
range and a batch size; ``prompts`` draws them from ``--seed``.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np


def lengths(rng: np.random.Generator, n: int, spec: Dict) -> np.ndarray:
    """``n`` prompt lengths: ``{"dist": "lognormal", "median", "sigma",
    "min", "max"}``, rounded and clipped to [min, max]."""
    if spec["dist"] == "lognormal":
        x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
        return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def prompts(rng: np.random.Generator, n: int, spec: Dict) -> List[List[int]]:
    """``n`` prompts with lengths from ``spec["lengths"]`` and token ids
    uniform in ``[spec["token_min"], spec["token_max"])``."""
    return [rng.integers(spec["token_min"], spec["token_max"], int(k)).tolist()
            for k in lengths(rng, n, spec["lengths"])]
