"""Readings the limits of a cell's checks are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seconds <s> --seeds 1 2 3 ...

For each seed, in one process (set-up is paid once for the compiles):
run the cell's window for ``--seconds``, read each number its check
compares, then read the same number with the lower-precision control in
the program's place (float8 operands; see ``bench/lib/gemm.py``).  One
JSON line per seed; the limits in ``bench/workloads/<cell>.json`` sit
between the largest program reading and the smallest control reading.
A benchmark run never runs the control.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# libtpu would otherwise write its logs to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()

    from bench.lib import harness

    cell = harness.load_cell(args.workload)
    devices, kind, peaks = harness.chips(cell.chips)
    harness.use_compile_cache()
    for seed in args.seeds:
        out = harness.driver(cell).run(cell, devices, peaks, seed=seed,
                                       seconds=args.seconds, trace=False,
                                       t0=time.perf_counter())
        t = time.perf_counter()
        control = out.control()
        print(json.dumps({
            "workload": cell.name, "seed": seed, "kind": kind,
            "program": {c.name: c.value for c in out.checks},
            "limits": {c.name: c.limit for c in out.checks},
            "control": control, "control_s": time.perf_counter() - t,
            "end_to_end": out.end_to_end}), flush=True)
        del out
    return 0


if __name__ == "__main__":
    sys.exit(main())
