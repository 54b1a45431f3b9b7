"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` runs a
traced window and reports its per-layer metrics, read from the profiler
trace by ``bench/metrics/<metric>.py``.  Every run checks what its timed
path produced against the configuration's plain reference and prints each
number compared beside its limit, on standard error and under ``checks``
in the result, which is the last line of standard output.

Without a TPU, with fewer chips than the cell asks for, or on a chip kind
missing from ``bench/lib/peaks.py``, it exits nonzero and prints no result.
"""
import time

T0 = time.perf_counter()   # set-up is timed from here

import argparse  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# libtpu would otherwise write its logs to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    import repro  # noqa: F401  (the system under test, from src/)

    from bench.lib import harness
    from bench.lib.peaks import UnknownDevice

    cell = harness.load_cell(args.workload)
    try:
        devices, kind, peaks = harness.chips(cell.chips)
    except (harness.NoChip, UnknownDevice) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2

    harness.use_compile_cache()
    if args.trace:
        shutil.rmtree(harness.TRACE_DIR, ignore_errors=True)
    out = harness.driver(cell).run(cell, devices, peaks, seed=args.seed,
                                   seconds=args.seconds, trace=bool(args.trace),
                                   t0=T0)
    per_layer, breakdown = {}, None
    if args.trace:
        from bench.lib import trace as tr
        ctx = harness.Reading(out.trace, out.work, peaks, len(devices))
        for m in cell.per_layer:
            value = harness.metric_reader(m["name"]).read(ctx)
            if value is not None:
                per_layer[m["name"]] = value
        breakdown = {"device_ops": tr.op_seconds(out.trace),
                     "idle_gaps": tr.idle_gaps(out.trace)}
    line = harness.result_line(cell, out, devices, kind, bool(args.trace),
                               per_layer, breakdown)
    harness.print_result(line, out.checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
