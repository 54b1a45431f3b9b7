"""Run one cell many times, each run a process of its own as a check runs
it, and report the spread of every metric.

    python3 bench/tools/sets.py --workload <cell> --seconds <s> \
        --seeds 11 12 13 14 15 16 --sets 2 [--warm 99] [--traced 21 22 23] \
        --out <file>.jsonl

``--warm`` first makes one short untimed run (it compiles into the
checkout's cache).  Then come ``--sets`` sets of ``bench/run.py`` runs,
each over the same ``--seeds``, and a ``--trace 1`` run for each of
``--traced``.  Every run appends one JSON line to ``--out``: its set, seed,
exit code, wall seconds, result line and the end of its standard error.
The summary printed last gives, per metric and set, the median and the
spread: the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median.

This process never imports JAX, so each run it starts has the chips to
itself.  ``--summarize <file>`` prints the summary of an earlier ``--out``.
"""
import argparse
import collections
import json
import os
import statistics
import subprocess
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def one(workload, seed, seconds, trace, timeout):
    t = time.perf_counter()
    try:
        done = subprocess.run(
            [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
             workload, "--seed", str(seed), "--seconds", str(seconds),
             "--trace", str(trace)],
            cwd=ROOT, capture_output=True, text=True, timeout=timeout)
        rc, out, err = done.returncode, done.stdout, done.stderr
    except subprocess.TimeoutExpired as e:
        rc, out, err = 124, e.stdout or "", e.stderr or ""
        out = out.decode() if isinstance(out, bytes) else out
        err = err.decode() if isinstance(err, bytes) else err
    lines = out.strip().splitlines()
    try:
        result = json.loads(lines[-1]) if lines else None
    except json.JSONDecodeError:
        result = None
    return {"rc": rc, "wall_s": time.perf_counter() - t, "result": result,
            "stdout_head": "\n".join(lines[:-1])[-2000:],
            "stderr_tail": err[-2000:]}


def spread(values):
    q1, med, q3 = statistics.quantiles(values, n=4)
    return med, (q3 - q1) / med


def summarize(rows):
    by = collections.defaultdict(list)
    for r in rows:
        res = r.get("result") or {}
        tag = "trace" if r["trace"] else f"set{r['set']}"
        for name, m in res.get("metrics", {}).items():
            by[(name, tag)].append(m["value"])
    bad = [(r["set"], r["seed"], r["trace"], r["rc"]) for r in rows
           if not (r.get("result") or {}).get("correct")]
    print(f"runs {len(rows)}, not correct or no result: {bad}")
    for (name, tag), vs in sorted(by.items()):
        if len(vs) >= 2:
            med, sp = spread(vs)
            print(f"{name:28s} {tag:6s} n={len(vs):2d} median {med!r} "
                  f"spread {sp:.5f} min {min(vs)!r} max {max(vs)!r}")
        else:
            print(f"{name:28s} {tag:6s} n={len(vs):2d} value {vs[0]!r}")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload")
    ap.add_argument("--seconds", type=int)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--warm", type=int)
    ap.add_argument("--traced", type=int, nargs="*", default=[])
    ap.add_argument("--timeout", type=int, default=1300)
    ap.add_argument("--out")
    ap.add_argument("--summarize")
    args = ap.parse_args()
    if args.summarize:
        with open(args.summarize) as f:
            rows = [json.loads(x) for x in f if x.strip()]
        summarize([r for r in rows if r["set"] or r["trace"]])
        return 0

    plan = ([(0, args.warm, min(args.seconds, 5), 0)]
            if args.warm is not None else [])
    plan += [(s + 1, seed, args.seconds, 0) for s in range(args.sets)
             for seed in args.seeds]
    plan += [(0, seed, args.seconds, 1) for seed in args.traced]
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    rows = []
    for set_no, seed, seconds, trace in plan:
        row = {"workload": args.workload, "set": set_no, "seed": seed,
               "seconds": seconds, "trace": trace}
        row.update(one(args.workload, seed, seconds, trace, args.timeout))
        with open(args.out, "a") as f:
            f.write(json.dumps(row) + "\n")
        res = row["result"] or {}
        print(f"set {set_no} seed {seed} trace {trace} rc {row['rc']} "
              f"wall {row['wall_s']:.1f} s correct {res.get('correct')} "
              f"{ {k: v['value'] for k, v in res.get('metrics', {}).items()} }",
              flush=True)
        if set_no or trace:          # the warm run is not summarized
            rows.append(row)
    summarize(rows)
    return 0


if __name__ == "__main__":
    sys.exit(main())
