"""Benchmark driver: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (stdout; the us field is EMPTY for
derived-only benches, never a fake 0.0) and mirrors rows into
bench_results.json for the experiment index.  Every record and report file
is stamped with ``"schema": 2``.

``--smoke`` runs the tiny-shape subset (no subprocess device farms) under
``repro.obs`` tracing and exits nonzero on any bench error -- the CI job
that catches plan-cache and dispatch regressions before merge.  It writes
two artifacts for upload: ``bench_trace.json`` (Chrome/Perfetto
trace_event) and ``bench_metrics.json`` (flat metrics snapshot).

``--report <metrics.json>`` pretty-prints a metrics snapshot written by
``repro.obs.write_metrics`` (counters, histogram summaries, span counts,
per-strategy collective totals).

``--conformance`` runs the ``repro.verify`` conformance matrix (strategy x
mesh shape x {square, ragged, batched} x dtype) on forced-host devices
(``CONFORMANCE_DEVICES`` env, default 8): every cell's executed collectives
must match the schedule trace and the analytic cost model exactly.  Exits
nonzero on any non-conforming cell.

``--drift [machine_profile.json]`` runs ``repro.verify.drift`` on forced-
host devices (``DRIFT_DEVICES`` env, default 8): obs recorder ==
interceptor == trace on live executions, plus calibrated-ranking stability
against the stored profile when one is given.  When the stored profile
embeds a ``repro.tune`` TuningTable, the tuning leg re-measures each
stored bucket and fails on winners stale beyond the same 10% noise
margin.  Writes drift_report.json; exits nonzero on divergence.

``--tune-smoke`` runs the kernel-autotuning bench subset (bounded
interpret-mode searches; tuned vs default blocks must not regress beyond
the noise margin) and writes bench_results_tune.json.  Exits nonzero on
any bench error -- the CI gate for the measured-autotuning path.
"""
from __future__ import annotations

import json
import os
import sys
import traceback

SCHEMA_VERSION = 2

# allow `python benchmarks/run.py` (not just -m benchmarks.run): the import
# below needs the repo root, and the benches need src/ for repro
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

# Every bench here times the host CPU backend or the Pallas interpreter, and
# several run forced-host device farms in child processes.  Pin this process
# and its children to the CPU: no figure here passes for a chip measurement,
# and no child contends for a chip another process holds.
os.environ["JAX_PLATFORMS"] = "cpu"


def _force_host_devices(env_var: str, default: int) -> None:
    """Set the forced-host device flag; must run before jax is imported."""
    devices = int(os.environ.get(env_var, str(default)))
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={devices}".strip())


def run_conformance() -> int:
    """Forced-host conformance matrix; must run before jax is imported so
    the device-count flag takes effect."""
    _force_host_devices("CONFORMANCE_DEVICES", 8)
    from repro.verify import run_matrix

    rows = run_matrix()
    print("strategy,mesh,case,dtype,overlap,ok,words_per_node,error")
    for r in rows:
        mesh = "x".join(str(s) for s in r["mesh"])
        print(f"{r['strategy']},{mesh},{r['case']},{r['dtype']},"
              f"{r.get('overlap', False)},"
              f"{r['ok']},{r['words_per_node']},{r['error']}", flush=True)
    bad = [r for r in rows if not r["ok"]]
    with open("conformance_results.json", "w") as f:
        json.dump({"schema": SCHEMA_VERSION, "cells": rows}, f, indent=1)
    print(f"# {len(rows)} cells, {len(bad)} non-conforming")
    return 1 if bad else 0


def run_drift(argv) -> int:
    """Forced-host drift check (see repro.verify.drift); flag must precede
    the jax import."""
    _force_host_devices("DRIFT_DEVICES", 8)
    profile_path = None
    i = argv.index("--drift")
    if i + 1 < len(argv) and not argv[i + 1].startswith("-"):
        profile_path = argv[i + 1]
    from repro.verify import check_drift

    report = check_drift(profile_path=profile_path)
    report["schema"] = SCHEMA_VERSION
    print("strategy,mesh,ok,collectives,error")
    for c in report["cells"]:
        mesh = "x".join(str(s) for s in c["mesh"])
        print(f"{c['strategy']},{mesh},{c['ok']},{c['collectives']},"
              f"{c['error']}", flush=True)
    for r in report["ranking"]:
        shape = "x".join(str(s) for s in r["shape"])
        mark = "FLIP" if r["flipped"] else "ok"
        print(f"# ranking {shape}: stored={r['stored_top']} "
              f"fresh={r['fresh_top']} margin={r['margin']:.3f} [{mark}]")
    for r in report.get("tuning", []):
        bucket = "x".join(str(s) for s in r["bucket"])
        mark = "FLIP" if r["flipped"] else "ok"
        print(f"# tuning {r['dtype']} {bucket}: stored={r['stored']} "
              f"fresh={r['fresh']} margin={r['margin']:.3f} [{mark}]")
    with open("drift_report.json", "w") as f:
        json.dump(report, f, indent=1)
    print(f"# drift {'OK' if report['ok'] else 'DIVERGED'} "
          f"({len(report['cells'])} cells, "
          f"{sum(r['flipped'] for r in report['ranking'])} ranking flips, "
          f"{sum(r['flipped'] for r in report.get('tuning', []))} "
          f"tuning flips)")
    return 0 if report["ok"] else 1


def run_report(path: str) -> int:
    """Pretty-print a metrics snapshot written by repro.obs.write_metrics,
    or a bench_results*.json row list written by this driver."""
    with open(path) as f:
        snap = json.load(f)
    if isinstance(snap, list):
        # bench results: rows with possibly-null us_per_call and error rows
        print(f"# bench report: {path} ({len(snap)} rows)")
        for row in snap:
            us = row.get("us_per_call")
            us_field = "-" if us is None else f"{us:.1f}"
            tail = row.get("error") or row.get("derived", "")
            print(f"  {row.get('name', '?')}: {us_field} us  {tail}")
        return 0
    print(f"# metrics report: {path} (schema {snap.get('schema', '?')})")
    metrics = snap.get("metrics", {})
    if metrics:
        print("\n## counters / histograms")
        for name in sorted(metrics):
            v = metrics[name]
            if isinstance(v, dict):  # histogram summary
                print(f"  {name}: n={v['count']} sum={v['sum']:.1f} "
                      f"min={v['min']:.1f} max={v['max']:.1f} "
                      f"mean={v['mean']:.1f}")
            else:
                print(f"  {name}: {v}")
    spans = snap.get("spans", {})
    if spans:
        print("\n## span counts")
        for name in sorted(spans):
            print(f"  {name}: {spans[name]}")
    colls = snap.get("collectives", {})
    if colls:
        print("\n## collectives by strategy")
        for strat in sorted(colls):
            kinds = colls[strat]
            detail = " ".join(
                f"{kind}={c['count']}({c['shard_words']}w)"
                for kind, c in sorted(kinds.items()))
            print(f"  {strat}: {detail}")
    return 0


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if "--conformance" in argv:
        return run_conformance()
    if "--drift" in argv:
        return run_drift(argv)
    if "--report" in argv:
        i = argv.index("--report")
        if i + 1 >= len(argv):
            print("--report requires a metrics JSON path", file=sys.stderr)
            return 2
        return run_report(argv[i + 1])

    from benchmarks.paper_benches import (ALL_BENCHES, SMOKE_BENCHES,
                                          TUNE_BENCHES)

    smoke = "--smoke" in argv
    tune = "--tune-smoke" in argv
    benches = TUNE_BENCHES if tune else (
        SMOKE_BENCHES if smoke else ALL_BENCHES)

    from repro import obs

    rows = []
    errors = 0
    print("name,us_per_call,derived")
    with obs.observe() as rec:
        for bench in benches:
            try:
                for name, us, derived in bench():
                    # derived-only rows time nothing: empty CSV field, null
                    # JSON value
                    us_field = "" if us is None else f"{us:.1f}"
                    print(f"{name},{us_field},{derived}", flush=True)
                    rows.append({"schema": SCHEMA_VERSION, "name": name,
                                 "us_per_call": us, "derived": derived})
            except Exception as e:  # noqa: BLE001 -- report and continue
                print(f"{bench.__name__},,ERROR:{type(e).__name__}:{e}",
                      flush=True)
                traceback.print_exc(file=sys.stderr)
                rows.append({"schema": SCHEMA_VERSION,
                             "name": bench.__name__, "error": str(e)})
                errors += 1
    out = ("bench_results_tune.json" if tune else
           "bench_results_smoke.json" if smoke else "bench_results.json")
    with open(out, "w") as f:
        json.dump(rows, f, indent=1)
    if smoke:
        # CI artifacts: Perfetto-loadable trace + flat metrics snapshot
        obs.write_trace("bench_trace.json", rec)
        obs.write_metrics("bench_metrics.json", rec)
        print("# wrote bench_trace.json bench_metrics.json")
    return 1 if ((smoke or tune) and errors) else 0


if __name__ == "__main__":
    sys.exit(main())
