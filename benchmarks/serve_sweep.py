"""Serving config-matrix sweep: mesh shape x batch bucket x strategy.

    PYTHONPATH=src python benchmarks/serve_sweep.py --smoke \
        --out serve_sweep.json
    PYTHONPATH=src python benchmarks/serve_sweep.py --report serve_sweep.json
    PYTHONPATH=src python benchmarks/serve_sweep.py --smoke \
        --baseline serve_sweep_prev.json

Each cell AOT-warms a ``repro.serve.Server`` for one (mesh, bucket,
strategy) config on forced-host devices (``SERVE_SWEEP_DEVICES`` env,
default 8 -- the flag must precede the jax import), serves a fixed
synthetic request batch, and records tokens/s/device, TTFT, p50/p99
per-token decode latency, the serve-window plan-cache hit rate, and
whether the plan-routed greedy tokens match the unrouted ``1x1``
baseline bitwise.  Output is a schema'd JSON (``repro.serve_sweep/v1``);
``--report`` renders it as a table (null-latency rows -- e.g.
``--max-new 1`` -- print as '-'), ``--baseline`` diffs tokens/s per cell
against a previous run and exits nonzero when a cell regresses beyond
``SERVE_SWEEP_MARGIN`` (default 25%: host-CPU serving is noisy).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

SCHEMA = "repro.serve_sweep/v1"

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def _force_host_devices() -> int:
    # forced-host device farm: pinned to the CPU, so that a machine with a
    # chip cannot hand this sweep one real device in place of the farm
    os.environ["JAX_PLATFORMS"] = "cpu"
    devices = int(os.environ.get("SERVE_SWEEP_DEVICES", "8"))
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={devices}"
            .strip())
    return devices


# (mesh label, mesh shape or None, strategy or None=auto).  2x2 exercises
# the torus families, 1x4 the ring/collective families, 1x1 is the
# unrouted baseline every routed cell's greedy tokens must match bitwise.
DEFAULT_GRID = (
    ("1x1", None, None),
    ("2x2", (2, 2), None),
    ("2x2", (2, 2), "cannon"),
    ("2x2", (2, 2), "summa"),
    ("1x4", (1, 4), None),
)


def _mesh(shape):
    import jax

    from repro.mesh import make_mesh

    if shape is None:
        return None
    n = shape[0] * shape[1]
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(f"need {n} devices, have {len(devs)}")
    return make_mesh(shape, ("x", "y"), devices=devs[:n])


def _prompts(rng, n, lo=2, hi=10, vocab=200):
    return [rng.integers(1, vocab, size=int(rng.integers(lo, hi))).tolist()
            for _ in range(n)]


def run_sweep(args) -> dict:
    n_devices = _force_host_devices()
    import jax
    import numpy as np

    from repro.configs import get_config, get_smoke_config
    from repro.models.registry import build_model
    from repro.plan import cache_clear
    from repro.runtime.serve import ServeConfig
    from repro.serve import Server, bucket_grid

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.dtype:
        import dataclasses

        cfg = dataclasses.replace(cfg, dtype=args.dtype)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    scfg = ServeConfig(max_new_tokens=args.max_new, max_seq=args.max_seq)
    buckets = bucket_grid(args.batches, args.seqs)
    rng = np.random.default_rng(args.seed)
    requests = {b: _prompts(rng, max(1, b.batch - 1), hi=min(10, b.seq + 1),
                            vocab=cfg.vocab_size)
                for b in buckets}

    # the unrouted baseline tokens per bucket, for bitwise comparison
    cache_clear()
    base = Server(model, params, scfg, buckets=buckets)
    base.warmup()
    baseline_tokens = {b: base.generate(requests[b]).sequences
                       for b in buckets}

    cells = []
    for mesh_label, mesh_shape, strategy in DEFAULT_GRID:
        try:
            mesh = _mesh(mesh_shape)
        except RuntimeError as e:
            for b in buckets:
                cells.append({"mesh": mesh_label, "bucket": b.label,
                              "strategy": strategy or "auto", "ok": False,
                              "error": str(e)})
            continue
        cache_clear()
        try:
            srv = Server(model, params, scfg, mesh=mesh, strategy=strategy,
                         buckets=buckets)
            t0 = time.perf_counter()
            warm = srv.warmup()
            warm_s = time.perf_counter() - t0
        except Exception:
            for b in buckets:
                cells.append({"mesh": mesh_label, "bucket": b.label,
                              "strategy": strategy or "auto", "ok": False,
                              "error": traceback.format_exc(limit=1)})
            continue
        for b in buckets:
            cells.append(_run_cell(srv, b, requests[b], baseline_tokens[b],
                                   mesh_label, strategy, warm[b.label],
                                   warm_s, n_devices if mesh else 1))
    return {
        "schema": SCHEMA,
        "arch": cfg.name,
        "created_unix": int(time.time()),
        "config": {"max_new_tokens": scfg.max_new_tokens,
                   "max_seq": scfg.max_seq, "devices": n_devices,
                   "buckets": [b.label for b in buckets]},
        "cells": cells,
    }


def _run_cell(srv, bucket, prompts, baseline, mesh_label, strategy,
              warm_info, warm_s, n_dev) -> dict:
    try:
        res = srv.generate(prompts)
        rep = srv.cache_report()
        q = res.latency_quantiles_ms()
        sw = rep.get("serve_window") or {}
        return {
            "mesh": mesh_label,
            "bucket": bucket.label,
            "strategy": strategy or "auto",
            "ok": True,
            "routed": res.bucket is not None and srv.mesh is not None,
            "plans": warm_info["plans"],
            "warmup_s": round(warm_s, 4),
            "tokens_per_s": round(res.tokens_per_s, 2),
            "tokens_per_s_per_device": round(res.tokens_per_s / n_dev, 2),
            "ttft_ms": round(res.ttft_s * 1e3, 3),
            "p50_ms": None if q["p50_ms"] is None else round(q["p50_ms"], 3),
            "p99_ms": None if q["p99_ms"] is None else round(q["p99_ms"], 3),
            "cache_hit_rate": sw.get("hit_rate"),
            "match_baseline": res.sequences == baseline,
            "error": None,
        }
    except Exception:
        return {"mesh": mesh_label, "bucket": bucket.label,
                "strategy": strategy or "auto", "ok": False,
                "error": traceback.format_exc(limit=1)}


def render_report(data) -> str:
    from repro.launch.report import serve_sweep_table

    return serve_sweep_table(data)


def _cell_key(c):
    return (c["mesh"], c["bucket"], c["strategy"])


def diff_baseline(data, baseline_data, margin: float):
    """Per-cell tokens/s regression vs a previous sweep JSON; returns the
    list of regressed cells."""
    old = {_cell_key(c): c for c in baseline_data["cells"] if c.get("ok")}
    regressions = []
    lines = []
    for c in data["cells"]:
        if not c.get("ok"):
            continue
        prev = old.get(_cell_key(c))
        if prev is None:
            continue
        now, was = c["tokens_per_s"], prev["tokens_per_s"]
        delta = (now - was) / was if was else 0.0
        flag = ""
        if now < was * (1.0 - margin):
            regressions.append(c)
            flag = "  <-- REGRESSION"
        lines.append(f"{c['mesh']} {c['bucket']} {c['strategy']}: "
                     f"{was:.1f} -> {now:.1f} tok/s ({delta:+.0%}){flag}")
    return regressions, lines


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--batches", type=int, nargs="+", default=[2, 4])
    ap.add_argument("--seqs", type=int, nargs="+", default=[16])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--dtype", default="float32",
                    help="model compute dtype; float32 (default) keeps "
                         "greedy argmax margins far above the accumulation-"
                         "order noise between schedules, so routed tokens "
                         "compare bitwise against the unrouted baseline")
    ap.add_argument("--out", default="serve_sweep.json")
    ap.add_argument("--report", metavar="JSON",
                    help="render a sweep JSON as a table and exit")
    ap.add_argument("--baseline", metavar="JSON",
                    help="diff tokens/s against a previous sweep JSON")
    args = ap.parse_args()

    if args.report:
        with open(args.report) as f:
            data = json.load(f)
        if data.get("schema") != SCHEMA:
            print(f"not a serve-sweep JSON (schema={data.get('schema')!r})")
            return 2
        print(f"### Serve sweep: {data['arch']} "
              f"(max_new={data['config']['max_new_tokens']}, "
              f"{data['config']['devices']} devices)\n")
        print(render_report(data))
        return 0

    data = run_sweep(args)
    with open(args.out, "w") as f:
        json.dump(data, f, indent=1)
    print(render_report(data))
    bad = [c for c in data["cells"]
           if c.get("ok") and not c["match_baseline"]]
    errs = [c for c in data["cells"] if not c.get("ok")]
    print(f"# {len(data['cells'])} cells, {len(errs)} errors, "
          f"{len(bad)} baseline mismatches -> {args.out}")

    rc = 1 if (bad or errs) else 0
    if args.baseline:
        margin = float(os.environ.get("SERVE_SWEEP_MARGIN", "0.25"))
        with open(args.baseline) as f:
            prev = json.load(f)
        regressions, lines = diff_baseline(data, prev, margin)
        print(f"\n# baseline diff vs {args.baseline} (margin {margin:.0%})")
        for ln in lines:
            print(ln)
        if regressions:
            rc = 1
    return rc


if __name__ == "__main__":
    sys.exit(main())
