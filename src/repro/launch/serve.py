"""Production serving launcher: plan-routed batched decode via repro.serve.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
        --mesh 2x2 --buckets 4x16 8x32 --max-new 16

Builds a ``repro.serve.Server`` (persistent compiled prefill/decode pair),
AOT-warms the declared (batch, seq) bucket grid -- filling the plan cache
with each bucket's ``SchedulePlan``s -- then serves a synthetic request
batch through the bucket router and prints throughput, TTFT, per-token
latency quantiles, and the serve-window plan-cache report.  ``--mesh``
routes every forward matmul through the plan engine (on CPU runs set
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` first, as the CI
smoke job does); without it the server decodes the local GSPMD baseline.
``--smoke`` selects the reduced config and exits nonzero on any serving
error -- the CI entry point.

``init_model`` / ``synthetic_prompts`` / ``serve`` / ``print_run`` are the
launcher's body, shared with ``chip_smoke.py``.
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config, get_smoke_config
from repro.launch.report import plan_cache_table
from repro.mesh import make_mesh
from repro.models.registry import build_model
from repro.runtime.serve import ServeConfig
from repro.serve import Server, ServeResult, as_bucket


def _parse_mesh(spec):
    if not spec:
        return None
    rows, cols = (int(s) for s in spec.lower().split("x"))
    devs = jax.devices()
    if len(devs) < rows * cols:
        raise SystemExit(
            f"--mesh {spec} needs {rows * cols} devices, have {len(devs)}; "
            f"set XLA_FLAGS=--xla_force_host_platform_device_count=N for "
            f"CPU runs")
    return make_mesh((rows, cols), ("x", "y"), devices=devs[: rows * cols])


def _parse_bucket(spec) -> tuple:
    batch, seq = (int(s) for s in spec.lower().split("x"))
    return (batch, seq)


def init_model(cfg, seed: int):
    """``(model, params)`` for ``cfg`` with random weights from ``seed``.
    The init is jitted: eager init of a full-width stacked model would hold
    every layer's fp32 normals at once."""
    model = build_model(cfg)
    return model, jax.jit(model.init)(jax.random.PRNGKey(seed))


def synthetic_prompts(seed: int, n: int, lo: int, hi: int,
                      vocab: int) -> List[List[int]]:
    """``n`` prompts of ``lo``..``hi - 1`` tokens drawn from ``seed``."""
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, size=rng.integers(lo, hi)).tolist()
            for _ in range(n)]


@dataclasses.dataclass
class ServeRun:
    """One launcher run: the warm server, its warmup accounting, the served
    batch, and the serving errors (empty when the run is sound)."""

    server: Server
    warmup: Dict[str, Dict]
    result: ServeResult
    errors: List[str]


def serve(model, params, sc: ServeConfig, prompts: Sequence[Sequence[int]],
          *, buckets: Sequence, mesh=None, strategy: Optional[str] = None,
          seed: int = 0) -> ServeRun:
    """Warm a ``Server`` over ``buckets`` and serve ``prompts`` once."""
    server = Server(model, params, sc, mesh=mesh, strategy=strategy,
                    buckets=[as_bucket(b) for b in buckets])
    warm = server.warmup()
    res = server.generate(prompts, key=jax.random.PRNGKey(seed))
    errors = []
    sw = server.cache_report().get("serve_window")
    if mesh is not None and sw is not None \
            and sw["hit_rate"] not in (None, 1.0):
        errors.append("warm-bucket serving missed the plan cache")
    if mesh is not None and res.plan_probe["probed"] == 0:
        errors.append("no warm plans probed -- decode not plan-routed")
    return ServeRun(server, warm, res, errors)


def print_run(run: ServeRun, name: str, routed: bool) -> None:
    """The launcher's report: warmup, throughput and latency, the first
    tokens of each request, and the plan-cache table."""
    for label, w in run.warmup.items():
        print(f"[warmup] bucket {label}: {w['plans']} plans, "
              f"{w['warm_s']:.2f}s, cache aliased {w['cache_alias']:.4f}")
    res = run.result
    q = res.latency_quantiles_ms()
    p50 = q["p50_ms"] if q["p50_ms"] is None else round(q["p50_ms"], 2)
    p99 = q["p99_ms"] if q["p99_ms"] is None else round(q["p99_ms"], 2)
    print(f"[serve] arch={name} {'plan-routed' if routed else 'local'} "
          f"batch={len(res.new_tokens)} bucket={res.bucket or 'cold'} "
          f"{res.generated_tokens} tokens in {res.wall_s:.2f}s "
          f"({res.tokens_per_s:.1f} tok/s) ttft={res.ttft_s * 1e3:.1f}ms "
          f"p50={p50}ms p99={p99}ms")
    for i, toks in enumerate(res.new_tokens):
        print(f"  req{i} (len {len(res.sequences[i]) - len(toks)}): "
              f"{toks[:8]}...")
    rep = run.server.cache_report()
    print("\n### Plan cache\n")
    print(plan_cache_table(rep["info"]))
    sw = rep.get("serve_window")
    if sw is not None:
        rate = "-" if sw["hit_rate"] is None else f"{sw['hit_rate']:.2f}"
        print(f"serve window: {sw['hits']} hits / {sw['misses']} misses "
              f"(hit rate {rate})")
    for err in run.errors:
        print(f"[serve] ERROR: {err}")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--mesh", default=None, metavar="RxC",
                    help="route matmuls through the plan engine on this mesh")
    ap.add_argument("--strategy", default=None,
                    help="pin the schedule strategy inside the plan scope")
    ap.add_argument("--buckets", nargs="+", default=["4x16", "8x32"],
                    metavar="BxS", help="warm (batch, seq) serving buckets")
    ap.add_argument("--batch", type=int, default=4,
                    help="synthetic requests to serve")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model, params = init_model(cfg, args.seed)
    mesh = _parse_mesh(args.mesh)
    sc = ServeConfig(max_new_tokens=args.max_new, max_seq=args.max_seq,
                     temperature=args.temperature)
    prompts = synthetic_prompts(args.seed, args.batch, 4, 12, cfg.vocab_size)
    run = serve(model, params, sc, prompts, mesh=mesh,
                strategy=args.strategy,
                buckets=[_parse_bucket(b) for b in args.buckets],
                seed=args.seed)
    print_run(run, cfg.name, routed=mesh is not None)
    return 1 if run.errors else 0


if __name__ == "__main__":
    sys.exit(main())
