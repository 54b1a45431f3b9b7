"""Plan-routed batched serving example: bucketed warmup + mesh decode.

    PYTHONPATH=src XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python examples/serve_batched.py
    PYTHONPATH=src python examples/serve_batched.py --no-mesh

Builds a ``repro.serve.Server`` over a 4-device (2x2) mesh: warmup
AOT-compiles the declared (batch, seq) buckets and fills the plan cache
with each bucket's solver-derived ``SchedulePlan``s; the request batch is
then routed to the nearest warm bucket (left-padded, offset-corrected)
and every decode matmul executes its planned schedule.  ``--no-mesh``
serves the local single-device baseline instead -- same buckets, same
tokens, no plan engine.
"""
import argparse
import os

if "host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4"
                               ).strip()

import jax                                                   # noqa: E402
import numpy as np                                           # noqa: E402

from repro.configs import get_smoke_config                   # noqa: E402
from repro.mesh import make_mesh                             # noqa: E402
from repro.models.registry import build_model                # noqa: E402
from repro.runtime.serve import ServeConfig                  # noqa: E402
from repro.serve import Server                               # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--no-mesh", action="store_true",
                    help="serve the local baseline instead of plan-routed")
    ap.add_argument("--strategy", default=None,
                    help="pin one schedule family (cannon, summa, ...)")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--batch", type=int, default=3)
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    mesh = None
    if not args.no_mesh:
        devs = jax.devices()
        if len(devs) < 4:
            raise SystemExit(f"need 4 devices for the 2x2 mesh, have "
                             f"{len(devs)}; run with --no-mesh or set "
                             f"XLA_FLAGS=--xla_force_host_platform_device_count=4")
        mesh = make_mesh((2, 2), ("x", "y"), devices=devs[:4])

    sc = ServeConfig(max_new_tokens=args.max_new, max_seq=128)
    server = Server(model, params, sc, mesh=mesh, strategy=args.strategy,
                    buckets=[(4, 16), (4, 32)])
    for label, w in server.warmup().items():
        print(f"warmup {label}: {w['plans']} plans in {w['warm_s']:.2f}s")

    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size,
                            size=rng.integers(3, 9)).tolist()
               for _ in range(args.batch)]
    print(f"arch={cfg.name} {'local' if mesh is None else 'plan-routed 2x2'}: "
          f"serving {len(prompts)} requests, "
          f"lens {[len(p) for p in prompts]}")

    res = server.generate(prompts)
    q = res.latency_quantiles_ms()
    print(f"bucket={res.bucket}: {res.generated_tokens} tokens in "
          f"{res.wall_s:.2f}s ({res.tokens_per_s:.1f} tok/s), "
          f"ttft {res.ttft_s * 1e3:.1f}ms, p50 {q['p50_ms']:.2f}ms")
    for i, toks in enumerate(res.new_tokens):
        print(f"req{i}: ...{toks}")

    rep = server.cache_report()
    sw = rep.get("serve_window") or {}
    print(f"plan cache: {rep['info']['currsize']} plans, serve-window "
          f"hit rate {sw.get('hit_rate')}")


if __name__ == "__main__":
    main()
