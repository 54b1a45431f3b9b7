"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --smoke \
        --steps 100 --ckpt /tmp/ckpt

On a real TPU cluster this process runs per host (jax.distributed
initializes from the TPU environment); the mesh comes from
``make_production_mesh`` when the device count allows, else from the
available devices.  Fault tolerance: checkpoints + auto-restore are in the
Trainer; pod-loss re-meshing in repro.runtime.elastic.
"""
from __future__ import annotations

import argparse

import jax

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config, get_smoke_config
from repro.data.pipeline import DataConfig, batch_iterator
from repro.mesh import make_mesh
from repro.models.registry import build_model
from repro.runtime.train import Trainer, TrainConfig


def build_mesh(tp: int):
    devs = jax.devices()
    n = len(devs)
    if n == 1:
        return None
    tp = min(tp, n)
    dp = n // tp
    return make_mesh((dp, tp), ("data", "model"), devices=devs[: dp * tp])


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--tp", type=int, default=4)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg)
    mesh = build_mesh(args.tp)
    print(f"[launch] arch={cfg.name} params={cfg.param_count()/1e6:.1f}M "
          f"mesh={'1 device' if mesh is None else dict(mesh.shape)}")

    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                    global_batch=args.batch, seed=args.seed)
    tc = TrainConfig(steps=args.steps, lr=args.lr,
                     warmup=max(args.steps // 20, 5),
                     ckpt_dir=args.ckpt, ckpt_every=max(args.steps // 4, 10),
                     log_every=max(args.steps // 20, 1))
    out = Trainer(model, tc, mesh=mesh).fit(
        jax.random.PRNGKey(args.seed), batch_iterator(dc)
    )
    h = out["history"]
    print(f"[launch] done: loss {h[0]['loss']:.4f} -> {h[-1]['loss']:.4f} "
          f"({out['restarts']} restarts)")


if __name__ == "__main__":
    main()
