import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ MUST precede every other import: jax locks the device count on first init.
# The 512 devices are a forced-host farm, so the dry run is pinned to the CPU.

"""Multi-pod dry-run: lower + compile every (architecture x input-shape)
cell on the production meshes and record memory/cost/roofline analysis.

    PYTHONPATH=src python -m repro.launch.dryrun                 # all cells
    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b \
        --shape train_4k --mesh single,multi
    PYTHONPATH=src python -m repro.launch.dryrun --out results.json

For each cell this lowers the *production* step function:
  train_4k     -> full train_step (fwd + bwd + AdamW update, donated state)
  prefill_32k  -> forward logits
  decode_32k / long_500k -> serve_step (one token against the KV/state cache)

and requires ``.lower().compile()`` to succeed on the 16x16 single-pod mesh
AND the 2x16x16 multi-pod mesh.  memory_analysis() proves fit;
cost_analysis() + the HLO call-graph analyzer feed Sec. Roofline.

(note: no ``from __future__`` here -- the XLA_FLAGS lines above must stay
the first statements of the module.)
"""
import argparse
import dataclasses
import json
import time
import traceback
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, get_config, runnable_cells, skipped_cells
from repro.mesh import make_production_mesh
from repro.launch.specs import (abstract_cache, abstract_opt_state,
                                abstract_params, input_specs)
from repro.models.sharding_rules import (cache_shardings, param_shardings,
                                         zero_shardings)
from repro.optim import adamw
from repro.roofline import analysis
from repro.runtime.sharding import resolve_axis, use_mesh


def _batch_shardings(batch, mesh: Mesh, *, shard_batch: bool):
    baxes = resolve_axis("batch", mesh)
    out = {}
    for k, v in batch.items():
        if k == "pos" or v.ndim == 0 or not shard_batch:
            out[k] = NamedSharding(mesh, P())
        else:
            out[k] = NamedSharding(mesh, P(baxes, *([None] * (v.ndim - 1))))
    return out


def _rep(mesh):
    return NamedSharding(mesh, P())


def lower_cell(arch: str, shape: str, mesh: Mesh, *,
               remat: str = "config", zero: bool = True) -> Dict:
    cfg = get_config(arch)
    if remat != "config":
        cfg = dataclasses.replace(cfg, remat=remat)
    model, aparams = abstract_params(cfg)
    cell = SHAPES[shape]
    chips = mesh.size
    psh = param_shardings(aparams, mesh)
    batch = input_specs(arch, shape)
    shard_batch = cell.global_batch >= mesh.shape.get("data", 1)
    bsh = _batch_shardings(batch, mesh, shard_batch=shard_batch)
    dtypes = jax.tree.map(lambda p: p.dtype, aparams)

    if cell.kind == "train":
        astate = abstract_opt_state(aparams)
        osh = zero_shardings(aparams, mesh) if zero else psh
        sh_state = {
            "step": _rep(mesh), "master": osh, "m": osh, "v": osh,
        }
        opt_cfg = adamw.AdamWConfig()

        def train_step(state, batch):
            def loss_of_master(master):
                params = jax.tree.map(lambda w, t: w.astype(t), master, dtypes)
                return model.loss(params, batch)
            (loss, _), grads = jax.value_and_grad(
                loss_of_master, has_aux=True
            )(state["master"])
            new_state, _ = adamw.step(state, grads, jnp.float32(1e-4), opt_cfg)
            return new_state, loss

        fn = jax.jit(
            train_step,
            in_shardings=(sh_state, bsh),
            out_shardings=(sh_state, _rep(mesh)),
            donate_argnums=(0,),
        )
        args = ({"step": jax.ShapeDtypeStruct((), jnp.int32),
                 **{k: astate[k] for k in ("master", "m", "v")}}, batch)
        tokens = cell.global_batch * cell.seq_len
        model_flops = analysis.train_model_flops(cfg.active_param_count(), tokens)
    elif cell.kind == "prefill":
        def prefill(params, batch):
            if cfg.family == "audio":
                logits, _ = model.forward(params, {
                    "tokens": batch["tokens"], "src_embed": batch["src_embed"]})
            else:
                logits, _ = model.forward(params, batch["tokens"])
            return logits
        model_ax = resolve_axis("model", mesh)
        from repro.layers.embed import padded_vocab
        if padded_vocab(cfg.vocab_size) % mesh.shape.get("model", 1) != 0:
            model_ax = None
        fn = jax.jit(
            prefill, in_shardings=(psh, bsh),
            out_shardings=NamedSharding(
                mesh, P(resolve_axis("batch", mesh), None, model_ax)),
        )
        args = (aparams, batch)
        tokens = cell.global_batch * cell.seq_len
        model_flops = analysis.infer_model_flops(cfg.active_param_count(), tokens)
    else:  # decode
        acache = abstract_cache(model, cfg, shape)
        csh = cache_shardings(acache, mesh, shard_batch=shard_batch)

        def serve_step(params, cache, batch):
            return model.decode_step(params, cache, batch["tokens"], batch["pos"])

        fn = jax.jit(
            serve_step,
            in_shardings=(psh, csh, bsh),
            out_shardings=(None, csh),
            donate_argnums=(1,),
        )
        args = (aparams, acache, batch)
        tokens = cell.global_batch  # one token per sequence
        model_flops = analysis.infer_model_flops(cfg.active_param_count(), tokens)

    t0 = time.perf_counter()
    with use_mesh(mesh):
        lowered = fn.lower(*args)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
    t_compile = time.perf_counter() - t0 - t_lower

    mem = compiled.memory_analysis()
    roof = analysis.from_compiled(compiled, chips=chips, model_flops=model_flops)
    rec = {
        "arch": arch, "shape": shape,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "chips": chips,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": {  # per-device bytes (XLA compiles the per-device module)
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
            "fits_hbm_16g": bool(
                (getattr(mem, "peak_memory_in_bytes", 0) or 0) < 16 * 2 ** 30
            ),
        },
        "roofline": roof.summary(),
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
    }
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="one arch id (default: all)")
    ap.add_argument("--shape", default=None, help="one shape (default: all)")
    ap.add_argument("--mesh", default="single,multi")
    ap.add_argument("--out", default="dryrun_results.json")
    ap.add_argument("--append", action="store_true")
    ap.add_argument("--remat", default="config",
                    help="override remat policy: config|none|dots|full")
    ap.add_argument("--no-zero", action="store_true",
                    help="disable ZeRO-1 optimizer-state sharding")
    args = ap.parse_args()

    meshes = {}
    if "single" in args.mesh:
        meshes["single"] = make_production_mesh(multi_pod=False)
    if "multi" in args.mesh:
        meshes["multi"] = make_production_mesh(multi_pod=True)

    cells = runnable_cells()
    if args.arch:
        from repro.configs import canonical
        cells = [c for c in cells if c[0] == canonical(args.arch)]
    if args.shape:
        cells = [c for c in cells if c[1] == args.shape]

    results = []
    if args.append and os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f).get("cells", [])
    done = {(r["arch"], r["shape"], r["mesh"]) for r in results if r.get("ok")}

    for arch, shape in cells:
        for mesh_name, mesh in meshes.items():
            mesh_id = "x".join(str(s) for s in mesh.devices.shape)
            if (arch, shape, mesh_id) in done:
                continue
            print(f"[dryrun] {arch} x {shape} on {mesh_id} ...", flush=True)
            try:
                rec = lower_cell(arch, shape, mesh, remat=args.remat,
                                 zero=not args.no_zero)
                rec["ok"] = True
                r = rec["roofline"]
                peak = rec["memory"]["peak_bytes"] or 0
                print(
                    f"  ok: compile {rec['compile_s']:.1f}s  "
                    f"dominant={r['dominant']}  "
                    f"compute={r['compute_s']:.3e}s memory={r['memory_s']:.3e}s "
                    f"coll={r['collective_s']:.3e}s  "
                    f"peak={peak/2**30:.2f}GiB",
                    flush=True,
                )
            except Exception as e:  # noqa: BLE001 -- record and continue
                rec = {"arch": arch, "shape": shape, "mesh": mesh_id,
                       "ok": False, "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-2000:]}
                print(f"  FAIL: {type(e).__name__}: {str(e)[:200]}", flush=True)
            results.append(rec)
            with open(args.out, "w") as f:
                json.dump({"cells": results,
                           "skipped": skipped_cells()}, f, indent=1)

    n_ok = sum(1 for r in results if r.get("ok"))
    print(f"[dryrun] {n_ok}/{len(results)} cells compiled; skips documented: "
          f"{len(skipped_cells())}")


if __name__ == "__main__":
    main()
