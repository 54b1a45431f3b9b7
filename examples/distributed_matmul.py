import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=16")
"""Distributed matmul strategies on 16 fake devices (runs anywhere).

    python examples/distributed_matmul.py        # PYTHONPATH=src

Executes the solver-derived Cannon schedule, SUMMA, the ring collective
matmuls and the 2.5D pod split on a fake 16-device mesh, verifies each
against the XLA reference, and prints the per-strategy collective bytes
parsed from the compiled HLO next to the paper's analytic cost model.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.cost import torus_schedule_cost
from repro.core.schedule import cannon_schedule
from repro.mesh import make_mesh
from repro.dist import (cannon_matmul, pod25d_matmul, ring_ag_matmul,
                        ring_rs_matmul, summa_matmul)
from repro.roofline.hlo_stats import analyze


def main():
    devs = np.array(jax.devices())
    q, n = 4, 512
    mesh = make_mesh((q, q), ("x", "y"), devices=devs[: q * q])
    a = jax.random.normal(jax.random.PRNGKey(0), (n, n), jnp.bfloat16)
    b = jax.random.normal(jax.random.PRNGKey(1), (n, n), jnp.bfloat16)
    ref = (a.astype(jnp.float32) @ b.astype(jnp.float32)).astype(jnp.bfloat16)

    print(f"=== {n}x{n} matmul on a {q}x{q} fake torus ===")
    # summa staged keeps the classic all-gather signature; summa+overlap
    # decomposes each gather into the one-hop ppermute chain it can hide
    # behind the local multiplies (same words either way)
    for name, fn in (
            ("cannon", cannon_matmul),
            ("summa", functools.partial(summa_matmul, overlap=False)),
            ("summa+ov", functools.partial(summa_matmul, overlap=True)),
    ):
        f = jax.jit(functools.partial(fn, mesh=mesh, axis_x="x", axis_y="y"))
        comp = f.lower(a, b).compile()
        out = f(a, b)
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32))))
        stats = analyze(comp.as_text())
        print(f"{name:8s} err={err:.3f}  coll_bytes/dev={stats.coll_bytes:.3e} "
              f"by_kind={ {k: int(v) for k, v in stats.coll.items() if v} }")

    rep = torus_schedule_cost(cannon_schedule(q), n)
    print(f"paper cost model: cannon words/node = {rep.words_per_node:.3e} "
          f"(x2 bytes bf16 = {2*rep.words_per_node:.3e} B)")

    print("\n=== 2.5D: contraction split over a pod axis (c=2) ===")
    mesh3 = make_mesh((2, 2, 2), ("pod", "x", "y"), devices=devs[:8])
    f25 = jax.jit(functools.partial(pod25d_matmul, mesh=mesh3, pod_axis="pod"))
    out = f25(a, b)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32))))
    stats = analyze(f25.lower(a, b).compile().as_text())
    print(f"pod25d   err={err:.3f}  coll_bytes/dev={stats.coll_bytes:.3e}")

    print("\n=== ring collective matmuls (1-D torus solutions) ===")
    mesh_r = make_mesh((8,), ("t",), devices=devs[:8])
    s, d, fdim = 512, 256, 256
    x = jax.random.normal(jax.random.PRNGKey(2), (s, d), jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(3), (d, fdim), jnp.bfloat16)
    ag = jax.jit(jax.shard_map(
        lambda xl, wl: ring_ag_matmul(xl, wl, "t"), mesh=mesh_r,
        in_specs=(P("t", None), P(None, "t")), out_specs=P(None, "t")))
    out = ag(x, w)
    ref2 = (x.astype(jnp.float32) @ w.astype(jnp.float32))
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref2)))
    stats = analyze(ag.lower(x, w).compile().as_text())
    print(f"ring_ag  err={err:.3f}  coll_bytes/dev={stats.coll_bytes:.3e} "
          f"(collective-permute chain, overlappable)")


if __name__ == "__main__":
    main()
