"""Plan engine vs legacy executors on fake devices (acceptance criteria).

Asserts, per strategy on a 4-device CPU mesh (8 devices for the 2.5D
family):

  * ``build_plan`` -> ``lower_shard_map``, ``symmetric_matmul(strategy=...)``
    and the strategy entry points (``cannon_matmul``, ...) all produce
    bitwise-identical outputs -- the entry points are facades over the plan
    engine, so this pins that every dispatch route builds the same plan
    (axes defaults, padding, specs), while the XLA-oracle comparison below
    pins the lowering's numerics themselves;
  * batched inputs (leading batch dims, none of which the pre-plan
    executors handled) and ragged m/n/k match the XLA oracle;
  * bf16 in / fp32 accumulation out holds on every strategy;
  * a repeated identical call hits the plan cache (stats counter);
  * the layer library routes through the plan engine inside
    ``planned_matmuls``.

Runs in a subprocess so the main pytest process keeps the 1-device view.
"""
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import functools
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P

from repro.dist import (cannon_matmul, summa_matmul, pod25d_matmul,
                        cannon25d_matmul, symmetric_matmul)
from repro import plan as planlib
from repro.plan import build_plan, execute_plan, lower_shard_map
from repro.mesh import make_mesh

devs = np.array(jax.devices())
mesh22 = make_mesh((2, 2), ("x", "y"), devices=devs[:4])
mesh1d = make_mesh((4,), ("t",), devices=devs[:4])
mesh3 = make_mesh((2, 2, 2), ("pod", "x", "y"), devices=devs[:8])

M, K, N = 32, 24, 16
a = jax.random.normal(jax.random.PRNGKey(0), (M, K), jnp.float32)
b = jax.random.normal(jax.random.PRNGKey(1), (K, N), jnp.float32)
ref = np.asarray(a) @ np.asarray(b)
tol = 3e-5

legacy = {
    "cannon": (mesh22, functools.partial(cannon_matmul, mesh=mesh22)),
    "summa": (mesh22, functools.partial(summa_matmul, mesh=mesh22)),
    "pod25d": (mesh3, functools.partial(pod25d_matmul, mesh=mesh3)),
    "cannon25d": (mesh3, functools.partial(cannon25d_matmul, mesh=mesh3)),
    "ring_ag": (mesh1d, None),
    "ring_rs": (mesh1d, None),
}

for strat, (mesh, legacy_fn) in legacy.items():
    via_sym = symmetric_matmul(a, b, mesh=mesh, strategy=strat)
    plan = build_plan(M, N, K, mesh=mesh, strategy=strat,
                      a_dtype=a.dtype, b_dtype=b.dtype)
    via_plan = lower_shard_map(plan)(a, b)
    assert np.array_equal(np.asarray(via_sym), np.asarray(via_plan)), \
        f"{strat}: symmetric_matmul != lower_shard_map(build_plan)"
    if legacy_fn is not None:
        via_legacy = legacy_fn(a, b)
        assert np.array_equal(np.asarray(via_legacy), np.asarray(via_plan)), \
            f"{strat}: legacy executor != plan lowering"
    err = float(np.max(np.abs(np.asarray(via_plan) - ref)))
    assert err < tol, f"{strat}: err {err} vs oracle"

# --- flattened multi-axis ring: the default cost-model outcome on 2-D
# meshes with a dominant contraction dim must actually execute ------------
from repro.dist.api import choose
ak = jax.random.normal(jax.random.PRNGKey(8), (16, 512), jnp.float32)
bk = jax.random.normal(jax.random.PRNGKey(9), (512, 16), jnp.float32)
assert choose(16, 16, 512, mesh=mesh22) == "ring_rs"
out = symmetric_matmul(ak, bk, mesh=mesh22)  # auto-dispatch, tuple ring axis
err = float(np.max(np.abs(np.asarray(out) - np.asarray(ak) @ np.asarray(bk))))
assert err < 2e-4, f"flattened-ring auto dispatch: err {err}"
out_ag = symmetric_matmul(a, b, mesh=mesh22, strategy="ring_ag")
err = float(np.max(np.abs(np.asarray(out_ag) - ref)))
assert err < tol, f"flattened ring_ag on 2-axis mesh: err {err}"

# --- plan cache: second identical dispatch must hit -------------------------
planlib.cache_clear()
symmetric_matmul(a, b, mesh=mesh22, strategy="cannon")
s0 = planlib.cache_stats()
symmetric_matmul(a, b, mesh=mesh22, strategy="cannon")
s1 = planlib.cache_stats()
assert s1["hits"] == s0["hits"] + 1 and s1["misses"] == s0["misses"], (s0, s1)

# --- batched inputs through every strategy ----------------------------------
B, S = 3, 10
xb = jax.random.normal(jax.random.PRNGKey(2), (B, S, K), jnp.float32)
bref = np.einsum("bmk,kn->bmn", np.asarray(xb), np.asarray(b))
for strat, (mesh, _) in legacy.items():
    out = symmetric_matmul(xb, b, mesh=mesh, strategy=strat)
    assert out.shape == (B, S, N), (strat, out.shape)
    err = float(np.max(np.abs(np.asarray(out) - bref)))
    assert err < tol, f"batched {strat}: err {err}"
# batched == hand-folded, bitwise (folding is the defined lowering)
flat = symmetric_matmul(xb.reshape(B * S, K), b, mesh=mesh22,
                        strategy="cannon").reshape(B, S, N)
bat = symmetric_matmul(xb, b, mesh=mesh22, strategy="cannon")
assert np.array_equal(np.asarray(bat), np.asarray(flat))
# batched-both
b3 = jax.random.normal(jax.random.PRNGKey(3), (B, K, N), jnp.float32)
out = symmetric_matmul(xb, b3, mesh=mesh22, strategy="cannon")
err = float(np.max(np.abs(np.asarray(out)
                          - np.einsum("bmk,bkn->bmn", np.asarray(xb),
                                      np.asarray(b3)))))
assert err < tol, f"batched-both: {err}"

# --- ragged shapes (m/n/k not divisible by any mesh side) -------------------
ar = jax.random.normal(jax.random.PRNGKey(4), (13, 11), jnp.float32)
br = jax.random.normal(jax.random.PRNGKey(5), (11, 7), jnp.float32)
rref = np.asarray(ar) @ np.asarray(br)
for strat, (mesh, _) in legacy.items():
    out = symmetric_matmul(ar, br, mesh=mesh, strategy=strat)
    assert out.shape == (13, 7)
    err = float(np.max(np.abs(np.asarray(out) - rref)))
    assert err < tol, f"ragged {strat}: err {err}"

# --- dtype promotion: bf16 in, fp32 accumulate out --------------------------
abf, bbf = a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
for strat, (mesh, _) in legacy.items():
    out = symmetric_matmul(abf, bbf, mesh=mesh, strategy=strat,
                           out_dtype=jnp.float32)
    assert out.dtype == jnp.float32, (strat, out.dtype)
    err = float(np.max(np.abs(np.asarray(out) - ref)))
    assert err < 0.5, f"bf16 {strat}: err {err}"
    # default out dtype follows the operands
    assert symmetric_matmul(abf, bbf, mesh=mesh,
                            strategy=strat).dtype == jnp.bfloat16

# --- layers route through the plan engine inside planned_matmuls ------------
from repro.layers.mlp import mlp, mlp_params
from repro.plan import planned_matmuls

p = mlp_params(jax.random.PRNGKey(6), 16, 32, dtype=jnp.float32)
x3 = jax.random.normal(jax.random.PRNGKey(7), (2, 8, 16), jnp.float32)
base = mlp(p, x3)
planlib.cache_clear()
with planned_matmuls(mesh1d):
    planned = mlp(p, x3)
assert planlib.cache_stats()["misses"] > 0, "layers did not consult the plan"
err = float(np.max(np.abs(np.asarray(planned) - np.asarray(base))))
assert err < 1e-4, f"planned mlp diverges: {err}"

print("PLAN_EXEC_OK")
"""


@pytest.mark.timeout(600)
def test_plan_execution_consistency_subprocess():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(_root(), "src")
    res = subprocess.run(
        [sys.executable, "-c", _SCRIPT], capture_output=True, text=True,
        env=env, timeout=590,
    )
    assert "PLAN_EXEC_OK" in res.stdout, (
        f"stdout:\n{res.stdout[-3000:]}\nstderr:\n{res.stderr[-3000:]}"
    )


def _root():
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
