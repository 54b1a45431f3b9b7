"""Distributed matmul strategies vs references on 8 fake devices.

Runs in a subprocess so the main pytest process keeps the default 1-device
view (the dry-run owns the 512-device configuration)."""
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import functools
import jax, jax.numpy as jnp, numpy as np
from repro.mesh import make_mesh
from jax.sharding import PartitionSpec as P
from repro.dist import (cannon_matmul, summa_matmul, pod25d_matmul,
                        ring_ag_matmul, ring_rs_matmul)

devs = np.array(jax.devices())
mesh22 = make_mesh((2, 2), ("x", "y"), devices=devs[:4])
M, K, N = 32, 24, 16
a = jax.random.normal(jax.random.PRNGKey(0), (M, K), jnp.float32)
b = jax.random.normal(jax.random.PRNGKey(1), (K, N), jnp.float32)
ref = a @ b
tol = 2e-5

c = jax.jit(functools.partial(cannon_matmul, mesh=mesh22, axis_x="x", axis_y="y"))(a, b)
assert float(jnp.max(jnp.abs(c - ref))) < tol, "cannon"

c = jax.jit(functools.partial(summa_matmul, mesh=mesh22, axis_x="x", axis_y="y"))(a, b)
assert float(jnp.max(jnp.abs(c - ref))) < tol, "summa"

mesh_pod = make_mesh((2,), ("pod",), devices=devs[:2])
c = jax.jit(functools.partial(pod25d_matmul, mesh=mesh_pod, pod_axis="pod"))(a, b)
assert float(jnp.max(jnp.abs(c - ref))) < tol, "pod25d"

mesh_r = make_mesh((4,), ("t",), devices=devs[:4])
S, D, F = 16, 8, 12
x = jax.random.normal(jax.random.PRNGKey(2), (S, D), jnp.float32)
w = jax.random.normal(jax.random.PRNGKey(3), (D, F), jnp.float32)
ag = jax.jit(jax.shard_map(lambda xl, wl: ring_ag_matmul(xl, wl, "t"),
    mesh=mesh_r, in_specs=(P("t", None), P(None, "t")), out_specs=P(None, "t")))(x, w)
assert float(jnp.max(jnp.abs(ag - x @ w))) < tol, "ring_ag"

y = jax.random.normal(jax.random.PRNGKey(4), (S, F), jnp.float32)
w2 = jax.random.normal(jax.random.PRNGKey(5), (F, D), jnp.float32)
rs = jax.jit(jax.shard_map(lambda yl, wl: ring_rs_matmul(yl, wl, "t"),
    mesh=mesh_r, in_specs=(P(None, "t"), P("t", None)), out_specs=P("t", None)))(y, w2)
assert float(jnp.max(jnp.abs(rs - y @ w2))) < tol, "ring_rs"

# batched (3D) ring matmul, as used by the transformer layers
xb = jax.random.normal(jax.random.PRNGKey(6), (2, S, D), jnp.float32)
agb = jax.jit(jax.shard_map(lambda xl, wl: ring_ag_matmul(xl, wl, "t"),
    mesh=mesh_r, in_specs=(P(None, "t", None), P(None, "t")),
    out_specs=P(None, None, "t")))(xb, w)
assert float(jnp.max(jnp.abs(agb - xb @ w))) < tol, "ring_ag_batched"

# 3-axis production-style mesh: 2.5D over pod composed with in-layer summa
mesh3 = make_mesh((2, 2, 2), ("pod", "x", "y"), devices=devs[:8])
c = jax.jit(functools.partial(pod25d_matmul, mesh=mesh3, pod_axis="pod"))(a, b)
assert float(jnp.max(jnp.abs(c - ref))) < tol, "pod25d_3axis"

print("DIST_SELFTEST_OK")
"""


@pytest.mark.timeout(600)
def test_distributed_strategies_subprocess():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(_root(), "src")
    res = subprocess.run(
        [sys.executable, "-c", _SCRIPT], capture_output=True, text=True,
        env=env, timeout=590,
    )
    assert "DIST_SELFTEST_OK" in res.stdout, (
        f"stdout:\n{res.stdout[-3000:]}\nstderr:\n{res.stderr[-3000:]}"
    )


def _root():
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
