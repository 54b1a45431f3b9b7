"""Ring-TP MLP block == GSPMD reference, and its HLO uses permute chains."""
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.layers.ring_blocks import ring_mlp, gspmd_mlp_reference
from repro.mesh import make_mesh
from repro.roofline.hlo_stats import analyze

devs = np.array(jax.devices())
mesh = make_mesh((4,), ("model",), devices=devs)
B, S, D, F = 2, 32, 16, 48
key = jax.random.PRNGKey(0)
x = jax.random.normal(key, (B, S, D), jnp.float32)
p = {
    "w_gate": jax.random.normal(jax.random.PRNGKey(1), (D, F), jnp.float32) * 0.1,
    "w_up": jax.random.normal(jax.random.PRNGKey(2), (D, F), jnp.float32) * 0.1,
    "w_down": jax.random.normal(jax.random.PRNGKey(3), (F, D), jnp.float32) * 0.1,
}
ref = gspmd_mlp_reference(p, x)

f = jax.jit(jax.shard_map(
    lambda xl, g, u, d: ring_mlp({"w_gate": g, "w_up": u, "w_down": d}, xl),
    mesh=mesh,
    in_specs=(P(None, "model", None), P(None, "model"), P(None, "model"),
              P("model", None)),
    out_specs=P(None, "model", None),
))
out = f(x, p["w_gate"], p["w_up"], p["w_down"])
err = float(jnp.max(jnp.abs(out - ref)))
assert err < 2e-5, err

# the prescribed schedule: permute chains, no all-gather/all-reduce ops
txt = f.lower(x, p["w_gate"], p["w_up"], p["w_down"]).compile().as_text()
st = analyze(txt)
assert st.coll["collective-permute"] > 0, st.coll
assert st.coll["all-gather"] == 0 and st.coll["all-reduce"] == 0, st.coll
print("RING_BLOCK_OK")
"""


@pytest.mark.timeout(600)
def test_ring_mlp_matches_gspmd_and_uses_permutes():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    res = subprocess.run([sys.executable, "-c", _SCRIPT], capture_output=True,
                         text=True, env=env, timeout=590)
    assert "RING_BLOCK_OK" in res.stdout, res.stdout[-3000:] + res.stderr[-3000:]
