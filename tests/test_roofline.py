"""HLO static analyzer: scan multipliers, collective accounting, terms."""
import jax
import jax.numpy as jnp
import pytest

from repro.roofline.analysis import Roofline
from repro.roofline.hlo_stats import analyze, _shape_elems_bytes


def test_scan_flops_multiplied():
    def f(x, ws):
        def body(x, w):
            return jnp.tanh(x @ w), None
        x, _ = jax.lax.scan(body, x, ws)
        return x

    L, m, d = 8, 128, 256
    comp = jax.jit(f).lower(
        jax.ShapeDtypeStruct((m, d), jnp.float32),
        jax.ShapeDtypeStruct((L, d, d), jnp.float32),
    ).compile()
    c = analyze(comp.as_text())
    analytic = 2 * m * d * d * L
    assert 0.9 < c.flops / analytic < 1.3

    # cross-check: XLA's own cost_analysis undercounts by exactly 1/L
    ca = comp.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    assert ca.get("flops", 0) < c.flops / 2


def test_nested_scan():
    def f(x, ws):
        def outer(x, w):
            def inner(x, _):
                return jnp.tanh(x @ w), None
            x, _ = jax.lax.scan(inner, x, None, length=4)
            return x, None
        x, _ = jax.lax.scan(outer, x, ws)
        return x

    comp = jax.jit(f).lower(
        jax.ShapeDtypeStruct((64, 64), jnp.float32),
        jax.ShapeDtypeStruct((3, 64, 64), jnp.float32),
    ).compile()
    c = analyze(comp.as_text())
    analytic = 2 * 64 * 64 * 64 * 3 * 4
    assert 0.9 < c.flops / analytic < 1.5


def test_shape_parse():
    elems, bytes_ = _shape_elems_bytes("bf16[256,4096]{1,0}")
    assert elems == 256 * 4096 and bytes_ == elems * 2
    elems, bytes_ = _shape_elems_bytes("(s32[], f32[8,8]{1,0})")
    assert bytes_ == 4 + 64 * 4


def test_collective_parse_handcrafted():
    hlo = """
HloModule m
ENTRY %main (p: f32[64,64]) -> f32[64,64] {
  %p = f32[64,64]{1,0} parameter(0)
  %ag = f32[128,64]{1,0} all-gather(%p), replica_groups={{0,1}}, dimensions={0}
  %ar = f32[128,64]{1,0} all-reduce(%ag), to_apply=%add
  %cp = f32[128,64]{1,0} collective-permute(%ar), source_target_pairs={{0,1},{1,0}}
  ROOT %sl = f32[64,64]{1,0} slice(%cp), slice={[0:64], [0:64]}
}
"""
    c = analyze(hlo)
    assert c.coll["all-gather"] == 128 * 64 * 4
    assert c.coll["all-reduce"] == 128 * 64 * 4
    assert c.coll["collective-permute"] == 128 * 64 * 4


def test_roofline_terms_and_dominant():
    r = Roofline(flops=1e15, hbm_bytes=1e12, coll_bytes=1e10,
                 coll_by_kind={}, model_flops=2.56e17, chips=256)
    assert r.compute_s == pytest.approx(1e15 / 197e12)
    assert r.memory_s == pytest.approx(1e12 / 819e9)
    assert r.collective_s == pytest.approx(1e10 / 50e9)
    assert r.dominant == "compute"
    assert 0 < r.roofline_fraction <= 1.0 + 1e-6


def test_psum_collective_counted_with_shardmap():
    """End-to-end: a sharded psum program shows all-reduce bytes."""
    import subprocess, sys, os
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.roofline.hlo_stats import analyze
from repro.mesh import make_mesh
mesh = make_mesh((4,), ("d",))
f = jax.shard_map(lambda x: jax.lax.psum(x, "d"), mesh=mesh,
                  in_specs=P("d"), out_specs=P())
comp = jax.jit(f).lower(jax.ShapeDtypeStruct((64, 32), jnp.float32)).compile()
c = analyze(comp.as_text())
assert c.coll["all-reduce"] > 0, c.coll
print("PSUM_OK")
"""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    res = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, env=env, timeout=300)
    assert "PSUM_OK" in res.stdout, res.stdout + res.stderr
