"""Hierarchical fat-tree lowering: planner, conformance, and calibration.

Planning/trace/ranking tests run on duck-typed meshes (no jax execution);
the subprocess job forces 16 host devices and asserts the executed
program's collectives equal the schedule trace and the analytic per-level
words, that outputs match jnp.matmul, and that an injected wrong-exchange
mutation is caught at the interceptor.
"""
import dataclasses
import math
import os
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

from repro.dist.api import estimate
from repro.obs.calibrate import _assemble_links
from repro.obs.profile import LinkParams, MachineProfile
from repro.plan import build_plan, mesh_candidates, rank_mesh_strategies
from repro.verify import ConformanceError, check, trace_plan, tree_level_words
from repro.verify.conformance import _check_structure, _xor_mask


def fake_mesh(sizes, names):
    total = math.prod(sizes)
    return SimpleNamespace(
        axis_names=tuple(names),
        shape=dict(zip(names, sizes)),
        size=total,
        devices=np.array([SimpleNamespace(id=i, platform="cpu")
                          for i in range(total)]),
    )


# ---------------------------------------------------------------------------
# planner: hierarchical axis roles, grid, padding, candidacy
# ---------------------------------------------------------------------------


def test_fattree_plan_reifies_hierarchy():
    mesh = fake_mesh((4, 2, 2), ("tree", "x", "y"))
    plan = build_plan(24, 24, 24, mesh=mesh, strategy="fattree",
                      use_cache=False)
    assert plan.grid == (4, 2, 2)
    assert plan.axes == ("tree", "x", "y")
    assert plan.axis_roles == (("tree", "tree"), ("x", "row"), ("y", "col"))
    # A is (row, tree x col)-sharded; k must pad to s*qx*qy on both operands
    assert plan.pad_a == (2, 16) and plan.pad_b == (16, 8)
    assert plan.replication == 1 and not plan.overlap
    assert plan.cost.strategy == "fattree"


def test_fattree_candidacy_needs_power_of_two_tree_axis():
    good = mesh_candidates(fake_mesh((2, 2, 2), ("tree", "x", "y")))
    assert "fattree" in good and "pod25d" in good
    bad = mesh_candidates(fake_mesh((3, 2, 2), ("tree", "x", "y")))
    assert "fattree" not in bad and "pod25d" in bad
    flat = mesh_candidates(fake_mesh((2, 2), ("x", "y")))
    assert "fattree" not in flat


def test_fattree_forced_on_bad_tree_axis_raises():
    mesh = fake_mesh((3, 2, 2), ("tree", "x", "y"))
    with pytest.raises(ValueError, match="power-of-two tree axis"):
        build_plan(24, 24, 24, mesh=mesh, strategy="fattree",
                   use_cache=False)
    with pytest.raises(ValueError, match=">= 3 axes"):
        build_plan(24, 24, 24, mesh=fake_mesh((2, 2), ("x", "y")),
                   strategy="fattree", use_cache=False)


def test_other_strategies_carry_axis_roles_too():
    mesh3 = fake_mesh((2, 2, 2), ("pod", "x", "y"))
    assert build_plan(24, 24, 24, mesh=mesh3, strategy="pod25d",
                      use_cache=False).axis_roles == \
        (("pod", "pod"), ("x", "row"), ("y", "col"))
    ring = build_plan(24, 24, 24, mesh=fake_mesh((4,), ("t",)),
                      strategy="ring_ag", use_cache=False)
    assert ring.axis_roles == (("t", "ring"),)


# ---------------------------------------------------------------------------
# conformance: structure predicate + the per-level triangle (static legs)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", [(2, 2, 2), (4, 2, 2), (8, 2, 2),
                                   (4, 1, 1), (2, 2, 4)])
def test_fattree_static_conformance(shape):
    """Structure + cost + per-level triangle on the virtual topology,
    including multi-level trees (s = 4, 8) and degenerate pods."""
    mesh = fake_mesh(shape, ("tree", "x", "y"))
    plan = build_plan(24, 24, 24, mesh=mesh, strategy="fattree",
                      use_cache=False)
    rep = check(plan)
    assert rep.strategy == "fattree" and rep.words_per_node > 0


def test_tree_level_words_closed_form():
    """Level l of an s-pod tree carries (s / 2^(l-1) - 1) * m * k words --
    the Gray-mask step count -- with exactly m*k across the root."""
    mesh = fake_mesh((8, 2, 2), ("tree", "x", "y"))
    plan = build_plan(16, 16, 256, mesh=mesh, strategy="fattree",
                      use_cache=False)
    levels = tree_level_words(trace_plan(plan))
    mk = 16 * 256
    assert levels == {1: 7 * mk, 2: 3 * mk, 3: 1 * mk}
    est = estimate("fattree", 16, 16, 256, 32, dtype_bytes=1,
                   grid=(8, 2, 2), axes=("tree", "x", "y"))
    assert est.tree_level_words == (7.0 * mk, 3.0 * mk, 1.0 * mk)


def test_xor_mask_predicate():
    from repro.core.fattree import tree_exchange_perm
    from repro.verify.trace import canonical_perm

    for s in (2, 4, 8):
        for t in range(s - 1):
            perm = canonical_perm(tree_exchange_perm(s, t))
            assert _xor_mask(perm, s) == t ^ (t + 1)
    # a ring translation is not an XOR involution (for s > 2)
    ring = canonical_perm([(d, (d + 1) % 4) for d in range(4)])
    assert _xor_mask(ring, 4) is None
    assert _xor_mask((), 4) is None


def test_structure_rejects_non_involution_exchange():
    """A movement perm that is a valid bijection but not an XOR-mask
    involution (a Gray-walk break) must fail the structure leg."""
    mesh = fake_mesh((4, 2, 2), ("tree", "x", "y"))
    plan = build_plan(24, 24, 24, mesh=mesh, strategy="fattree",
                      use_cache=False)
    trace = trace_plan(plan)
    ring = tuple((d, (d + 1) % 4) for d in range(4))
    recs = list(trace.records)
    idx = next(i for i, r in enumerate(recs) if r.phase == "movement")
    recs[idx] = dataclasses.replace(recs[idx], perm=ring)
    bad = dataclasses.replace(trace, records=tuple(recs))
    with pytest.raises(ConformanceError, match="XOR-mask involution"):
        _check_structure(plan, bad)


# ---------------------------------------------------------------------------
# calibration: DCN link class + the hierarchical ranking flip
# ---------------------------------------------------------------------------


def test_assemble_links_splits_dcn_from_ici():
    def samples(axis, alpha, bw):
        sizes = [1 << 14, 1 << 17, 1 << 20]
        return (axis, sizes, [alpha + s / bw for s in sizes])

    links = dict(_assemble_links(
        [samples("tree", 1e-3, 1e8), samples("x", 1e-6, 1e11),
         samples("y", 1e-6, 1e11)],
        tree_axes=("tree",)))
    assert set(links) == {"ici", "dcn", "axis:tree", "axis:x", "axis:y"}
    # the slow inter-pod link must not contaminate the pooled ICI fit
    assert links["ici"].alpha_s < 1e-4 < links["dcn"].alpha_s
    assert links["axis:tree"].alpha_s == pytest.approx(1e-3, rel=1e-3)
    # all-tree meshes still produce a usable pooled "ici" (= the dcn fit)
    only_tree = dict(_assemble_links([samples("tree", 1e-3, 1e8)],
                                     tree_axes=("tree",)))
    assert only_tree["ici"] == only_tree["dcn"]
    # no tree axes: identical to the historical pooled behavior
    flat = dict(_assemble_links([samples("x", 1e-6, 1e11)]))
    assert set(flat) == {"ici", "axis:x"}


def test_slow_tree_profile_flips_ranking_to_fattree():
    """The acceptance-criteria regression pin: with a latency-skewed tree
    axis (DCN-ish: 1 s alpha, 1 GB/s) and free intra-pod links, the
    calibrated ranking must prefer the hierarchical plan -- it crosses the
    tree axis once per super-step ((s-1) messages of A shards) while the
    flat strategies either reduce C over it or flatten it into their ring.
    The analytic (uncalibrated) ranking must NOT prefer it, or the test
    would pass vacuously."""
    mesh = fake_mesh((2, 2, 2), ("tree", "x", "y"))
    fast = LinkParams(alpha_s=0.0, bw_bytes_per_s=1e12)
    slow = LinkParams(alpha_s=1.0, bw_bytes_per_s=1e9)
    skewed = MachineProfile(
        device_kind="cpu", peak_flops=1e18,
        links=(("ici", slow), ("dcn", slow), ("axis:tree", slow),
               ("axis:x", fast), ("axis:y", fast)))
    m, n, k = 64, 32, 512
    assert rank_mesh_strategies(m, n, k, mesh)[0].strategy != "fattree"
    ranked = rank_mesh_strategies(m, n, k, mesh, profile=skewed)
    assert ranked[0].strategy == "fattree"
    # and the win is structural, not a tie: one tree round vs >= 2
    runner_up = skewed.seconds(ranked[1])
    assert skewed.seconds(ranked[0]) < 0.75 * runner_up


# ---------------------------------------------------------------------------
# executed program: real devices, interceptor == trace == analytics
# ---------------------------------------------------------------------------

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import jax, jax.numpy as jnp, numpy as np

from repro.dist import fattree_matmul
from repro.plan import build_plan, execute_plan
from repro.mesh import make_mesh
from repro.verify import (ConformanceError, check, compare_records,
                          measure_plan, trace_plan)

devs = np.array(jax.devices())
rng = np.random.default_rng(0)

# numeric + measured-conformance cells: square, ragged, batched, bf16
mesh8 = make_mesh((2, 2, 2), ("tree", "x", "y"), devices=devs[:8])
for kwargs in ({"m": 24, "n": 24, "k": 24},
               {"m": 13, "n": 7, "k": 11},
               {"m": 5, "n": 8, "k": 12, "batch": (3,)},
               {"m": 16, "n": 16, "k": 16, "a_dtype": jnp.bfloat16,
                "b_dtype": jnp.bfloat16}):
    m, n, k = kwargs.pop("m"), kwargs.pop("n"), kwargs.pop("k")
    batch = kwargs.get("batch", ())
    dt = kwargs.get("a_dtype", jnp.float32)
    plan = build_plan(m, n, k, mesh=mesh8, strategy="fattree", **kwargs)
    a = jnp.asarray(rng.normal(size=batch + (m, k)), dt)
    b = jnp.asarray(rng.normal(size=(k, n)), dt)
    out = execute_plan(plan, a, b)
    ref = jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32))
    assert jnp.allclose(out.astype(jnp.float32), ref,
                        atol=2e-2, rtol=2e-2), (m, n, k)
    check(plan, measure=True)

# multi-level tree: 4 pods x (2 x 2), measured
mesh16 = make_mesh((4, 2, 2), ("tree", "x", "y"), devices=devs[:16])
plan16 = build_plan(24, 24, 24, mesh=mesh16, strategy="fattree",
                    use_cache=False)
check(plan16, measure=True)

# facade
a = jnp.ones((16, 32)); b = jnp.ones((32, 8))
assert jnp.allclose(fattree_matmul(a, b, mesh=mesh8), a @ b)

# executed wrong-exchange mutation: break the Gray walk in the lowering
# only (the trace keeps the true program) -- the interceptor must diverge
import repro.dist.fattree as df
orig = df.tree_exchange_perm
df.tree_exchange_perm = lambda s, t: tuple((d, (d + 1) % s) for d in range(s))
try:
    cap = measure_plan(plan16)
finally:
    df.tree_exchange_perm = orig
try:
    compare_records(trace_plan(plan16).records, cap.records)
    raise SystemExit("executed exchange mutation not caught")
except ConformanceError:
    pass

print("FATTREE_EXEC_OK")
"""


@pytest.mark.timeout(600)
def test_fattree_execution_subprocess():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(_root(), "src")
    res = subprocess.run(
        [sys.executable, "-c", _SCRIPT], capture_output=True, text=True,
        env=env, timeout=590,
    )
    assert "FATTREE_EXEC_OK" in res.stdout, (
        f"stdout:\n{res.stdout[-3000:]}\nstderr:\n{res.stderr[-3000:]}"
    )


def _root():
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
