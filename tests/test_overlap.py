"""Overlapped collective-matmul execution (double-buffered lowering family).

Pins the PR-7 acceptance criteria:

  * ``overlap_capability`` / ``estimate(overlap=...)`` derive the
    overlapped flag from the lowering's capability, not the strategy name,
    and the cannon-vs-summa ranking flip that follows is pinned;
  * ``build_plan`` reifies the resolved variant on ``SchedulePlan.overlap``
    (== ``plan.cost.overlapped``), caches staged/overlapped twins
    separately, and rejects impossible requests;
  * an overlapped plan moves the identical collective multiset as its
    staged twin (trace level here; the executed interceptor/obs legs run
    in the forced-host subprocess test), and both variants pass
    ``conformance.check``;
  * per-axis ``axis:{name}`` α–β link classes price ``comm_by_axis`` terms
    (pooled fallback preserves the analytic identity);
  * prefetch collectives carry the ``comm="hidden"`` tag through obs;
  * the double-buffer rotation never reorders the movement homomorphism
    (hypothesis property over the Cannon family);
  * ``benchmarks/run.py --report`` renders bench-row lists with null
    ``us_per_call`` without crashing.
"""
import importlib.util
import json
import math
import os
import subprocess
import sys
from collections import Counter
from types import SimpleNamespace

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import obs
from repro.core.schedule import cannon_schedule
from repro.dist.api import estimate, overlap_capability
from repro.obs.profile import LinkParams, MachineProfile, default_profile
from repro.plan import build_plan, rank_mesh_strategies
from repro.plan.cache import plan_cache
from repro.plan.ir import TorusProgram
from repro.verify.conformance import check, memory_bound_words
from repro.verify.trace import trace_plan


def fake_mesh(sizes, names):
    total = math.prod(sizes)
    return SimpleNamespace(
        axis_names=tuple(names),
        shape=dict(zip(names, sizes)),
        size=total,
        devices=np.array([SimpleNamespace(id=i, platform="cpu")
                          for i in range(total)]),
    )


# --- capability / estimate derivation ----------------------------------------


def test_overlap_capability_by_lowering():
    assert overlap_capability("cannon")
    assert overlap_capability("summa")
    assert overlap_capability("cannon25d")
    assert overlap_capability("ring_ag") and overlap_capability("ring_rs")
    # pod25d: only the 3-axis (SUMMA-in-layer) program double-buffers
    assert overlap_capability("pod25d", grid=(2, 2, 2))
    assert overlap_capability("pod25d", grid=None)
    assert not overlap_capability("pod25d", grid=(4,))
    for s in ("xla_ag", "xla_rs", "local"):
        assert not overlap_capability(s)


def test_estimate_overlap_derived_not_name_keyed():
    # summa's decomposed-gather lowering makes it overlapped by default now
    e = estimate("summa", 4096, 4096, 4096, 16)
    assert e.overlapped
    assert e.total_s == max(e.compute_s, e.comm_s)
    staged = estimate("summa", 4096, 4096, 4096, 16, overlap=False)
    assert not staged.overlapped
    assert staged.total_s == staged.compute_s + staged.comm_s
    # identical word counts either way -- overlap is an execution property
    assert staged.comm_bytes == e.comm_bytes and staged.msgs == e.msgs
    # incapable lowerings cannot be priced overlapped
    with pytest.raises(ValueError, match="no overlapped lowering"):
        estimate("xla_ag", 1024, 1024, 1024, 8, overlap=True)
    with pytest.raises(ValueError, match="no overlapped lowering"):
        estimate("pod25d", 1024, 1024, 1024, 4, grid=(4,), overlap=True)
    assert not estimate("pod25d", 1024, 1024, 1024, 4, grid=(4,)).overlapped


def test_latency_profile_ranking_flip_capability_derived():
    """Regression pin for the old strategy-name overlap rule.  On a
    latency-dominated 4x4 machine, summa's 6 rounds beat cannon's 8 only
    because summa's chain lowering now prices as overlapped: max(3, 6) = 6
    < max(3, 8) = 8.  Under the old rule (summa staged) summa would pay
    3 + 6 = 9 > 8 and cannon would win -- the flip this test pins."""
    mesh = fake_mesh((4, 4), ("x", "y"))
    m = n = k = 4096
    prof = MachineProfile(
        device_kind="synth", peak_flops=2.86e9,  # compute ~= 3.0 s/device
        links=(("ici", LinkParams(1.0, 1e18)),))
    ranked = rank_mesh_strategies(m, n, k, mesh, profile=prof)
    assert ranked[0].strategy == "summa"
    by = {e.strategy: e for e in ranked}
    assert by["summa"].overlapped and by["cannon"].overlapped
    import dataclasses

    summa_staged = dataclasses.replace(by["summa"], overlapped=False)
    # the old rule's ordering: staged summa loses to overlapped cannon
    assert prof.seconds(summa_staged) > prof.seconds(by["cannon"])
    assert prof.seconds(by["summa"]) < prof.seconds(by["cannon"])


# --- build_plan resolution ----------------------------------------------------


def test_build_plan_reifies_overlap_capability():
    mesh = fake_mesh((2, 4), ("x", "y"))
    plan = build_plan(64, 64, 64, mesh=mesh, strategy="summa")
    assert plan.overlap            # strict max < sum win on the cost model
    assert plan.cost.overlapped == plan.overlap
    staged = build_plan(64, 64, 64, mesh=mesh, strategy="summa",
                        overlap=False)
    assert not staged.overlap and not staged.cost.overlapped
    assert plan_cache.info()["misses"] == 2  # twins cached separately
    again = build_plan(64, 64, 64, mesh=mesh, strategy="summa")
    assert again is plan and plan_cache.info()["hits"] == 1


def test_build_plan_default_cannon_overlapped_when_model_predicts_win():
    """Acceptance pin: ``max(compute, comm) < compute + comm`` holds for
    the default cannon cell (both terms positive), so the planner picks
    the double-buffered body."""
    mesh = fake_mesh((4, 4), ("x", "y"))
    plan = build_plan(256, 256, 256, mesh=mesh, strategy="cannon")
    assert plan.overlap
    e = plan.cost
    assert e.compute_s > 0 and e.comm_s > 0
    assert max(e.compute_s, e.comm_s) < e.compute_s + e.comm_s
    import dataclasses

    prof = default_profile()
    staged = dataclasses.replace(e, overlapped=False)
    over = dataclasses.replace(e, overlapped=True)
    assert prof.seconds(over) < prof.seconds(staged)


def test_build_plan_rejects_impossible_overlap_requests():
    with pytest.raises(ValueError, match="no overlapped lowering"):
        build_plan(64, 64, 64, mesh=None, overlap=True)
    mesh1d = fake_mesh((4,), ("t",))
    with pytest.raises(ValueError, match="intrinsically overlapped"):
        build_plan(64, 64, 64, mesh=mesh1d, strategy="ring_ag",
                   overlap=False)
    assert build_plan(64, 64, 64, mesh=mesh1d, strategy="ring_ag").overlap
    pod1d = fake_mesh((4,), ("pod",))
    with pytest.raises(ValueError, match="no overlapped lowering"):
        build_plan(64, 64, 64, mesh=pod1d, strategy="pod25d", axes=("pod",),
                   overlap=True)


# --- trace equivalence: overlapped twin moves the same words ------------------

TWIN_CELLS = (
    ("cannon", (3, 3), ("x", "y")),
    ("cannon", (4, 4), ("x", "y")),
    ("summa", (2, 4), ("x", "y")),
    ("summa", (4, 4), ("x", "y")),
    ("cannon25d", (2, 2, 2), ("pod", "x", "y")),
    ("pod25d", (2, 2, 2), ("pod", "x", "y")),
)


@pytest.mark.parametrize("strategy,sizes,names", TWIN_CELLS)
def test_overlapped_twin_same_movement_words_and_conformance(
        strategy, sizes, names):
    mesh = fake_mesh(sizes, names)
    staged = build_plan(24, 24, 24, mesh=mesh, strategy=strategy,
                        axes=names, overlap=False)
    over = build_plan(24, 24, 24, mesh=mesh, strategy=strategy,
                      axes=names, overlap=True)
    assert not staged.overlap and over.overlap
    ts, to = trace_plan(staged), trace_plan(over)
    # the movement homomorphism is an invariant of the variant choice
    assert ts.movement_words() == to.movement_words()
    if strategy in ("cannon", "cannon25d"):
        # torus double-buffering is a pure dataflow reorder: identical
        # records, not merely identical words
        assert Counter(r.key for r in ts.records) == \
            Counter(r.key for r in to.records)
    else:
        # decomposed gathers: all_gather records become one-hop ppermutes
        moved = [r for r in to.records if r.phase == "gather"]
        assert moved and all(r.kind == "ppermute" for r in moved)
    # both variants conform (structure + cost + memory bound)
    check(staged)
    check(over)
    assert to.peak_node_words <= memory_bound_words(over) + 1e-6


def test_overlapped_torus_peak_counts_double_buffers():
    mesh = fake_mesh((4, 4), ("x", "y"))
    staged = build_plan(32, 32, 32, mesh=mesh, strategy="cannon",
                       overlap=False)
    over = build_plan(32, 32, 32, mesh=mesh, strategy="cannon",
                      overlap=True)
    a_blk = b_blk = (32 // 4) * (32 // 4)
    assert trace_plan(over).peak_node_words == \
        trace_plan(staged).peak_node_words + a_blk + b_blk


# --- per-axis α–β pricing -----------------------------------------------------


def test_estimate_comm_by_axis_terms_sum_to_totals():
    mesh = fake_mesh((2, 4), ("x", "y"))
    ranked = rank_mesh_strategies(512, 512, 512, mesh)
    summa = next(e for e in ranked if e.strategy == "summa")
    assert {ax for ax, _, _ in summa.comm_by_axis} == {"x", "y"}
    assert sum(b for _, b, _ in summa.comm_by_axis) == \
        pytest.approx(summa.comm_bytes)
    assert sum(ms for _, _, ms in summa.comm_by_axis) == summa.msgs
    # without axis roles the estimate carries no terms
    assert estimate("summa", 512, 512, 512, 8).comm_by_axis == ()


def test_per_axis_profile_prices_each_axis():
    """m >> n: almost all bytes are A panels, which ride the y axis.  A
    profile with a slow axis:y must price the cell higher than one with a
    slow axis:x -- the pooled model cannot tell them apart."""
    mesh = fake_mesh((2, 4), ("x", "y"))
    ranked = rank_mesh_strategies(8192, 64, 1024, mesh)
    summa = next(e for e in ranked if e.strategy == "summa")
    a_bytes = dict((ax, b) for ax, b, _ in summa.comm_by_axis)
    assert a_bytes["y"] > a_bytes["x"]
    fast, slow = LinkParams(0.0, 1e12), LinkParams(0.0, 1e9)

    def prof(x_link, y_link):
        return MachineProfile(
            device_kind="synth", peak_flops=1e18,
            links=(("axis:x", x_link), ("axis:y", y_link),
                   ("ici", LinkParams(0.0, 1e12))))

    slow_y = prof(fast, slow).seconds(summa)
    slow_x = prof(slow, fast).seconds(summa)
    assert slow_y > slow_x
    # missing axis classes fall back to the pooled link: analytic identity
    pooled = MachineProfile(
        device_kind="synth", peak_flops=1e18,
        links=(("ici", LinkParams(0.0, 1e9)),))
    expected = max(2.0 * summa.m * summa.n * summa.k / summa.tp / 1e18,
                   summa.comm_bytes / 1e9)
    assert pooled.seconds(summa) == pytest.approx(expected)
    assert default_profile().seconds(summa) == pytest.approx(
        max(2.0 * summa.m * summa.n * summa.k / summa.tp
            / default_profile().peak_flops,
            summa.comm_bytes / default_profile().link("ici").bw_bytes_per_s))


# --- obs: hidden-comm tagging -------------------------------------------------


def test_collective_comm_tag_exposed_and_hidden():
    with obs.observe() as rec:
        with obs.span("plan.execute", strategy="cannon"):
            obs.record_collective("ppermute", 4, 16, perm=[(0, 1), (1, 0)])
            with obs.span("dist.prefetch", comm="hidden"):
                obs.record_collective("ppermute", 4, 16,
                                      perm=[(0, 1), (1, 0)])
    exposed, hidden = rec.collectives
    assert exposed.comm == "exposed" and hidden.comm == "hidden"
    assert exposed.key == hidden.key  # comm never enters the multiset key
    doc = obs.to_trace_events(rec)
    comms = [e["args"]["comm"] for e in doc["traceEvents"]
             if e["name"] == "collective.ppermute"]
    assert sorted(comms) == ["exposed", "hidden"]
    totals = obs.collective_totals(rec)
    assert totals["cannon"]["ppermute"]["count"] == 2
    assert totals["cannon"]["ppermute"]["shard_words"] == 32
    assert totals["cannon"]["ppermute"]["hidden_words"] == 16


# --- property: rotation preserves the movement homomorphism -------------------


def _apply(state, perm):
    if not perm:
        return state
    out = list(state)
    for src, dst in perm:
        out[dst] = state[src]
    return tuple(out)


def _compute_inputs(prog, overlapped):
    """Per-step (A-state, B-state) each local multiply consumes, simulating
    the staged and double-buffered bodies' dataflow on symbolic blocks."""
    n = prog.q * prog.q
    a = _apply(tuple(range(n)), prog.skew_a)
    b = _apply(tuple(range(n)), prog.skew_b)
    seen = []
    for step in range(prog.steps):
        if overlapped and step < prog.steps - 1:
            nxt_a = _apply(a, prog.step_a)
            nxt_b = _apply(b, prog.step_b)
        seen.append((a, b))
        if step < prog.steps - 1:
            if overlapped:
                a, b = nxt_a, nxt_b
            else:
                a = _apply(a, prog.step_a)
                b = _apply(b, prog.step_b)
    return seen


@settings(max_examples=30, deadline=None)
@given(q=st.integers(2, 7))
def test_double_buffer_rotation_preserves_movement(q):
    prog = TorusProgram.from_schedule(cannon_schedule(q))
    assert _compute_inputs(prog, False) == _compute_inputs(prog, True)


# --- executed conformance + bitwise identity (forced-host subprocess) ---------

_EXEC_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import jax, jax.numpy as jnp, numpy as np
from collections import Counter

from repro import obs
from repro.plan import build_plan
from repro.plan.lower_shard_map import _lower_shard_map
from repro.verify.conformance import check, compare_records
from repro.mesh import make_mesh
from repro.verify.trace import trace_plan

devs = np.array(jax.devices())
mesh44 = make_mesh((4, 4), ("x", "y"), devices=devs[:16])
mesh24 = make_mesh((2, 4), ("x", "y"), devices=devs[:8])
rng = np.random.default_rng(0)
a = jnp.asarray(rng.standard_normal((48, 32)), jnp.float32)
b = jnp.asarray(rng.standard_normal((32, 40)), jnp.float32)

# overlapped cannon on the 4x4 mesh conforms with the SAME collective
# multiset as its staged twin, and the outputs are bitwise identical
plans = {}
outs = {}
for ov in (False, True):
    plan = build_plan(48, 40, 32, mesh=mesh44, strategy="cannon",
                      overlap=ov, use_cache=False)
    check(plan, measure=True)
    plans[ov] = plan
    outs[ov] = np.asarray(_lower_shard_map(plan)(a, b))
compare_records(trace_plan(plans[False]).records,
                trace_plan(plans[True]).records)
assert np.array_equal(outs[False], outs[True]), "cannon overlap not bitwise"

# summa's decomposed-gather twin: same movement words, allclose output
# (per-slab fp32 dots re-associate the contraction sum)
souts = {}
for ov in (False, True):
    plan = build_plan(48, 40, 32, mesh=mesh24, strategy="summa",
                      overlap=ov, use_cache=False)
    check(plan, measure=True)
    souts[ov] = np.asarray(_lower_shard_map(plan)(a, b))
    if ov:
        tr = trace_plan(plan)
        st = trace_plan(build_plan(48, 40, 32, mesh=mesh24,
                                   strategy="summa", overlap=False,
                                   use_cache=False))
        assert tr.movement_words() == st.movement_words()
assert np.allclose(souts[False], souts[True], rtol=1e-5, atol=1e-5)

# exposed-vs-hidden: the overlapped cannon body hides its step permutes
# behind the prefetch span; only the two skews stay exposed
plan = plans[True]
with obs.observe() as rec:
    with obs.span("plan.execute", strategy="cannon"):
        jax.block_until_ready(_lower_shard_map(plan)(a, b))
hidden = [ev for ev in rec.collectives if ev.comm == "hidden"]
exposed = [ev for ev in rec.collectives if ev.comm == "exposed"]
assert len(hidden) == 6, (len(hidden), len(exposed))   # 3 rounds x {A, B}
assert len(exposed) == 2, (len(hidden), len(exposed))  # the two skews
print("OVERLAP_EXEC_OK")
"""


@pytest.mark.timeout(600)
def test_overlapped_execution_conformance_subprocess():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(_root(), "src")
    res = subprocess.run(
        [sys.executable, "-c", _EXEC_SCRIPT], capture_output=True,
        text=True, env=env, timeout=590,
    )
    assert "OVERLAP_EXEC_OK" in res.stdout, (
        f"stdout:\n{res.stdout[-3000:]}\nstderr:\n{res.stderr[-3000:]}"
    )


# --- benchmarks/run.py --report regression ------------------------------------


def test_run_report_renders_null_us_rows(tmp_path, capsys):
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(_root(), "benchmarks", "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rows = [
        {"schema": 2, "name": "lowerbound_gap", "us_per_call": None,
         "derived": "bound=1.0"},
        {"schema": 2, "name": "overlap_vs_staged_cannon_2x2",
         "us_per_call": 123.4, "derived": "speedup=1.10x"},
        {"schema": 2, "name": "bench_broken", "error": "boom"},
    ]
    p = tmp_path / "bench_results.json"
    p.write_text(json.dumps(rows))
    assert mod.run_report(str(p)) == 0
    out = capsys.readouterr().out
    assert "lowerbound_gap: -" in out
    assert "123.4 us" in out and "boom" in out
    # metrics snapshots still render
    snap = tmp_path / "metrics.json"
    snap.write_text(json.dumps({"schema": 1, "metrics": {}, "spans": {},
                                "collectives": {}}))
    assert mod.run_report(str(snap)) == 0


def _root():
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
