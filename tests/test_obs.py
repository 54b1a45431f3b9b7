"""repro.obs: span tracing, metrics, exporters, machine profiles.

Pins the PR-6 acceptance criteria:

  * span nesting + Perfetto trace_event export round-trips (schema keys,
    JSON-serializable, nesting depths);
  * disabled mode is a true no-op (shared singleton span, empty recorder);
  * spans reach a ``jax.profiler`` trace under their own names, args as
    stats, whenever a profiler session records, with or without the
    recorder;
  * the obs collective multiset equals the ``repro.verify`` interceptor's
    AND the schedule trace's, per strategy, on real executions (subprocess
    with forced-host devices);
  * ``rank_mesh_strategies(profile=default_profile())`` reproduces the
    analytic ranking exactly, and a synthetic latency-dominated profile
    flips cannon -> summa (the calibrated-ranking regression test);
  * profile JSON round-trip + newer-schema rejection, α–β fit recovery;
  * plan-cache ``cache_info()`` size/eviction accounting.
"""
import contextlib
import json
import math
import os
import subprocess
import sys
from collections import Counter
from types import SimpleNamespace

import numpy as np
import pytest

from repro import obs
from repro.obs.profile import (LinkParams, MachineProfile, default_profile,
                               fit_alpha_beta, load_profile, save_profile)
from repro.plan import PlanCache, rank_mesh_strategies
from repro.plan.cache import plan_cache


def fake_mesh(sizes, names):
    total = math.prod(sizes)
    return SimpleNamespace(
        axis_names=tuple(names),
        shape=dict(zip(names, sizes)),
        size=total,
        devices=np.array([SimpleNamespace(id=i, platform="cpu")
                          for i in range(total)]),
    )


# --- spans / recorder --------------------------------------------------------


def test_disabled_mode_is_noop():
    assert not obs.enabled()
    s1 = obs.span("a", x=1)
    s2 = obs.span("b")
    assert s1 is s2 is obs.NOOP_SPAN  # shared singleton, no allocation
    with s1:
        obs.record_collective("ppermute", 4, 64, perm=[(0, 1), (1, 0)])
        obs.instant("nothing")
        assert obs.current_tags() == {}
    rec = obs.get_recorder()
    assert rec.spans == [] and rec.collectives == [] and rec.instants == []


def test_span_nesting_and_tags():
    with obs.observe() as rec:
        with obs.span("outer", strategy="cannon", m=8):
            with obs.span("inner", m=16):
                assert obs.current_tags() == {"strategy": "cannon", "m": 16}
            with obs.span("inner"):
                pass
    names = [s.name for s in rec.spans]
    assert names == ["inner", "inner", "outer"]  # exit order
    depths = {s.name: s.depth for s in rec.spans}
    assert depths == {"inner": 1, "outer": 0}
    assert rec.span_counts() == {"inner": 2, "outer": 1}
    outer = next(s for s in rec.spans if s.name == "outer")
    inner = next(s for s in rec.spans if s.name == "inner")
    assert outer.dur_us >= inner.dur_us >= 0
    # observe() restored the previous (disabled) state
    assert not obs.enabled()


@pytest.mark.parametrize("recording", [False, True])
@pytest.mark.parametrize("profiling", [False, True])
def test_span_reaches_profiler_trace_and_recorder(recording, profiling,
                                                  tmp_path,
                                                  xplane_host_events):
    import jax

    n_before = len(obs.get_recorder().spans)
    session = (jax.profiler.trace(str(tmp_path)) if profiling
               else contextlib.nullcontext())
    scope = obs.observe() if recording else contextlib.nullcontext()
    with scope as rec, session:
        span = obs.span("serve.batch", rows=8, label="8x512")
        if not (recording or profiling):
            assert span is obs.NOOP_SPAN
        with span as handle:
            handle.set_metadata(tokens=512)
    args = {"rows": 8, "label": "8x512", "tokens": 512}
    if recording:
        (record,) = rec.spans
        assert record.name == "serve.batch" and record.args == args
    else:
        assert len(obs.get_recorder().spans) == n_before
    if profiling:
        found = [(name, stats) for name, _, _, stats
                 in xplane_host_events(tmp_path) if name == "serve.batch"]
        assert found == [("serve.batch", args)]
    else:
        assert not list(tmp_path.iterdir())


def test_collective_events_carry_strategy_and_key():
    with obs.observe() as rec:
        with obs.span("plan.execute", strategy="summa"):
            obs.record_collective("all_gather", 4, 128)
            obs.record_collective("ppermute", 4, 64,
                                  perm=[(1, 0), (0, 1), (2, 2)])
    ag, pp = rec.collectives
    assert ag.strategy == "summa" and pp.strategy == "summa"
    assert ag.key == ("all_gather", 4, 128, None)
    # identity pairs dropped, rest sorted -- verify's canonical form
    assert pp.key == ("ppermute", 4, 64, ((0, 1), (1, 0)))
    ms = obs.collective_multiset(rec, strategy="summa")
    assert ms == Counter([ag.key, pp.key])
    assert obs.collective_multiset(rec, strategy="cannon") == Counter()


def test_trace_export_perfetto_roundtrip(tmp_path):
    with obs.observe() as rec:
        with obs.span("plan.build", strategy="cannon", m=8, n=8, k=8):
            with obs.span("plan.lower", strategy="cannon"):
                obs.record_collective("ppermute", 4, 16, perm=[(0, 1)])
        obs.instant("plan.built", strategy="cannon")
    doc = obs.to_trace_events(rec)
    assert doc["otherData"]["schema"] == obs.SCHEMA_VERSION
    events = doc["traceEvents"]
    xs = [e for e in events if e["ph"] == "X"]
    inst = [e for e in events if e["ph"] == "i"]
    assert {e["name"] for e in xs} == {"plan.build", "plan.lower"}
    assert "collective.ppermute" in {e["name"] for e in inst}
    for e in xs:  # Perfetto complete-event required keys
        assert {"name", "ph", "ts", "dur", "pid", "tid"} <= set(e)
        assert e["dur"] >= 0
    coll = next(e for e in inst if e["name"] == "collective.ppermute")
    assert coll["args"]["strategy"] == "cannon"
    assert coll["args"]["shard_words"] == 16
    # file round-trip stays valid JSON with identical events
    p = tmp_path / "trace.json"
    obs.write_trace(str(p), rec)
    assert json.loads(p.read_text())["traceEvents"] == json.loads(
        json.dumps(events))


def test_metrics_counters_and_histograms():
    obs.reset_metrics()
    c = obs.counter("test.count")
    c.inc()
    c.inc(2, strategy="cannon")
    assert c.total() == 3
    h = obs.histogram("test.us")
    for v in (1.0, 3.0, 5.0):
        h.observe(v)
    s = h.summary()
    assert s["count"] == 3 and s["sum"] == 9.0
    assert s["min"] == 1.0 and s["max"] == 5.0
    snap = obs.snapshot()
    assert any(k.startswith("test.count") for k in snap)
    assert snap["test.us"]["mean"] == 3.0
    obs.reset_metrics()
    assert obs.counter("test.count").total() == 0


def test_metrics_snapshot_envelope():
    obs.reset_metrics()
    with obs.observe() as rec:
        with obs.span("plan.execute", strategy="ring_ag"):
            obs.record_collective("ppermute", 4, 32, perm=[(0, 1)])
    snap = obs.metrics_snapshot(rec)
    assert snap["schema"] == obs.SCHEMA_VERSION
    assert snap["spans"] == {"plan.execute": 1}
    assert snap["collectives"]["ring_ag"]["ppermute"]["count"] == 1
    assert snap["collectives"]["ring_ag"]["ppermute"]["shard_words"] == 32


# --- machine profiles / calibrated ranking -----------------------------------


def test_profile_json_roundtrip(tmp_path):
    # links sorted by class name -- the canonical (from_json) order
    prof = MachineProfile(
        device_kind="cpu", peak_flops=1e12,
        links=(("axis:x", LinkParams(2e-6, 5e9)),
               ("ici", LinkParams(1e-6, 1e10))),
        created="2026-08-08T00:00:00Z")
    p = tmp_path / "machine_profile.json"
    save_profile(prof, str(p))
    back = load_profile(str(p))
    assert back == prof
    assert back.link("axis:x").alpha_s == 2e-6
    assert back.link("missing") is back.links[0][1]  # first-class fallback


def test_profile_rejects_newer_schema():
    with pytest.raises(ValueError, match="newer"):
        MachineProfile.from_json(
            {"schema": 99, "peak_flops": 1.0, "links": {}})


def test_fit_alpha_beta_recovers_parameters():
    alpha, bw = 5e-6, 2e9
    sizes = [1 << 14, 1 << 17, 1 << 20, 1 << 22]
    times = [alpha + s / bw for s in sizes]
    lp = fit_alpha_beta(sizes, times)
    assert lp.alpha_s == pytest.approx(alpha, rel=1e-6)
    assert lp.bw_bytes_per_s == pytest.approx(bw, rel=1e-6)
    # degenerate single point: everything attributed to bandwidth
    one = fit_alpha_beta([1 << 20], [1e-3])
    assert one.alpha_s == 0.0 and one.bw_bytes_per_s > 0


def test_default_profile_matches_analytic_ranking():
    mesh = fake_mesh((4, 4), ("x", "y"))
    for m, n, k in ((4096, 4096, 4096), (64, 1024, 64), (256, 256, 1 << 16)):
        analytic = [e.strategy for e in rank_mesh_strategies(m, n, k, mesh)]
        calibrated = [e.strategy for e in rank_mesh_strategies(
            m, n, k, mesh, profile=default_profile())]
        assert calibrated == analytic, (m, n, k)


def test_latency_profile_flips_cannon_to_summa():
    """The calibrated-ranking regression test: a latency-dominated machine
    (huge α, effectively infinite bandwidth/compute) must prefer the
    fewer-rounds schedule -- summa (qx-1)+(qy-1)=6 rounds beats cannon
    2q=8 on 4x4 -- while the analytic (bandwidth-only) model prefers
    cannon."""
    mesh = fake_mesh((4, 4), ("x", "y"))
    m = n = k = 4096
    analytic_top = rank_mesh_strategies(m, n, k, mesh)[0].strategy
    assert analytic_top == "cannon"
    latency = MachineProfile(
        device_kind="synth", peak_flops=1e18,
        links=(("ici", LinkParams(1.0, 1e18)),))
    ranked = rank_mesh_strategies(m, n, k, mesh, profile=latency)
    assert ranked[0].strategy == "summa"
    by_strategy = {e.strategy: e for e in ranked}
    assert latency.seconds(by_strategy["summa"]) < \
        latency.seconds(by_strategy["cannon"])
    # the estimates themselves (the conformance-checked word counts) are
    # identical to the analytic run -- only the sort key changed
    assert {e.strategy: e.comm_bytes for e in ranked} == \
        {e.strategy: e.comm_bytes
         for e in rank_mesh_strategies(m, n, k, mesh)}


def test_build_plan_profile_in_cache_key():
    from repro.plan import build_plan

    mesh = fake_mesh((4, 4), ("x", "y"))
    plan_cache.clear()
    latency = MachineProfile(
        device_kind="synth", peak_flops=1e18,
        links=(("ici", LinkParams(1.0, 1e18)),))
    p_analytic = build_plan(4096, 4096, 4096, mesh=mesh)
    p_latency = build_plan(4096, 4096, 4096, mesh=mesh, profile=latency)
    assert p_analytic.strategy == "cannon"
    assert p_latency.strategy == "summa"
    assert plan_cache.info()["misses"] == 2  # distinct cache entries
    assert build_plan(4096, 4096, 4096, mesh=mesh,
                      profile=latency).strategy == "summa"
    assert plan_cache.info()["hits"] == 1


# --- plan cache accounting ---------------------------------------------------


def test_cache_info_eviction_accounting():
    c = PlanCache(max_entries=2)
    assert c.get("a") is None
    c.put("a", 1)
    c.put("b", 2)
    assert c.get("a") == 1
    c.put("b", 3)  # overwrite: no eviction
    assert c.info()["evictions"] == 0
    c.put("c", 4)  # capacity hit: oldest ("a") dropped
    info = c.info()
    assert info["evictions"] == 1
    assert info["currsize"] == 2 and info["maxsize"] == 2
    assert c.get("a") is None  # evicted
    assert info["hits"] == 1 and info["misses"] == 1
    c.clear()
    assert c.info() == {"hits": 0, "misses": 0, "currsize": 0,
                        "maxsize": 2, "evictions": 0}


def test_report_plan_cache_table():
    from repro.launch.report import plan_cache_table

    table = plan_cache_table({"hits": 3, "misses": 1, "currsize": 1,
                              "maxsize": 1024, "evictions": 0})
    assert "| 3 | 1 | 0.75 | 1 | 1024 | 0 |" in table


# --- obs == interceptor == trace on real executions (subprocess) -------------

_TRIANGLE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from collections import Counter

from repro import obs
from repro.plan import build_plan
from repro.plan.lower_shard_map import _lower_shard_map
from repro.verify.interceptor import intercept
from repro.mesh import make_mesh
from repro.verify.trace import trace_plan

devs = np.array(jax.devices())
mesh22 = make_mesh((2, 2), ("x", "y"), devices=devs[:4])
mesh1d = make_mesh((4,), ("t",), devices=devs[:4])
mesh3 = make_mesh((2, 2, 2), ("pod", "x", "y"), devices=devs[:8])
cells = [("cannon", mesh22), ("summa", mesh22), ("ring_ag", mesh1d),
         ("ring_rs", mesh1d), ("cannon25d", mesh3), ("pod25d", mesh3)]

m, n, k = 24, 16, 32
a = jnp.ones((m, k), jnp.float32)
b = jnp.ones((k, n), jnp.float32)
for strat, mesh in cells:
    plan = build_plan(m, n, k, mesh=mesh, strategy=strat, use_cache=False)
    with obs.observe() as rec:
        with intercept() as cap:  # both observers active simultaneously
            with obs.span("plan.execute", strategy=strat):
                jax.block_until_ready(_lower_shard_map(plan)(a, b))
    obs_ms = obs.collective_multiset(rec, strategy=strat)
    int_ms = Counter(r.key for r in cap.records)
    trace_ms = Counter(r.key for r in trace_plan(plan).records)
    assert len(int_ms) > 0, f"{strat}: interceptor saw nothing"
    assert obs_ms == int_ms == trace_ms, (
        f"{strat}: obs={sorted(obs_ms.items())} "
        f"interceptor={sorted(int_ms.items())} "
        f"trace={sorted(trace_ms.items())}")
    assert all(ev.strategy == strat for ev in rec.collectives), strat
print("OBS_TRIANGLE_OK")
"""


@pytest.mark.timeout(600)
def test_obs_multiset_matches_interceptor_subprocess():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(_root(), "src")
    res = subprocess.run(
        [sys.executable, "-c", _TRIANGLE_SCRIPT], capture_output=True,
        text=True, env=env, timeout=590,
    )
    assert "OBS_TRIANGLE_OK" in res.stdout, (
        f"stdout:\n{res.stdout[-3000:]}\nstderr:\n{res.stderr[-3000:]}"
    )


def _root():
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
