"""repro.tune: the measured-autotuning search, table, and planner wiring.

Three layers of coverage:

  * search/table invariants -- every candidate MXU-aligned and
    VMEM-feasible (property test), bucket sharing, JSON round-trip with
    newer-schema rejection, profile embedding;
  * planner wiring -- a doctored table flips the strategy ranking and the
    overlap decision (the pinned regression that measured kernel seconds
    really enter ``calibrated_total_s``), tuned blocks land in the plan's
    ``TilingPlan``, the tuner participates in the plan-cache key;
  * the serving loop -- a subprocess Server warmup tunes each bucket's
    local shapes and the serve window runs at a 100% tuning-cache hit
    rate (the tuning twin of the plan-cache pin).
"""
import os
import subprocess
import sys

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=16")

import jax
import pytest
from hypothesis import given, settings, strategies as st

from repro import obs
from repro.kernels.matmul.kernel import vmem_working_set_bytes
from repro.mesh import make_mesh
from repro.obs.profile import MachineProfile, default_profile
from repro.plan import build_plan, rank_mesh_strategies
from repro.tune import (MXU, TunedBlocks, TuningTable, Tuner,
                        VMEM_BUDGET_BYTES, candidate_space, load_table,
                        pad_up, save_table, scaled_call_seconds,
                        shape_bucket, table_key, tune_shape)


def _root():
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _entry(bucket, seconds, blocks=(128, 128, 128), order="zorder"):
    return TunedBlocks(block_m=blocks[0], block_n=blocks[1],
                       block_k=blocks[2], order=order, seconds=seconds,
                       bucket=bucket)


# --- candidate space -------------------------------------------------------


class TestCandidateSpace:
    @settings(max_examples=80, deadline=None)
    @given(m=st.integers(1, 4096), n=st.integers(1, 4096),
           k=st.integers(1, 4096), dtype_bytes=st.sampled_from([1, 2, 4]))
    def test_candidates_aligned_and_feasible(self, m, n, k, dtype_bytes):
        """Every searched candidate is MXU-aligned and fits the same VMEM
        budget ``default_blocks`` targets -- the search can never propose
        a block the kernel would spill on."""
        cands = candidate_space(m, n, k, dtype_bytes)
        assert cands
        for bm, bn, bk, order in cands:
            assert bm % MXU == 0 and bn % MXU == 0 and bk % MXU == 0
            assert order in ("zorder", "rowmajor")
            if min(m, n, k) >= MXU:  # tiny shapes get the canonical stub
                assert vmem_working_set_bytes(
                    bm, bn, bk, dtype_bytes) <= VMEM_BUDGET_BYTES
                assert bm <= pad_up(m) and bn <= pad_up(n) and bk <= pad_up(k)

    def test_tiny_shape_single_candidate(self):
        assert candidate_space(64, 32, 8) == ((MXU, MXU, MXU, "zorder"),)

    def test_max_candidates_bounds_deterministically(self):
        full = candidate_space(512, 512, 512, 2)
        sub = candidate_space(512, 512, 512, 2, max_candidates=6)
        assert len(sub) == 6 and set(sub) <= set(full)
        assert sub == candidate_space(512, 512, 512, 2, max_candidates=6)

    def test_fp32_space_no_larger_than_bf16(self):
        bf16 = candidate_space(4096, 4096, 4096, 2)
        fp32 = candidate_space(4096, 4096, 4096, 4)
        assert set(fp32) <= set(bf16)


# --- buckets and keys ------------------------------------------------------


class TestBuckets:
    def test_nearby_shapes_share_bucket(self):
        assert shape_bucket(300, 128, 200) == (512, 128, 256)
        assert table_key(300, 128, 200, "float32") == \
            table_key(290, 100, 140, "float32")

    def test_dtype_splits_key(self):
        assert table_key(256, 256, 256, "float32") != \
            table_key(256, 256, 256, "bfloat16")

    def test_scaled_call_seconds(self):
        e = _entry((512, 512, 512), 1.0)
        # a call with exactly half the padded FLOPs costs half the seconds
        assert scaled_call_seconds(e, 256, 512, 512) == pytest.approx(0.5)
        assert scaled_call_seconds(e, 512, 512, 512) == pytest.approx(1.0)


# --- table persistence -----------------------------------------------------


class TestTableJson:
    def _table(self):
        t = TuningTable(device_kind="cpu", created="2026-08-08")
        t = t.with_entry(256, 256, 256, "float32",
                         _entry((256, 256, 256), 1e-4, (256, 256, 256),
                                "rowmajor"))
        return t.with_entry(300, 128, 200, "bfloat16",
                            _entry((512, 128, 256), 5e-5))

    def test_round_trip(self, tmp_path):
        t = self._table()
        path = save_table(t, str(tmp_path / "t.json"))
        back = load_table(path)
        assert back == t
        assert back.lookup(290, 100, 140, "bfloat16").seconds == 5e-5

    def test_newer_schema_rejected(self):
        obj = self._table().to_json()
        obj["schema"] = 99
        with pytest.raises(ValueError, match="newer than supported"):
            TuningTable.from_json(obj)

    def test_lookup_counts_stats_without_breaking_hash(self):
        t = self._table()
        h0 = hash(t)
        assert t.lookup(256, 256, 256, "float32") is not None
        assert t.lookup(64, 64, 64, "float32") is None
        assert t.stats == {"hits": 1, "misses": 1}
        assert hash(t) == h0  # stats excluded from eq/hash

    def test_profile_embedding_round_trip(self, tmp_path):
        prof = default_profile()
        import dataclasses

        prof = dataclasses.replace(prof, tuning=self._table())
        obj = prof.to_json()
        back = MachineProfile.from_json(obj)
        assert back.tuning is not None
        assert back.tuning.lookup(256, 256, 256, "float32",
                                  count=False).order == "rowmajor"
        # pre-tuning profile JSONs still load (tuning stays None)
        del obj["tuning"]
        assert MachineProfile.from_json(obj).tuning is None


# --- the search itself -----------------------------------------------------


class TestSearch:
    def test_tune_shape_returns_feasible_winner(self):
        e = tune_shape(64, 64, 64, "float32", reps=1, interpret=True)
        assert (e.block_m, e.block_n, e.block_k) == (MXU, MXU, MXU)
        assert e.seconds > 0 and e.bucket == (128, 128, 128)

    def test_tuner_searches_once_per_bucket(self):
        tuner = Tuner(reps=1, max_candidates=2, interpret=True)
        e1 = tuner.entry_for(64, 64, 64, dtype="float32")
        e2 = tuner.entry_for(100, 90, 120, dtype="float32")  # same bucket
        assert e1 is e2
        assert tuner.stats["searches"] == 1
        assert tuner.stats["hits"] == 1 and tuner.stats["misses"] == 1
        assert tuner.compute_seconds(64, 64, 64, dtype="float32") > 0
        assert tuner.stats["searches"] == 1  # cached, no re-search

    def test_tuner_table_snapshot(self):
        tuner = Tuner(reps=1, max_candidates=2, interpret=True,
                      device_kind="cpu")
        tuner.entry_for(64, 64, 64, dtype="float32")
        table = tuner.table()
        assert table.device_kind == "cpu" and len(table.entries) == 1
        assert table.lookup(64, 64, 64, "float32", count=False) is not None


# --- planner wiring --------------------------------------------------------


def _mesh(shape, names, need):
    devs = jax.devices()
    if len(devs) < need:
        pytest.skip(f"needs {need} forced-host devices, have {len(devs)}")
    return make_mesh(shape, names, devices=devs[:need])


class TestPlannerWiring:
    def test_doctored_table_flips_strategy(self):
        """The pinned regression: on a 4x4 mesh at 4096^3 the analytic
        model picks cannon; a tuning table claiming cannon's local bucket
        (1024^3) is slow and summa's (1024x1024x256) is ~free must flip
        the calibrated ranking to summa -- measured kernel seconds really
        drive ``calibrated_total_s``."""
        mesh = _mesh((4, 4), ("x", "y"), 16)
        m = n = k = 4096
        assert rank_mesh_strategies(m, n, k, mesh)[0].strategy == "cannon"
        tbl = TuningTable(device_kind="cpu")
        tbl = tbl.with_entry(1024, 1024, 1024, "float32",
                             _entry((1024, 1024, 1024), 10.0))
        tbl = tbl.with_entry(1024, 1024, 256, "float32",
                             _entry((1024, 1024, 256), 1e-9))
        ranked = rank_mesh_strategies(m, n, k, mesh, tuning=tbl,
                                      dtype="float32")
        assert ranked[0].strategy == "summa"
        plan = build_plan(m, n, k, mesh=mesh, strategy=None, batch=(),
                          a_dtype="float32", b_dtype="float32",
                          out_dtype=None, tuning=tbl, use_cache=False)
        assert plan.strategy == "summa"
        assert plan.tiling.tuned  # doctored blocks folded into the tiling

    def test_doctored_table_flips_overlap(self):
        """Zero measured compute leaves nothing to hide the collectives
        behind: the overlap resolver must fall back to staged."""
        mesh = _mesh((2, 2), ("x", "y"), 4)
        m = n = k = 4096
        kw = dict(mesh=mesh, strategy="cannon", batch=(),
                  a_dtype="float32", b_dtype="float32", out_dtype=None,
                  use_cache=False)
        assert build_plan(m, n, k, **kw).overlap is True
        tbl = TuningTable(device_kind="cpu").with_entry(
            2048, 2048, 2048, "float32", _entry((2048, 2048, 2048), 0.0))
        assert build_plan(m, n, k, tuning=tbl, **kw).overlap is False

    def test_tuned_blocks_consumed_by_tiling(self):
        mesh = _mesh((2, 2), ("x", "y"), 4)
        tbl = TuningTable(device_kind="cpu").with_entry(
            256, 256, 256, "float32",
            _entry((256, 256, 256), 1e-4, (128, 128, 256), "rowmajor"))
        plan = build_plan(512, 512, 512, mesh=mesh, strategy="cannon",
                          batch=(), a_dtype="float32", b_dtype="float32",
                          out_dtype=None, tuning=tbl, use_cache=False)
        t = plan.tiling
        assert t.tuned and t.order == "rowmajor"
        assert (t.block_m, t.block_n, t.block_k) == (128, 128, 256)

    def test_local_plan_uses_tuned_blocks(self):
        tbl = TuningTable(device_kind="cpu").with_entry(
            256, 256, 256, "float32",
            _entry((256, 256, 256), 1e-4, (256, 128, 128), "rowmajor"))
        plan = build_plan(256, 256, 256, mesh=None, strategy=None,
                          batch=(), a_dtype="float32", b_dtype="float32",
                          out_dtype=None, tuning=tbl, use_cache=False)
        assert plan.strategy == "local" and plan.tiling.tuned
        assert plan.tiling.block_m == 256

    def test_tuning_in_plan_cache_key(self):
        from repro.plan import plan_cache

        tbl = TuningTable(device_kind="cpu").with_entry(
            256, 256, 256, "float32",
            _entry((256, 256, 256), 1e-4, (128, 128, 256), "rowmajor"))
        kw = dict(mesh=None, strategy=None, batch=(), a_dtype="float32",
                  b_dtype="float32", out_dtype=None)
        p0 = build_plan(256, 256, 256, **kw)
        p1 = build_plan(256, 256, 256, tuning=tbl, **kw)
        assert not p0.tiling.tuned and p1.tiling.tuned
        # distinct cache entries: re-lookups return the right plan
        assert build_plan(256, 256, 256, **kw) is p0
        assert build_plan(256, 256, 256, tuning=tbl, **kw) is p1

    def test_explicit_tiling_beats_table(self):
        from repro.plan import TilingPlan

        tbl = TuningTable(device_kind="cpu").with_entry(
            256, 256, 256, "float32",
            _entry((256, 256, 256), 1e-4, (128, 128, 256), "rowmajor"))
        plan = build_plan(256, 256, 256, mesh=None, strategy=None, batch=(),
                          a_dtype="float32", b_dtype="float32",
                          out_dtype=None, tuning=tbl, use_cache=False,
                          tiling=TilingPlan(block_m=128))
        assert not plan.tiling.tuned and plan.tiling.block_m == 128


# --- pad-waste metric ------------------------------------------------------


def test_pad_waste_metric_recorded():
    import jax.numpy as jnp

    from repro.kernels.matmul import matmul

    a = jnp.ones((300, 128), jnp.float32)
    b = jnp.ones((128, 128), jnp.float32)
    with obs.observe() as rec:
        matmul(a, b, block_m=256, interpret=True)
    snap = obs.metrics_snapshot(rec)
    waste = snap["metrics"]["kernel.pad_waste"]
    # m=300 pads to 512 under block_m=256; n and k are exact
    assert waste["count"] == 1
    assert waste["mean"] == pytest.approx(512 / 300)


# --- serve warmup tunes, serve window hits ---------------------------------

_TUNE_SERVE_SCRIPT = r"""
import dataclasses, os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from repro.configs import get_smoke_config
from repro.mesh import make_mesh
from repro.models.registry import build_model
from repro.runtime.serve import ServeConfig
from repro.serve import warmup
from repro.tune import Tuner

devs = jax.devices()
mesh = make_mesh((2, 2), ("x", "y"), devices=devs[:4])
cfg = dataclasses.replace(get_smoke_config("llama3_2_1b"), dtype="float32")
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
scfg = ServeConfig(max_new_tokens=4, max_seq=64)

tuner = Tuner(reps=1, max_candidates=2, interpret=True)
srv = warmup(model, params, scfg, mesh=mesh, buckets=[(2, 8)], tuning=tuner)
assert tuner.stats["searches"] > 0, tuner.stats  # warmup tuned the buckets

r = srv.generate([[5, 6, 7], [9, 2, 3, 4, 1]])
rep = srv.cache_report()
assert rep["serve_window"]["hit_rate"] == 1.0, rep
# no serve-window search: every tuning lookup hit the warmup entries
tw = rep["tuning"]["serve_window"]
assert tw["misses"] == 0 and tw["hit_rate"] == 1.0, rep["tuning"]
assert r.plan_probe["tune_probed"] > 0, r.plan_probe
assert r.plan_probe["tune_missing"] == 0, r.plan_probe
assert rep["tuning"]["entries"] > 0
searches_before = tuner.stats["searches"]
srv.generate([[4, 4], [7, 7, 7]])
assert tuner.stats["searches"] == searches_before  # still no search
print("TUNE_SERVE_OK")
"""


@pytest.mark.timeout(600)
def test_serve_warmup_tunes_subprocess():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(_root(), "src")
    res = subprocess.run(
        [sys.executable, "-c", _TUNE_SERVE_SCRIPT], capture_output=True,
        text=True, env=env, timeout=590)
    assert "TUNE_SERVE_OK" in res.stdout, (
        f"stdout:\n{res.stdout[-3000:]}\nstderr:\n{res.stderr[-3000:]}")
