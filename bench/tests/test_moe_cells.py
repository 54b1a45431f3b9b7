"""The deepseek-moe cells at small sizes on the CPU: a sound run is
``correct``; the lower-precision control and each fault planted under the
timed path are not.  The harness's look for a chip is skipped; the rest of
a run is driven as ``bench/run.py`` drives it."""
import jax
import jax.numpy as jnp
import pytest

import small
from bench.lib import harness

CELLS = ["dsmoe.engine.ep8", "dsmoe.serve.chat"]

# Every width cut, the expert share kept in proportion: 4 of 16 experts
# held (experts 4-7), 3 per token, 2 shared, one dense layer first.
SMALL_CONFIG = dict(hidden_size=128, num_hidden_layers=3, head_dim=32,
                    num_attention_heads=4, num_key_value_heads=4,
                    intermediate_size=256, moe_intermediate_size=64,
                    n_routed_experts=4, published={"n_routed_experts": 16},
                    first_held_expert=4, num_experts_per_tok=3,
                    vocab_size=512)
# The serve cell's logit gap is wider at full width: at this size sound
# runs read 0.0008 to 0.011 and the fp8 control 0.086 to 0.112 (CPU, seeds
# 1-5), so the small serve cell's limit is 0.03.  The engine cell's own
# limits hold here: moe_err reads 0.0055 to 0.0075 sound and 0.066 to 0.085
# under the control, which also routes 16 to 31 pairs differently.
SMALL_WORKLOAD = {
    "moe": dict(rows=256, trace_seconds=1, check_layers=2),
    "serve": dict(small.SMALL_WORKLOAD["serve"],
                  limits={"logit_gap": 0.03}),
}


@pytest.fixture(autouse=True)
def _fresh_programs():
    """A planted fault is not hidden by a program cached from an earlier
    test."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def cell(name):
    c = harness.load_cell(name)
    c.config = dict(c.config, **SMALL_CONFIG)
    c.workload.update(SMALL_WORKLOAD[c.workload["driver"]])
    return c


def _correct(out, c):
    line = harness.result_line(c, out, jax.devices()[:c.chips],
                               "TPU v5 lite", False, {}, None)
    return line["correct"]


def _run_is_correct(name, seed=2**33 + 17):
    c = cell(name)
    return _correct(small.run(c, seed=seed), c)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    c = cell(name)
    out = small.run(c)
    assert _correct(out, c), out.checks
    assert out.attempted > 0 and out.failed == 0


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    c = cell(name)
    out = small.run(c)
    first = out.checks[0]
    out.checks[0] = harness.Check(first.name, out.control(), first.limit)
    assert not _correct(out, c), out.checks


def test_engine_traced_run_counts_the_rows_routed():
    """A traced run hands the readers each layer's rows per held expert;
    the rows routed to held experts are about held / experts of all."""
    c = cell("dsmoe.engine.ep8")
    out = small.run(c, trace=True)
    rows = out.work["expert_rows"]
    assert len(rows) == 2 and all(len(r) == 4 for r in rows)
    pairs = c.workload["rows"] * 3
    assert 0.1 * pairs < sum(rows[0]) < 0.5 * pairs
    assert out.work["gmm_min_s"] > 0 and out.work["useful_flops"] > 0


# -- faults under the timed path: the program's MoE layer ---------------------

def _patch_gmm(monkeypatch, fault):
    import repro.layers.moe as moe

    real = moe.grouped_matmul
    monkeypatch.setattr(moe, "grouped_matmul",
                        lambda x, w, sizes, **kw: fault(real, x, w, sizes,
                                                        **kw))


def _rows_of_group_dropped(real, x, w, sizes, **kw):
    out = real(x, w, sizes, **kw)
    row = jnp.arange(x.shape[0])[:, None]
    return jnp.where((row >= sizes[0]) & (row < sizes[0] + sizes[1]), 0, out)


def _two_experts_swapped(real, x, w, sizes, **kw):
    return real(x, w[jnp.asarray([1, 0, 2, 3])], sizes, **kw)


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", [_rows_of_group_dropped,
                                   _two_experts_swapped])
def test_expert_gemm_fault(monkeypatch, name, fault):
    _patch_gmm(monkeypatch, fault)
    assert not _run_is_correct(name)


@pytest.mark.parametrize("name", CELLS)
def test_absent_pair_computed_as_if_held(monkeypatch, name):
    """Pairs routed to experts held elsewhere are handed to a held one."""
    import repro.layers.moe as moe

    real = moe.route

    def route(router, x, cfg):
        gates, experts, probs = real(router, x, cfg)
        first, held = cfg.first_held_expert, cfg.experts_held
        absent = (experts < first) | (experts >= first + held)
        return gates, jnp.where(absent, first + experts % held,
                                experts), probs

    monkeypatch.setattr(moe, "route", route)
    assert not _run_is_correct(name)
