"""The check that decides ``correct``, on the CPU at small sizes: a sound
run passes; the lower-precision control and each fault planted under the
timed path fail it.  The harness's look for a chip is skipped; the rest
of a run is driven as ``bench/run.py`` drives it."""
import jax
import jax.numpy as jnp
import pytest

import small
from bench.lib import harness

CELLS = ["danube.engine.prefill4k", "danube.serve.chat",
         "granite20b.engine.2x2"]


@pytest.fixture(autouse=True)
def _fresh_programs():
    """Every run plans, lowers and traces anew, so a planted fault is
    not hidden by a program cached from an earlier test."""
    from repro.plan import cache_clear
    from repro.plan.lower_shard_map import _lower_shard_map_cached

    cache_clear()
    _lower_shard_map_cached.cache_clear()
    jax.clear_caches()
    yield
    cache_clear()
    _lower_shard_map_cached.cache_clear()
    jax.clear_caches()


def _correct(out, cell):
    """``correct`` as the result line that ``bench/run.py`` prints has it."""
    line = harness.result_line(cell, out, jax.devices()[:cell.chips],
                               "TPU v5 lite", False, {}, None)
    return line["correct"]


def _run_is_correct(name):
    cell = small.cell(name)
    return _correct(small.run(cell), cell)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    cell = small.cell(name)
    out = small.run(cell)
    assert _correct(out, cell), out.checks
    assert out.attempted > 0 and out.failed == 0


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    """The reference in the program's place with float8 operands, read as
    the cell's first check, makes the run's ``correct`` false."""
    cell = small.cell(name)
    out = small.run(cell)
    first = out.checks[0]
    out.checks[0] = harness.Check(first.name, out.control(), first.limit)
    assert not _correct(out, cell), out.checks


# -- faults under the engine's timed path ------------------------------------

def _patch_execute(monkeypatch, fault):
    import repro.plan

    real = repro.plan.execute_plan
    monkeypatch.setattr(repro.plan, "execute_plan",
                        lambda p, a, b: fault(real(p, a, b)))


@pytest.mark.parametrize("name", ["danube.engine.prefill4k",
                                  "granite20b.engine.2x2"])
def test_engine_answer_altered(monkeypatch, name):
    _patch_execute(monkeypatch, lambda out: out.at[3, 5].add(
        jnp.asarray(1.0, out.dtype) * jnp.max(jnp.abs(out))))
    assert not _run_is_correct(name)


@pytest.mark.parametrize("name", ["danube.engine.prefill4k",
                                  "granite20b.engine.2x2"])
def test_engine_half_the_rows_left_out(monkeypatch, name):
    _patch_execute(monkeypatch, lambda out: out.at[out.shape[0] // 2:].set(0))
    assert not _run_is_correct(name)


def test_engine_exchange_between_chips_left_out(monkeypatch):
    import repro.dist._collectives as coll

    monkeypatch.setattr(coll, "ppermute", lambda x, axis_name, perm: x)
    assert not _run_is_correct("granite20b.engine.2x2")


# -- faults under the server's timed path -------------------------------------

def test_serve_token_altered(monkeypatch):
    import repro.serve.server as server

    real = server._sample
    monkeypatch.setattr(server, "_sample",
                        lambda logits, cfg, key: real(logits, cfg, key)
                        .at[0].add(1) % logits.shape[-1])
    assert not _run_is_correct("danube.serve.chat")


def test_serve_step_returns_its_state_unchanged(monkeypatch):
    from repro.models.lm import DecoderLM

    real = DecoderLM.decode_step
    monkeypatch.setattr(
        DecoderLM, "decode_step",
        lambda self, params, cache, *a, **k: (
            real(self, params, cache, *a, **k)[0], cache))
    assert not _run_is_correct("danube.serve.chat")


def test_serve_half_the_batch_left_out(monkeypatch):
    from repro.serve import Server

    real = Server.generate

    def half(self, prompts, key=None):
        res = real(self, prompts, key)
        keep = len(res.new_tokens) // 2
        res.new_tokens = res.new_tokens[:keep] + [[]] * (
            len(res.new_tokens) - keep)
        return res

    monkeypatch.setattr(Server, "generate", half)
    assert not _run_is_correct("danube.serve.chat")
