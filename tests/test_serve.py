"""Serving harness tests: decode determinism, left-padding invariance,
bucket routing, warmup cache pinning, sweep-JSON schema.

The plan-routed path (mesh-dependent) runs in a subprocess on forced-host
devices, like tests/test_plan_exec.py; everything else runs in-process on
the 1-device view.  The routed-vs-unrouted bitwise comparison uses an
fp32 model: split-K schedules legitimately reorder the fp32 accumulation,
and in bf16 that noise (~1 ulp per matmul) can flip greedy argmax ties --
fp32 keeps the top-1 margin orders of magnitude above it.
"""
import dataclasses
import json
import os
import subprocess
import sys
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import get_smoke_config
from repro.models.registry import build_model
from repro.runtime.serve import (ServeConfig, _default_prefill, _default_step,
                                 batch_requests, generate)
from repro.serve import Bucket, Server, bucket_grid, route, warmup


def _root():
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def llama():
    cfg = get_smoke_config("llama3_2_1b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


# --- ServeConfig validation (edge cases that used to slip through) ---------


def test_serveconfig_rejects_bad_fields():
    with pytest.raises(ValueError, match="max_new_tokens"):
        ServeConfig(max_new_tokens=-1)
    with pytest.raises(ValueError, match="max_seq"):
        ServeConfig(max_seq=0)
    with pytest.raises(ValueError, match="temperature"):
        ServeConfig(temperature=-0.5)


def test_generate_max_new_zero_appends_nothing(llama):
    """max_new_tokens=0 must return the prompts unchanged -- the seed
    version still appended one sampled token."""
    _, model, params = llama
    prompts = np.array([[5, 6, 7, 8]], np.int32)
    out = generate(model, params, prompts,
                   ServeConfig(max_new_tokens=0, max_seq=32))
    assert out.shape == (1, 4)
    assert np.array_equal(out, prompts)


def test_generate_cache_overrun_raises(llama):
    """prompt + max_new_tokens > max_seq used to silently overrun the KV
    cache; now it's a ValueError before any compute."""
    _, model, params = llama
    prompts = np.array([[1] * 30], np.int32)
    with pytest.raises(ValueError, match="max_seq"):
        generate(model, params, prompts,
                 ServeConfig(max_new_tokens=8, max_seq=32))


# --- batch_requests --------------------------------------------------------


def test_batch_requests_empty_list():
    """Used to raise a bare ValueError from max() on an empty sequence."""
    batch, lens = batch_requests([])
    assert batch.shape == (0, 0) and lens.shape == (0,)


def test_generate_empty_batch(llama):
    _, model, params = llama
    batch, _ = batch_requests([])
    out = generate(model, params, batch, ServeConfig(max_new_tokens=4))
    assert out.shape == (0, 0)


def test_batch_requests_shapes_and_lens():
    batch, lens = batch_requests([[1, 2, 3], [7]], pad_id=9)
    assert batch.tolist() == [[1, 2, 3], [9, 9, 7]]
    assert lens.tolist() == [3, 1]


def test_batch_requests_pad_to():
    batch, lens = batch_requests([[1, 2]], pad_to=5)
    assert batch.tolist() == [[0, 0, 0, 1, 2]] and lens.tolist() == [2]
    with pytest.raises(ValueError, match="pad_to"):
        batch_requests([[1, 2, 3]], pad_to=2)


def test_batch_requests_rejects_empty_prompt():
    with pytest.raises(ValueError, match="empty"):
        batch_requests([[1, 2], []])


# --- bucket router ---------------------------------------------------------


def test_bucket_validation_and_grid():
    with pytest.raises(ValueError):
        Bucket(0, 8)
    grid = bucket_grid([4, 2], [32, 16])
    assert [b.label for b in grid] == ["2x16", "2x32", "4x16", "4x32"]


def test_route_picks_smallest_fitting():
    buckets = bucket_grid([2, 4], [16, 32])
    assert route(2, 10, buckets) == Bucket(2, 16)
    assert route(3, 10, buckets) == Bucket(4, 16)
    assert route(2, 20, buckets) == Bucket(2, 32)
    assert route(5, 10, buckets) is None      # batch too large
    assert route(2, 40, buckets) is None      # prompt too long


def test_server_rejects_bucket_overrunning_cache(llama):
    _, model, params = llama
    with pytest.raises(ValueError, match="max_seq"):
        Server(model, params, ServeConfig(max_new_tokens=8, max_seq=16),
               buckets=[(2, 16)])


# --- decode determinism ----------------------------------------------------


def test_greedy_determinism_across_runs_and_batch_order(llama):
    _, model, params = llama
    cfg = ServeConfig(max_new_tokens=5, max_seq=32)
    prompts = [[5, 6, 7], [9, 2, 3, 4]]
    batch, lens = batch_requests(prompts)
    a = generate(model, params, batch, cfg, lens=lens)
    b = generate(model, params, batch, cfg, lens=lens)
    assert np.array_equal(a, b)
    # reversed batch order: same per-request tokens, permuted rows
    rbatch, rlens = batch_requests(prompts[::-1])
    r = generate(model, params, rbatch, cfg, lens=rlens)
    for i, p in enumerate(prompts):
        fwd = a[i, batch.shape[1] - lens[i]:]
        rev = r[1 - i, rbatch.shape[1] - rlens[1 - i]:]
        assert np.array_equal(fwd, rev), f"request {i} depends on batch order"


def test_temperature_sampling_reproducible_under_fixed_key(llama):
    _, model, params = llama
    cfg = ServeConfig(max_new_tokens=6, max_seq=32, temperature=0.8)
    prompts = np.array([[5, 6, 7], [9, 2, 3]], np.int32)
    key = jax.random.PRNGKey(42)
    a = generate(model, params, prompts, cfg, key=key)
    b = generate(model, params, prompts, cfg, key=key)
    assert np.array_equal(a, b)
    c = generate(model, params, prompts, cfg, key=jax.random.PRNGKey(7))
    assert a.shape == c.shape


# --- left-padding invariance ----------------------------------------------


@pytest.mark.parametrize("arch", ["llama3_2_1b", "minicpm3_4b"])
def test_left_pad_invariance(arch):
    """A prompt decoded alone emits the same greedy tokens as when it is
    left-padded into a mixed-length batch with per-row offsets (GQA and
    MLA attention paths)."""
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    scfg = ServeConfig(max_new_tokens=4, max_seq=32)
    prompts = [[5, 6, 7], [9, 2, 3, 4, 1, 8]]
    batch, lens = batch_requests(prompts)
    padded = generate(model, params, batch, scfg, lens=lens)
    for i, p in enumerate(prompts):
        alone = generate(model, params, np.asarray([p], np.int32), scfg)
        row = padded[i, batch.shape[1] - lens[i]:]
        assert np.array_equal(alone[0], row), (
            f"{arch} request {i}: alone {alone[0].tolist()} != "
            f"padded {row.tolist()}")


def test_server_bucket_invariance_and_trimming(llama):
    """Routing through a bucket (seq left-pad + dummy batch rows) must not
    change any request's greedy tokens, and padding must be stripped from
    the returned sequences."""
    _, model, params = llama
    scfg = ServeConfig(max_new_tokens=4, max_seq=32)
    srv = Server(model, params, scfg, buckets=[(4, 8)])
    srv.warmup()
    prompts = [[5, 6, 7], [9, 2, 3, 4, 1]]
    res = srv.generate(prompts)
    assert res.bucket == "4x8"
    assert len(res.sequences) == 2           # dummy rows trimmed
    for i, p in enumerate(prompts):
        alone = generate(model, params, np.asarray([p], np.int32), scfg)
        assert res.sequences[i] == alone[0].tolist()
        assert res.new_tokens[i] == alone[0, len(p):].tolist()


# --- Server edge behavior --------------------------------------------------


def test_server_empty_cold_and_null_latency(llama):
    _, model, params = llama
    srv = Server(model, params, ServeConfig(max_new_tokens=2, max_seq=64),
                 buckets=[(2, 8)])
    srv.warmup()
    assert srv.generate([]).sequences == []
    cold = srv.generate([[1] * 20])          # longer than any bucket seq
    assert cold.bucket is None and len(cold.new_tokens[0]) == 2
    zero = Server(model, params, ServeConfig(max_new_tokens=0, max_seq=64),
                  buckets=[(2, 8)])
    r0 = zero.generate([[5, 6, 7]])
    assert r0.new_tokens == [[]]
    assert r0.latency_quantiles_ms() == {"p50_ms": None, "p99_ms": None}


def test_server_compiles_a_cold_shape_once(llama):
    """A batch that fits no bucket is served at its own shape, through the
    same compiled programs as a bucket's, compiled on first use and kept:
    a second such batch compiles nothing and decodes as the plain path."""
    _, model, params = llama
    scfg = ServeConfig(max_new_tokens=3, max_seq=64)
    srv = Server(model, params, scfg, buckets=[(2, 8)])
    srv.warmup()
    prompts = [[4, 5, 6] * 4, [7, 8] * 5, [9] * 3]  # 3 rows, 12 slots: cold
    first = srv.generate(prompts)
    shapes = dict(srv._programs)
    second = srv.generate(prompts)
    assert first.bucket is None and second.bucket is None
    assert srv._programs == shapes and len(shapes) == 2
    assert second.new_tokens == first.new_tokens
    batch, lens = batch_requests(prompts)
    plain = generate(model, params, batch, scfg, lens=lens)
    for i, p in enumerate(prompts):
        assert first.new_tokens[i] == plain[i, batch.shape[1]:].tolist()


def test_server_generate_spans_on_profiler_clock(llama, tmp_path,
                                                 xplane_host_events):
    """Every phase of ``generate`` is a host span in the profiler's trace,
    nested under ``serve.generate``, with host-value args."""
    _, model, params = llama
    new = 4
    srv = Server(model, params, ServeConfig(max_new_tokens=new, max_seq=32),
                 buckets=[(4, 8)])
    srv.warmup()
    prompts = [[5, 6, 7], [9, 2, 3, 4, 1]]
    with jax.profiler.trace(str(tmp_path)):
        srv.generate(prompts)
    spans = [e for e in xplane_host_events(tmp_path)
             if e[0].startswith("serve.")]
    assert Counter(name for name, *_ in spans) == {
        "serve.generate": 1, "serve.prepare": 1, "serve.prefill": 1,
        "serve.first_token": 1, "serve.decode": 1, "serve.collect": 1,
        "serve.key_split": new - 1, "serve.decode_step": new - 1}
    by_name = {name: (s, e, args) for name, s, e, args in spans}
    g0, g1, gen_args = by_name["serve.generate"]
    assert gen_args == {"requests": 2, "bucket": "4x8"}
    assert all(g0 <= s <= e <= g1 for _, s, e, _ in spans)
    phases = ["serve.prepare", "serve.prefill", "serve.first_token",
              "serve.decode", "serve.collect"]
    ends = [by_name[p][:2] for p in phases]
    assert all(a[1] <= b[0] for a, b in zip(ends, ends[1:]))   # in order
    d0, d1, _ = by_name["serve.decode"]
    loop = [name for name, s, e, _ in spans
            if name in ("serve.key_split", "serve.decode_step")
            and d0 <= s <= e <= d1]
    assert loop == ["serve.key_split", "serve.decode_step"] * (new - 1)
    assert by_name["serve.prepare"][2] == {"repinned": 0}
    assert by_name["serve.prefill"][2] == {
        "batch": 4, "seq": 8, "tokens": sum(len(p) for p in prompts)}


def test_warmup_helper_returns_warm_server(llama):
    _, model, params = llama
    alias = obs.counter("serve.cache_alias")
    before = alias.value(bucket="2x8")
    with obs.observe():
        srv = warmup(model, params,
                     ServeConfig(max_new_tokens=2, max_seq=64),
                     buckets=[(2, 8)])
    assert "2x8" in srv.warmup_report
    # the compiled step aliases the whole cache: it updates it in place
    assert srv.warmup_report["2x8"]["cache_alias"] == 1.0
    assert alias.value(bucket="2x8") - before == 1.0
    res = srv.generate([[4, 5]])
    assert res.bucket == "2x8" and len(res.new_tokens[0]) == 2


@pytest.mark.parametrize("phase", ["prefill", "step"])
@pytest.mark.parametrize("arch", ["llama3_2_1b", "xlstm_350m"])
def test_default_programs_donate_the_cache(arch, phase):
    """``_default_prefill`` (one pass, or the recurrent families' step
    loop) and ``_default_step`` take the cache donated: the call deletes
    the buffers passed in, and the caller goes on with those returned."""
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    cache = model.init_cache(2, 16)
    tokens = jnp.ones((2, 4), jnp.int32)
    if phase == "prefill":
        _, new = _default_prefill(model)(params, cache, tokens)
    else:
        _, new = _default_step(model)(params, cache, tokens[:, :1],
                                      jnp.int32(0))
    assert all(a.is_deleted() for a in jax.tree.leaves(cache))
    assert not any(a.is_deleted() for a in jax.tree.leaves(new))


# --- sweep JSON schema + report -------------------------------------------


def _synthetic_sweep():
    cell = {
        "mesh": "2x2", "bucket": "4x16", "strategy": "auto", "ok": True,
        "routed": True, "plans": 8, "warmup_s": 1.0, "tokens_per_s": 100.0,
        "tokens_per_s_per_device": 12.5, "ttft_ms": 9.5,
        "p50_ms": None, "p99_ms": None,   # 1-token run: no timed steps
        "cache_hit_rate": 1.0, "match_baseline": True, "error": None,
    }
    bad = {"mesh": "1x4", "bucket": "4x16", "strategy": "cannon",
           "ok": False, "error": "ValueError: cannon needs a square mesh"}
    return {
        "schema": "repro.serve_sweep/v1", "arch": "llama3.2-1b-smoke",
        "created_unix": 1754600000,
        "config": {"max_new_tokens": 1, "max_seq": 64, "devices": 8,
                   "buckets": ["4x16"]},
        "cells": [cell, bad],
    }


def test_sweep_schema_roundtrip_and_null_latency_rendering(tmp_path):
    from repro.launch.report import serve_sweep_table

    data = _synthetic_sweep()
    path = tmp_path / "sweep.json"
    path.write_text(json.dumps(data))
    back = json.loads(path.read_text())
    assert back == data
    table = serve_sweep_table(back)
    row = [ln for ln in table.splitlines() if "4x16" in ln and "auto" in ln][0]
    cols = [c.strip() for c in row.split("|")]
    assert cols[8] == "-" and cols[9] == "-"      # null p50/p99 render as -
    assert "100.000" in row and "1.000" in row
    err_row = [ln for ln in table.splitlines() if "ERR" in ln][0]
    assert "square mesh" in err_row


def test_sweep_report_cli(tmp_path):
    path = tmp_path / "sweep.json"
    path.write_text(json.dumps(_synthetic_sweep()))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_root(), "src")
    res = subprocess.run(
        [sys.executable, os.path.join(_root(), "benchmarks", "serve_sweep.py"),
         "--report", str(path)],
        capture_output=True, text=True, env=env, timeout=120)
    assert res.returncode == 0, res.stderr
    assert "| mesh | bucket |" in res.stdout and "| - | - |" in res.stdout


def test_sweep_baseline_diff_margin():
    sys.path.insert(0, os.path.join(_root(), "benchmarks"))
    try:
        import serve_sweep
    finally:
        sys.path.pop(0)
    now, prev = _synthetic_sweep(), _synthetic_sweep()
    prev["cells"][0]["tokens_per_s"] = 200.0
    regressions, lines = serve_sweep.diff_baseline(now, prev, margin=0.25)
    assert len(regressions) == 1 and "REGRESSION" in lines[0]
    regressions, _ = serve_sweep.diff_baseline(now, prev, margin=0.60)
    assert regressions == []


# --- plan-routed serving on forced-host devices (subprocess) ---------------

_ROUTED_SCRIPT = r"""
import dataclasses, os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np
from repro import obs
from repro.configs import get_smoke_config
from repro.models.registry import build_model
from repro.plan import cache_info
from repro.runtime.serve import ServeConfig, batch_requests, generate
from repro.serve import Server, warmup
from repro.mesh import make_mesh

devs = jax.devices()
mesh = make_mesh((2, 2), ("x", "y"), devices=devs[:4])
cfg = dataclasses.replace(get_smoke_config("llama3_2_1b"), dtype="float32")
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
scfg = ServeConfig(max_new_tokens=5, max_seq=64)
prompts = [[5, 6, 7], [9, 2, 3, 4, 1]]

# unrouted baseline through the same Server machinery
base = Server(model, params, scfg, buckets=[(2, 8)])
base.warmup()
rb = base.generate(prompts)

with obs.observe() as rec:
    srv = warmup(model, params, scfg, mesh=mesh, buckets=[(2, 8)])
    assert srv.warmup_report["2x8"]["plans"] > 0, srv.warmup_report
    rp = srv.generate(prompts)
    rep = srv.cache_report()

# decode matmuls routed through SchedulePlans: collectives were executed
ms = obs.collective_multiset(rec)
assert sum(ms.values()) > 0, "no collectives -- decode not plan-routed"
# warmup -> serve plan-cache pin: every serve-window lookup hit
assert rep["serve_window"]["hit_rate"] == 1.0, rep
assert rp.plan_probe["probed"] > 0 and rp.plan_probe["missing"] == 0, \
    rp.plan_probe
# plan-routed greedy tokens == unrouted baseline, bitwise
assert rb.sequences == rp.sequences, (rb.sequences, rp.sequences)

# the module-level generate(mesh=...) path agrees too
batch, lens = batch_requests(prompts, pad_to=8)
routed = generate(model, params, batch, scfg, mesh=mesh, lens=lens)
unrouted = generate(model, params, batch, scfg, lens=lens)
assert np.array_equal(routed, unrouted), (routed, unrouted)

# second batch stays pinned at 100% hits
srv.generate([[4, 4], [7, 7, 7]])
rep2 = srv.cache_report()
assert rep2["serve_window"]["hit_rate"] == 1.0, rep2
assert cache_info()["misses"] == rep2["info"]["misses"]
print("SERVE_PLAN_OK")
"""


@pytest.mark.timeout(600)
def test_plan_routed_serving_subprocess():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(_root(), "src")
    res = subprocess.run(
        [sys.executable, "-c", _ROUTED_SCRIPT], capture_output=True,
        text=True, env=env, timeout=590)
    assert "SERVE_PLAN_OK" in res.stdout, (
        f"stdout:\n{res.stdout[-3000:]}\nstderr:\n{res.stderr[-3000:]}")
