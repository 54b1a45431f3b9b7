"""The MoE layer and a DeepSeekMoE decoder against the plain float32
reference (``tests/moe_reference.py``), on seeded weights at smoke size.

Parameters are float32 here, so the program and the reference route every
token alike and agree to float32 rounding; the bf16 model is compared on
the chip by the benchmark's cells."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import moe_reference as ref
from repro.configs import get_smoke_config
from repro.layers.moe import _rows_bound, moe_params, routed_experts
from repro.models.registry import build_model

# float32 rounding over a few layers, relative to the largest logit
TOL = 2e-4


def _cfg(**kw):
    return dataclasses.replace(get_smoke_config("deepseek_moe_16b"),
                               dtype="float32", **kw)


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


def _model(cfg, seed=0):
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(seed))


SHARES = [
    pytest.param({}, id="all-held"),
    pytest.param(dict(experts_held=4, first_held_expert=8), id="held-8-11"),
    pytest.param(dict(experts_held=4, first_held_expert=8,
                      norm_topk_prob=True), id="held-8-11-renormalised"),
    pytest.param(dict(norm_topk_prob=True), id="all-held-renormalised"),
]


@pytest.mark.parametrize("share", SHARES)
def test_forward_matches_reference(share):
    cfg = _cfg(**share)
    model, params = _model(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0,
                                cfg.vocab_size)
    logits, _ = jax.jit(model.forward)(params, tokens)
    want = ref.forward(cfg, params, tokens)
    assert _rel(logits[..., :cfg.vocab_size], want) < TOL


@pytest.mark.parametrize("share", SHARES[:2])
def test_prefill_then_decode_matches_reference(share):
    """Prefill through the cache, then decode token by token: every step's
    logits against the reference's full forward."""
    cfg = _cfg(**share)
    model, params = _model(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 20), 0,
                                cfg.vocab_size)
    want = ref.forward(cfg, params, tokens)
    cache = model.init_cache(2, 32)
    logits, cache = jax.jit(model.prefill)(params, cache, tokens[:, :12])
    got = [logits]
    step = jax.jit(model.decode_step)
    for t in range(12, 20):
        logits, cache = step(params, cache, tokens[:, t:t + 1], jnp.int32(t))
        got.append(logits)
    got = jnp.stack(got, axis=1)[..., :cfg.vocab_size]
    assert _rel(got, want[:, 11:]) < TOL


def _layer(cfg, seed=3, tokens=64):
    p = moe_params(jax.random.PRNGKey(seed), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (tokens, cfg.d_model))
    return p, x


def _share(p, first, held):
    return dict(p, **{w: p[w][first:first + held]
                      for w in ("w_gate", "w_up", "w_down")})


def test_shares_add_up_to_the_uncut_layer():
    """The routed parts of the four shares that hold experts 0-3, 4-7, 8-11
    and 12-15, with the shared experts counted once, add up to the uncut
    reference layer."""
    full = _cfg()
    p, x = _layer(full)
    total = jnp.zeros_like(x)
    for first in range(0, full.num_experts, 4):
        cfg = dataclasses.replace(full, experts_held=4,
                                  first_held_expert=first)
        total = total + routed_experts(_share(p, first, 4), x, cfg)[0]
    sh = p["shared"]
    with jax.default_matmul_precision("highest"):
        total = total + ref.swiglu(x, sh["w_gate"], sh["w_up"], sh["w_down"])
        want = ref.moe_layer(full, p, x)
    assert _rel(total, want) < 1e-5


@pytest.mark.parametrize("share", [
    pytest.param({}, id="all-held"),
    pytest.param(dict(experts_held=4, first_held_expert=0), id="held-0-3"),
])
def test_no_token_dropped_when_every_token_picks_one_expert(share):
    """A router that sends every token to experts 0, 1 and 2, with gates
    of about 0.14, 0.13 and 0.12: every pair is computed, however many
    rows one expert gets.  With a share held, the rows overflow the buffer
    sized for even routing."""
    cfg = _cfg(**share)
    p, x = _layer(cfg, tokens=1024)
    x = 2 * jnp.abs(x) / jnp.sum(jnp.abs(x), -1, keepdims=True)
    p["router"] = jnp.zeros_like(p["router"]).at[:, :3].set(
        jnp.asarray([1.0, 0.9, 0.8]))
    even, most = _rows_bound(x.shape[0], cfg.experts_held, cfg)
    assert even < 3 * x.shape[0] <= most or not share
    got, _ = routed_experts(p, x, cfg)
    with jax.default_matmul_precision("highest"):
        want = ref.expert_layer(cfg, p, x)
    assert _rel(got, want) < 1e-5


def test_padding_slots_reach_no_expert():
    """Rows marked invalid (a left-padded row's padding) get no routed
    part; the others get what they get alone."""
    cfg = _cfg(experts_held=4, first_held_expert=4)
    p, x = _layer(cfg)
    valid = jnp.arange(x.shape[0]) % 3 > 0
    got, _ = routed_experts(p, x, cfg, valid)
    alone, _ = routed_experts(p, x, cfg)
    assert not jnp.any(got[~valid])
    assert _rel(got[valid], alone[valid]) < 1e-6


def test_request_does_not_depend_on_batch_mates_or_left_padding():
    """A prompt's logits, prefilled and decoded alone, equal its logits
    left-padded into a batch beside another prompt."""
    cfg = _cfg(experts_held=4, first_held_expert=4)
    model, params = _model(cfg)
    prompt = [5, 17, 3, 99, 41]
    other = [7, 1, 250, 8, 8, 30, 2, 64, 11]
    alone_cache = model.init_cache(1, 16)
    batch_cache = model.init_cache(2, 16)
    prefill, step = jax.jit(model.prefill), jax.jit(model.decode_step)
    a, alone_cache = prefill(params, alone_cache, jnp.asarray([prompt]))
    pad = len(other) - len(prompt)
    offsets = jnp.asarray([pad, 0], jnp.int32)
    b, batch_cache = prefill(
        params, batch_cache, jnp.asarray([[0] * pad + prompt, other]), offsets)
    assert _rel(b[0], a[0]) < 1e-5
    for t in range(3):
        tok = jnp.argmax(a, -1)[:, None].astype(jnp.int32)
        a, alone_cache = step(params, alone_cache, tok, jnp.int32(len(prompt) + t))
        b, batch_cache = step(params, batch_cache,
                              jnp.concatenate([tok, tok]), jnp.int32(len(other) + t),
                              offsets)
        assert _rel(b[0], a[0]) < 1e-5


_MESH_SCRIPT = r"""
import dataclasses, os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
import repro.layers.moe as moe
from repro.configs import get_smoke_config
from repro.mesh import make_mesh
from repro.models.registry import build_model
from repro.models.sharding_rules import param_shardings
from repro.runtime.sharding import use_mesh

calls = []
real = moe._expert_parallel
moe._expert_parallel = lambda *a: calls.append(a[-1]) or real(*a)
cfg = dataclasses.replace(get_smoke_config("deepseek_moe_16b"),
                          dtype="float32", experts_held=4,
                          first_held_expert=8)
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, 256)
want, aux_want = jax.jit(model.forward)(params, tokens)
mesh = make_mesh((2, 2), ("data", "model"))
with use_mesh(mesh):
    got, aux = jax.jit(model.forward)(
        jax.device_put(params, param_shardings(params, mesh)), tokens)
err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
assert calls and set(calls) == {2}, calls
assert err < 1e-5, err
assert abs(float(aux) - float(aux_want)) < 1e-4 * float(aux_want)
print("MESH_MOE_OK")
"""


def test_expert_parallel_on_a_mesh_matches_one_device():
    """On a (data 2, model 2) mesh each device holds 2 of the 4 held
    experts; summed over ``model`` the logits and the load-balance loss
    are those of one device."""
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    res = subprocess.run([sys.executable, "-c", _MESH_SCRIPT],
                         capture_output=True, text=True, env=env, timeout=590)
    assert "MESH_MOE_OK" in res.stdout, res.stdout[-2000:] + res.stderr[-3000:]
