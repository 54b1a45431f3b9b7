"""Per-architecture smoke tests + recurrence/attention consistency checks."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config, get_smoke_config
from repro.models.registry import build_model

B, S = 2, 64


def _batch(cfg, key=0):
    tokens = jax.random.randint(jax.random.PRNGKey(key), (B, S), 0, cfg.vocab_size)
    labels = jax.random.randint(jax.random.PRNGKey(key + 1), (B, S), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "labels": labels}
    if cfg.family == "audio":
        batch["src_embed"] = jax.random.normal(
            jax.random.PRNGKey(key + 2), (B, S, cfg.d_model)
        )
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_train_step(arch):
    """Reduced config: one forward + loss on CPU; shapes + finiteness."""
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg)
    loss, metrics = jax.jit(model.loss)(params, batch)
    assert jnp.isfinite(loss)
    assert 0.0 < float(loss) < 20.0
    logits, _ = model.forward(params, batch["tokens"]) if cfg.family != "audio" \
        else model.forward(params, batch)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_decode_step(arch):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    cache = model.init_cache(B, 32)
    tok = jnp.zeros((B, 1), jnp.int32)
    logits, new_cache = jax.jit(model.decode_step)(params, cache, tok, jnp.int32(0))
    assert logits.shape == (B, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))
    # cache structure preserved
    assert jax.tree.structure(cache) == jax.tree.structure(new_cache)


@pytest.mark.parametrize("arch", ["llama3_2_1b", "minicpm3_4b", "h2o_danube3_4b",
                                  "xlstm_350m", "zamba2_2_7b"])
def test_decode_matches_forward(arch):
    """Step-by-step decode logits must match the parallel forward pass --
    the strongest cache-correctness check (covers GQA full cache, SWA
    rolling cache, MLA absorbed decode, mLSTM/sLSTM and SSD states)."""
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    T = 24
    tokens = jax.random.randint(jax.random.PRNGKey(7), (B, T), 0, cfg.vocab_size)
    fwd_logits, _ = model.forward(params, tokens)

    cache = model.init_cache(B, T)
    step_fn = jax.jit(model.decode_step)
    errs = []
    for t in range(T):
        logits, cache = step_fn(params, cache, tokens[:, t : t + 1], jnp.int32(t))
        ref = fwd_logits[:, t]
        errs.append(float(jnp.max(jnp.abs(logits - ref))))
    scale = float(jnp.max(jnp.abs(fwd_logits))) + 1e-6
    assert max(errs) / scale < 0.08, f"max rel err {max(errs)/scale}"


def test_moe_load_balance_loss_positive():
    cfg = get_smoke_config("qwen3_moe_30b_a3b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    _, aux = model.forward(params, _batch(cfg)["tokens"])
    assert float(aux) > 0.5  # ~1.0 for balanced routing


def test_param_count_formula_matches_init():
    import dataclasses
    held = dataclasses.replace(get_smoke_config("deepseek_moe_16b"),
                               experts_held=4, first_held_expert=12)
    for cfg in [get_smoke_config(a) for a in
                ("llama3_2_1b", "qwen3_moe_30b_a3b", "zamba2_2_7b",
                 "deepseek_moe_16b")] + [held]:
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        actual = sum(np.prod(p.shape) for p in jax.tree.leaves(params))
        expect = cfg.param_count()
        assert abs(actual - expect) / actual < 0.05, (cfg.name, actual, expect)
    # a token touches at most top_k of the 4 experts held
    expert = 3 * held.d_model * held.moe_d_ff
    moe_layers = held.num_layers - held.first_dense_layers
    assert held.param_count() - held.active_param_count() == (
        (4 - min(held.top_k, 4)) * expert * moe_layers)


def test_full_configs_match_assignment():
    """Exact published dims from the assignment table."""
    c = get_config("llama3.2-1b")
    assert (c.num_layers, c.d_model, c.num_heads, c.num_kv_heads,
            c.d_ff, c.vocab_size) == (16, 2048, 32, 8, 8192, 128256)
    c = get_config("granite-20b")
    assert (c.num_layers, c.d_model, c.num_heads, c.num_kv_heads) == (52, 6144, 48, 1)
    c = get_config("qwen3-moe-30b-a3b")
    assert (c.num_experts, c.top_k, c.vocab_size) == (128, 8, 151936)
    c = get_config("deepseek-moe-16b")
    assert (c.num_layers, c.first_dense_layers, c.d_model, c.num_heads,
            c.num_kv_heads, c.head_dim, c.d_ff, c.vocab_size) == (
        28, 1, 2048, 16, 16, 128, 10944, 102400)
    assert (c.num_experts, c.experts_held, c.num_shared_experts, c.top_k,
            c.moe_d_ff, c.norm_topk_prob, c.norm_eps) == (
        64, 64, 2, 6, 1408, False, 1e-6)
    c = get_config("zamba2-2.7b")
    assert (c.num_layers, c.d_model, c.ssm_state) == (54, 2560, 64)
    c = get_config("minicpm3-4b")
    assert (c.q_lora_rank, c.kv_lora_rank) == (768, 256)
    c = get_config("seamless-m4t-medium")
    assert (c.enc_layers, c.dec_layers, c.vocab_size) == (12, 12, 256206)
    c = get_config("h2o-danube-3-4b")
    assert (c.num_layers, c.d_model, c.window) == (24, 3840, 4096)
    c = get_config("chameleon-34b")
    assert (c.num_layers, c.d_model, c.vocab_size) == (48, 8192, 65536)
    c = get_config("xlstm-350m")
    assert (c.num_layers, c.d_model, c.d_ff) == (24, 1024, 0)


class TestRecurrentCores:
    def test_ssd_chunked_vs_recurrent(self):
        from repro.layers.mamba2 import _ssd_chunk_scan
        B_, S_, H_, P_, N_ = 2, 32, 3, 4, 5
        xh = jax.random.normal(jax.random.PRNGKey(0), (B_, S_, H_, P_))
        dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(1), (B_, S_, H_)))
        Bm = jax.random.normal(jax.random.PRNGKey(2), (B_, S_, N_))
        Cm = jax.random.normal(jax.random.PRNGKey(3), (B_, S_, N_))
        A = -jnp.exp(jax.random.normal(jax.random.PRNGKey(4), (H_,)))
        y8, _ = _ssd_chunk_scan(xh, dt, Bm, Cm, A, chunk=8)
        y16, _ = _ssd_chunk_scan(xh, dt, Bm, Cm, A, chunk=16)
        assert float(jnp.max(jnp.abs(y8 - y16))) < 1e-4  # chunk-invariance

        h = jnp.zeros((B_, H_, P_, N_))
        ys = []
        for t in range(S_):
            a = jnp.exp(dt[:, t] * A)
            h = h * a[:, :, None, None] + jnp.einsum(
                "bh,bn,bhp->bhpn", dt[:, t], Bm[:, t], xh[:, t])
            ys.append(jnp.einsum("bn,bhpn->bhp", Cm[:, t], h))
        ref = jnp.stack(ys, axis=1)
        assert float(jnp.max(jnp.abs(y8 - ref))) < 1e-4

    def test_mlstm_chunked_vs_recurrent(self):
        from repro.layers.xlstm import _mlstm_chunk_scan
        B_, S_, H_, D_ = 2, 32, 2, 4
        q = jax.random.normal(jax.random.PRNGKey(5), (B_, S_, H_, D_))
        k = jax.random.normal(jax.random.PRNGKey(6), (B_, S_, H_, D_))
        v = jax.random.normal(jax.random.PRNGKey(7), (B_, S_, H_, D_))
        li = jax.nn.log_sigmoid(jax.random.normal(jax.random.PRNGKey(8), (B_, S_, H_)))
        lf = jax.nn.log_sigmoid(jax.random.normal(jax.random.PRNGKey(9), (B_, S_, H_)) + 2)
        y, _ = _mlstm_chunk_scan(q, k, v, li, lf, chunk=8)
        scale = D_ ** -0.5
        C = jnp.zeros((B_, H_, D_, D_)); n = jnp.zeros((B_, H_, D_))
        ys = []
        for t in range(S_):
            f = jnp.exp(lf[:, t]); i = jnp.exp(li[:, t])
            C = C * f[:, :, None, None] + jnp.einsum("bhd,bhe,bh->bhde", k[:, t], v[:, t], i)
            n = n * f[:, :, None] + k[:, t] * i[:, :, None]
            yt = jnp.einsum("bhd,bhde->bhe", q[:, t], C) * scale
            qn = jnp.einsum("bhd,bhd->bh", q[:, t], n) * scale
            ys.append(yt / jnp.maximum(jnp.abs(qn), 1.0)[..., None])
        ref = jnp.stack(ys, axis=1)
        assert float(jnp.max(jnp.abs(y - ref))) < 1e-4


@pytest.mark.parametrize("arch", ["llama3_2_1b", "minicpm3_4b",
                                  "h2o_danube3_4b"])
def test_prefill_matches_stepwise_decode(arch):
    """One-pass prefill must fill the cache identically to step-by-step
    decode (and return the same last-token logits); h2o-danube's smoke
    config fills a rolling window of 16 with 12 prompt tokens."""
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    T = 12
    tokens = jax.random.randint(jax.random.PRNGKey(3), (B, T), 0, cfg.vocab_size)

    cache_a = model.init_cache(B, 32)
    logits_a, cache_a = jax.jit(model.prefill)(params, cache_a, tokens)

    cache_b = model.init_cache(B, 32)
    step = jax.jit(model.decode_step)
    for t in range(T):
        logits_b, cache_b = step(params, cache_b, tokens[:, t : t + 1], jnp.int32(t))

    scale = float(jnp.max(jnp.abs(logits_b))) + 1e-6
    assert float(jnp.max(jnp.abs(logits_a - logits_b))) / scale < 0.05
    err = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                           - b.astype(jnp.float32)))),
        cache_a, cache_b,
    )
    assert max(jax.tree.leaves(err)) < 0.05, err

    # continuing decode from the prefilled cache matches too
    nxt = jnp.zeros((B, 1), jnp.int32)
    la, _ = step(params, cache_a, nxt, jnp.int32(T))
    lb, _ = step(params, cache_b, nxt, jnp.int32(T))
    assert float(jnp.max(jnp.abs(la - lb))) / scale < 0.05


def _xs_ys_forward(model, params, cache, tokens, positions, pos,
                   offsets=None, layouts=None):
    """The cached layer scan as it was before the cache was carried: each
    layer's cache goes in as the scan's ``xs`` and comes back, rewritten
    whole, as its ``ys``."""
    from repro.layers.blocks import block_apply
    from repro.layers.embed import embed, unembed
    from repro.layers.norms import rms_norm

    cfg = model.cfg
    x = embed(params["embed"], tokens)
    new_cache = {}
    stacks = (("dense_layers", "attn_mlp"), ("layers", model.kind))
    for name, kind in stacks:
        if name not in cache:
            continue

        def body(x, lp_lc, kind=kind):
            lp, lc = lp_lc
            x, _, nc = block_apply(lp, x, cfg, kind, positions, lc, pos,
                                   offsets)
            return x, nc

        x, new_cache[name] = jax.lax.scan(body, x,
                                          (params[name], cache[name]))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return unembed(params["embed"], x, cfg.vocab_size)[:, -1], new_cache


# GQA full cache, rolling window (16 slots, crossed below), MQA, MLA, and
# MHA with a leading dense layer
@pytest.mark.parametrize("with_offsets", [False, True])
@pytest.mark.parametrize("arch", ["llama3_2_1b", "h2o_danube3_4b",
                                  "granite_20b", "minicpm3_4b",
                                  "deepseek_moe_16b"])
def test_carried_cache_matches_xs_ys_scan(arch, with_offsets, monkeypatch):
    """The carried, in-place cache gives bitwise the logits and cache
    contents of the old xs/ys layer scan, through a prefill and decode
    steps past the rolling window."""
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompt, steps, max_seq = 8, 12, 32
    tokens = jax.random.randint(jax.random.PRNGKey(5), (B, prompt + steps),
                                1, cfg.vocab_size)
    offsets = jnp.asarray([0, 3], jnp.int32) if with_offsets else None

    def run():
        extra = (offsets,) if with_offsets else ()
        cache = model.init_cache(B, max_seq)
        logits, cache = jax.jit(model.prefill)(params, cache,
                                               tokens[:, :prompt], *extra)
        out = [logits]
        step = jax.jit(model.decode_step)
        for t in range(prompt, prompt + steps):
            logits, cache = step(params, cache, tokens[:, t:t + 1],
                                 jnp.int32(t), *extra)
            out.append(logits)
        return out, cache

    new_logits, new_cache = run()
    monkeypatch.setattr(model, "_cached_forward",
                        lambda *a: _xs_ys_forward(model, *a))
    old_logits, old_cache = run()
    for t, (a, b) in enumerate(zip(new_logits, old_logits)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), t
    assert jax.tree.structure(new_cache) == jax.tree.structure(old_cache)
    for a, b in zip(jax.tree.leaves(new_cache), jax.tree.leaves(old_cache)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("arch", ["llama3_2_1b", "minicpm3_4b",
                                  "deepseek_moe_16b"])
def test_prefill_reads_nothing_of_the_cache(arch):
    """A prefill attends over the prompt's own keys and only writes the
    cache: given one full of NaN it returns bitwise the logits, and the
    prompt's slots, of a fresh one (a read of a masked slot would carry
    the NaN through its zero weight)."""
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompt, max_seq = 8, 32
    tokens = jax.random.randint(jax.random.PRNGKey(3), (B, prompt), 1,
                                cfg.vocab_size)
    offsets = jnp.asarray([0, 3], jnp.int32)
    fresh = model.init_cache(B, max_seq)
    stale = jax.tree.map(lambda a: jnp.full(a.shape, jnp.nan, a.dtype),
                         fresh)
    prefill = jax.jit(model.prefill)
    la, ca = prefill(params, fresh, tokens, offsets)
    lb, cb = prefill(params, stale, tokens, offsets)
    assert np.array_equal(np.asarray(la), np.asarray(lb))
    for a, b in zip(jax.tree.leaves(ca), jax.tree.leaves(cb)):
        assert np.array_equal(np.asarray(a[:, :, :prompt]),
                              np.asarray(b[:, :, :prompt]))
