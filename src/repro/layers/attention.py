"""Attention layers: GQA (optionally sliding-window) and MLA.

Two execution paths:
  * ``xla``   -- chunked masked einsum (scan over query chunks keeps the
                 score matrix O(chunk x S) instead of O(S^2)); this is the
                 path the multi-pod dry-run lowers, and its matmuls carry the
                 sharding annotations that GSPMD turns into collectives.
  * ``flash`` -- the Pallas kernel (repro.kernels.flash_attention) for real
                 TPU runs; numerically validated against the same oracle.

Decode paths maintain a KV cache: full cache for GQA, rolling window cache
for SWA (h2o-danube at 500k), and the *compressed latent* cache for MLA with
the absorbed-matmul decode (w_uk/w_uv folded into the query/output products
-- a schedule re-association in the spirit of the paper: same instruction
set X, different equivariant map).  A cached call writes only its new
slots, in place, into the cache it is given: ``at`` addresses the layer,
``()`` for a single layer's cache (B, S, ...), ``(i,)`` for layer i of a
stacked cache (L, B, S, ...).  A decode step (``pos`` given) attends over
that layer where it lies; a prefill (``pos`` None: the cache holds nothing
before the call) attends over its own keys, as training does.

The qkv/output projections route through ``layers.linear``: inside a
``repro.plan.planned_matmuls(mesh)`` scope they dispatch through the plan
engine (mesh-aware schedule, plan cache) instead of the purely local
multiply.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from .linear import linear, linear_params
from .norms import rms_norm, rms_norm_params
from .rope import apply_rope

Params = Dict[str, jax.Array]
Cache = Dict[str, jax.Array]


# ---------------------------------------------------------------------------
# masked chunked attention core (shared by GQA and MLA expanded paths)
# ---------------------------------------------------------------------------


def _mask(qpos: jax.Array, kpos: jax.Array, window: int,
          causal: bool = True) -> jax.Array:
    """(Lq, Skv) -- or, with batched positions, (B, Lq, Skv) -- boolean
    mask: causal + optional sliding window.  Negative key positions
    (unwritten rolling-cache slots and left-padding slots, whose logical
    position is slot - offset < 0) are always invalid.  ``qpos``/``kpos``
    may be (L,)/(S,) or per-row (B, L)/(B, S); the two layouts broadcast."""
    if causal:
        m = kpos[..., None, :] <= qpos[..., :, None]
    else:
        m = jnp.ones(jnp.broadcast_shapes(
            qpos[..., :, None].shape, kpos[..., None, :].shape), bool)
    m = jnp.logical_and(m, (kpos >= 0)[..., None, :])
    if window > 0:
        m = jnp.logical_and(m, kpos[..., None, :] > qpos[..., :, None] - window)
    return m


def _sdpa(q: jax.Array, k: jax.Array, v: jax.Array, qpos, kpos,
          window: int, scale: float, causal: bool = True,
          probs_dtype=jnp.float32) -> jax.Array:
    """q: (B, L, Hkv, G, Dk); k: (B, S, Hkv, Dk); v: (B, S, Hkv, Dv).

    Softmax statistics stay fp32; ``probs_dtype=bf16`` stores the
    probability matrix (the dominant S^2 traffic) at half width before the
    PV product -- the Sec.-Perf memory-term optimization.  QK/PV einsums
    run on native (bf16) operands with fp32 accumulation -- the MXU-native
    mode -- instead of materializing fp32 copies of K/V-cache-sized
    tensors (Sec. Perf, hillclimb C it2)."""
    s = jnp.einsum(
        "blhgd,bshd->blhgs", q, k, preferred_element_type=jnp.float32
    ) * scale
    m = _mask(qpos, kpos, window, causal)              # (L, S) or (B, L, S)
    if m.ndim == 2:
        m = m[None]
    s = jnp.where(m[:, :, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(probs_dtype)
    o = jnp.einsum(
        "blhgs,bshd->blhgd", p, v.astype(probs_dtype),
        preferred_element_type=jnp.float32,
    )
    return o.astype(v.dtype)


def chunked_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    qpos: jax.Array, kpos: jax.Array,
    *, window: int = 0, chunk: int = 1024, scale: Optional[float] = None,
    causal: bool = True, probs_dtype=jnp.float32,
) -> jax.Array:
    """q: (B, Sq, H, Dk) grouped against k/v: (B, Skv, Hkv, D*).
    Scans over query chunks so peak memory is O(B*chunk*H*Skv)."""
    b, sq, h, dk = q.shape
    _, skv, hkv, dv = v.shape
    g = h // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(dk)
    qg = q.reshape(b, sq, hkv, g, dk)
    if sq <= chunk:
        o = _sdpa(qg, k, v, qpos, kpos, window, scale, causal, probs_dtype)
        return o.reshape(b, sq, h, dv)
    assert sq % chunk == 0, (sq, chunk)
    nc = sq // chunk
    qc = qg.reshape(b, nc, chunk, hkv, g, dk).transpose(1, 0, 2, 3, 4, 5)
    if qpos.ndim == 2:  # per-row positions (B, Sq): chunk alongside q
        pc = qpos.reshape(b, nc, chunk).transpose(1, 0, 2)
    else:
        pc = qpos.reshape(nc, chunk)

    def body(_, qp):
        qi, pi = qp
        return None, _sdpa(qi, k, v, pi, kpos, window, scale, causal, probs_dtype)

    _, oc = jax.lax.scan(body, None, (qc, pc))
    o = oc.transpose(1, 0, 2, 3, 4, 5).reshape(b, sq, h, dv)
    return o


def _cache_write(cache: Cache, new: Cache, at: Tuple, slot) -> Cache:
    """Write each leaf of ``new`` (B, S, ...) into the layer of ``cache``
    that ``at`` addresses, from sequence slot ``slot``: one
    dynamic-update-slice a leaf, which a donated cache takes in place."""
    def put(buf, x):
        x = x.reshape((1,) * len(at) + x.shape)
        start = tuple(at) + (0, slot) + (0,) * (x.ndim - len(at) - 2)
        return jax.lax.dynamic_update_slice(buf, x, start)
    return {n: put(cache[n], new[n]) for n in new}


# ---------------------------------------------------------------------------
# GQA (covers MHA, MQA, SWA)
# ---------------------------------------------------------------------------


def gqa_params(key, cfg: ModelConfig, dtype=jnp.bfloat16) -> Params:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": linear_params(ks[0], d, h * hd, dtype),
        "wk": linear_params(ks[1], d, kv * hd, dtype),
        "wv": linear_params(ks[2], d, kv * hd, dtype),
        "wo": linear_params(ks[3], h * hd, d, dtype),
    }


def gqa_attention(
    p: Params, x: jax.Array, cfg: ModelConfig,
    positions: jax.Array,
    cache: Optional[Cache] = None,
    pos: Optional[jax.Array] = None,
    causal: bool = True,
    offsets: Optional[jax.Array] = None,
    at: Tuple = (),
) -> Tuple[jax.Array, Optional[Cache]]:
    """x: (B, S, d).  With a cache, writes this call's S keys/values into
    the layer ``at`` addresses from slot ``pos`` (0 when ``pos`` is None);
    attends over the call's own keys when ``pos`` is None (training, a
    prefill), else over that layer of the cache (module docstring).

    ``offsets`` (B,) shifts each row's logical positions for left-padded
    serving batches: cache slot j holds row i's logical position
    j - offsets[i], so padding slots land at negative positions and the
    ``kpos >= 0`` mask removes them -- a row left-padded by ``offsets[i]``
    attends to exactly the keys it would see decoded alone.  ``positions``
    must then be the matching per-row logical query positions (B, S)."""
    b, s, d = x.shape
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = linear(x, p["wq"]).reshape(b, s, h, hd)
    k = linear(x, p["wk"]).reshape(b, s, kv, hd)
    v = linear(x, p["wv"]).reshape(b, s, kv, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)

    pdt = jnp.bfloat16 if cfg.attn_probs_dtype == "bf16" else jnp.float32
    new_cache = None
    if cache is not None:
        s_cache = cache["k"].shape[len(at) + 1]
        rolling = cfg.window > 0 and s_cache == cfg.window
        slot = 0 if pos is None else (pos % s_cache) if rolling else pos
        new_cache = _cache_write(cache, {"k": k, "v": v}, at, slot)
    if pos is None:  # train, or a prefill: the call's own keys
        o = chunked_attention(
            q, k, v, positions, positions,
            window=cfg.window, chunk=cfg.attn_chunk, causal=causal,
            probs_dtype=pdt,
        )
    else:
        ck, cv = new_cache["k"][at], new_cache["v"][at]
        idx = jnp.arange(s_cache)
        if rolling:
            # slot i holds position pos - ((pos - i) mod W); invalid (< 0)
            # slots are masked by the causal check against qpos = pos.
            kpos = pos - jnp.mod(pos - idx, s_cache)
        else:
            kpos = idx
        if offsets is not None:
            kpos = kpos[None, :] - offsets[:, None]  # per-row logical slots
        o = chunked_attention(
            q, ck, cv, positions, kpos,
            window=cfg.window, chunk=cfg.attn_chunk, probs_dtype=pdt,
        )
    o = linear(o.reshape(b, s, h * hd), p["wo"])
    return o, new_cache


def gqa_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype=jnp.bfloat16) -> Cache:
    s = min(max_seq, cfg.window) if cfg.window > 0 else max_seq
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((batch, s, kv, hd), dtype),
        "v": jnp.zeros((batch, s, kv, hd), dtype),
    }


# ---------------------------------------------------------------------------
# MLA (minicpm3): latent-compressed KV with absorbed decode
# ---------------------------------------------------------------------------


def mla_params(key, cfg: ModelConfig, dtype=jnp.bfloat16) -> Params:
    d, h = cfg.d_model, cfg.num_heads
    qr, kvr = cfg.q_lora_rank, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    ks = jax.random.split(key, 5)
    return {
        "wq_a": linear_params(ks[0], d, qr, dtype),
        "q_norm": rms_norm_params(qr),
        "wq_b": linear_params(ks[1], qr, h * (nope + rope), dtype),
        "wkv_a": linear_params(ks[2], d, kvr + rope, dtype),
        "kv_norm": rms_norm_params(kvr),
        "wkv_b": linear_params(ks[3], kvr, h * (nope + vd), dtype),
        "wo": linear_params(ks[4], h * vd, d, dtype),
    }


def _mla_q(p: Params, x, cfg: ModelConfig, positions):
    b, s, _ = x.shape
    h = cfg.num_heads
    nope, rope = cfg.qk_nope_dim, cfg.qk_rope_dim
    q = linear(rms_norm(linear(x, p["wq_a"]), p["q_norm"], cfg.norm_eps), p["wq_b"])
    q = q.reshape(b, s, h, nope + rope)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_latent(p: Params, x, cfg: ModelConfig, positions):
    kvr, rope = cfg.kv_lora_rank, cfg.qk_rope_dim
    kv = linear(x, p["wkv_a"])
    c_kv = rms_norm(kv[..., :kvr], p["kv_norm"], cfg.norm_eps)
    k_rope = kv[..., kvr:][:, :, None, :]  # (B, S, 1, rope): shared head
    k_rope = apply_rope(k_rope, positions, cfg.rope_theta)[:, :, 0, :]
    return c_kv, k_rope


def mla_attention(
    p: Params, x: jax.Array, cfg: ModelConfig,
    positions: jax.Array,
    cache: Optional[Cache] = None,
    pos: Optional[jax.Array] = None,
    offsets: Optional[jax.Array] = None,
    at: Tuple = (),
) -> Tuple[jax.Array, Optional[Cache]]:
    """As ``gqa_attention``; a cache holds the latent ``c_kv`` and the
    shared ``k_rope``, attended in the latent space (absorbed decode)."""
    b, s, d = x.shape
    h = cfg.num_heads
    nope, rope, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    kvr = cfg.kv_lora_rank
    scale = 1.0 / math.sqrt(nope + rope)
    q_nope, q_rope = _mla_q(p, x, cfg, positions)
    c_kv, k_rope = _mla_latent(p, x, cfg, positions)

    new_cache = None
    if cache is not None:
        new_cache = _cache_write(cache, {"c_kv": c_kv, "k_rope": k_rope},
                                 at, 0 if pos is None else pos)
    if pos is None:
        # expanded path: materialize per-head K/V from the latent
        kvb = linear(c_kv, p["wkv_b"]).reshape(b, s, h, nope + vd)
        k_nope, v = kvb[..., :nope], kvb[..., nope:]
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (b, s, h, rope))],
            axis=-1,
        )
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        o = chunked_attention(
            q, k, v, positions, positions, chunk=cfg.attn_chunk, scale=scale,
            probs_dtype=jnp.bfloat16 if cfg.attn_probs_dtype == "bf16"
            else jnp.float32,
        )
    else:
        # absorbed decode: attend in the kv_lora_rank latent space
        cc, cr = new_cache["c_kv"][at], new_cache["k_rope"][at]
        # wkv_b columns are per-head blocks of (nope + vd) -- must match the
        # expanded path's reshape(b, s, h, nope + vd) exactly
        w_b = p["wkv_b"].reshape(kvr, h, nope + vd)
        w_uk = w_b[:, :, :nope]
        w_uv = w_b[:, :, nope:]
        q_c = jnp.einsum(  # fold w_uk into q
            "bshn,lhn->bshl", q_nope.astype(jnp.float32),
            w_uk.astype(jnp.float32),
        )
        sc = jnp.einsum("bshl,btl->bsht", q_c, cc.astype(jnp.float32))
        sc += jnp.einsum(
            "bshr,btr->bsht", q_rope.astype(jnp.float32),
            cr.astype(jnp.float32),
        )
        sc *= scale
        kpos = jnp.arange(cc.shape[1])
        if offsets is not None:
            # per-row logical slot positions; left-padding slots (< 0)
            # are masked out, matching the GQA kpos >= 0 convention
            kpos_b = kpos[None, :] - offsets[:, None]        # (B, T)
            valid = jnp.logical_and(
                kpos_b[:, None, :] <= positions[:, :, None],  # (B, S, T)
                kpos_b[:, None, :] >= 0)
            sc = jnp.where(valid[:, :, None, :], sc, -1e30)
        else:
            valid = kpos[None, :] <= positions[:, None]      # (S, T)
            sc = jnp.where(valid[None, :, None, :], sc, -1e30)
        pr = jax.nn.softmax(sc, axis=-1)
        att_c = jnp.einsum("bsht,btl->bshl", pr, cc.astype(jnp.float32))
        o = jnp.einsum("bshl,lhv->bshv", att_c, w_uv.astype(jnp.float32))
        o = o.astype(x.dtype)
    o = linear(o.reshape(b, s, h * vd), p["wo"])
    return o, new_cache


def mla_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype=jnp.bfloat16) -> Cache:
    return {
        "c_kv": jnp.zeros((batch, max_seq, cfg.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, max_seq, cfg.qk_rope_dim), dtype),
    }
