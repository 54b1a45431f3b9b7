"""Decoder-only LM covering the dense / MoE / VLM (early-fusion) families.

Layers are *stacked* (one leading L axis per parameter) and executed with
``jax.lax.scan`` so the HLO -- and hence the 512-device dry-run compile time
-- is depth-independent.  deepseek-moe's leading dense layers live in their
own (short) stack.  Decode carries each stacked KV cache through the same
scan and updates it in place.  Remat policy is configurable per config
(none | dots | full).
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.layout import with_layout_constraint

from repro.layers.blocks import block_apply, block_params
from repro.layers.attention import gqa_cache, mla_cache
from repro.layers.embed import embed, embed_params, unembed
from repro.layers.norms import rms_norm, rms_norm_params
from repro.models.config import ModelConfig
from repro.runtime.sharding import constrain

Params = Dict
Cache = Dict


def _remat(fn, cfg: ModelConfig):
    if cfg.remat == "full":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        )
    return fn


def _stack_init(key, n: int, mk):
    return jax.vmap(mk)(jax.random.split(key, n))


class DecoderLM:
    # serving can hand this model left-padded batches with per-row position
    # offsets (see ``prefill``/``decode_step``); the recurrent families
    # cannot (their state carries pad tokens forward), so the serving
    # runtime checks this flag before passing offsets.
    supports_position_offsets = True

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.kind = "attn_moe" if cfg.num_experts else "attn_mlp"
        self.dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32

    # -- params -------------------------------------------------------------
    def init(self, key) -> Params:
        cfg = self.cfg
        k_e, k_d, k_l = jax.random.split(key, 3)
        params: Params = {
            "embed": embed_params(
                k_e, cfg.vocab_size, cfg.d_model, cfg.tie_embeddings, self.dtype
            ),
            "final_norm": rms_norm_params(cfg.d_model),
        }
        nd = cfg.first_dense_layers
        if nd:
            params["dense_layers"] = _stack_init(
                k_d, nd, lambda k: block_params(k, cfg, "attn_mlp", self.dtype)
            )
        params["layers"] = _stack_init(
            k_l, cfg.num_layers - nd,
            lambda k: block_params(k, cfg, self.kind, self.dtype),
        )
        return params

    # -- forward ------------------------------------------------------------
    def forward(self, params: Params, tokens: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """tokens: (B, S) -> (logits (B, S, V) fp32, aux loss)."""
        cfg = self.cfg
        x = embed(params["embed"], tokens)
        x = constrain(x, "batch", None, None)
        positions = jnp.arange(tokens.shape[1])
        aux0 = jnp.zeros((), jnp.float32)

        if cfg.first_dense_layers:
            def dense_body(carry, lp):
                x, aux = carry
                x, a, _ = block_apply(lp, x, cfg, "attn_mlp", positions)
                return (x, aux + a), None
            (x, aux0), _ = jax.lax.scan(
                _remat(dense_body, cfg), (x, aux0), params["dense_layers"]
            )

        def body(carry, lp):
            x, aux = carry
            x, a, _ = block_apply(lp, x, cfg, self.kind, positions)
            return (x, aux + a), None

        (x, aux), _ = jax.lax.scan(_remat(body, cfg), (x, aux0), params["layers"])
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = unembed(params["embed"], x, cfg.vocab_size)
        logits = constrain(logits, "batch", None, "model")
        return logits, aux

    def loss(self, params: Params, batch: Dict) -> Tuple[jax.Array, Dict]:
        logits, aux = self.forward(params, batch["tokens"])
        ce = cross_entropy(logits, batch["labels"])
        total = ce + 0.01 * aux
        return total, {"ce": ce, "aux": aux}

    # -- decode ---------------------------------------------------------------
    def init_cache(self, batch: int, max_seq: int) -> Cache:
        cfg = self.cfg
        mk = mla_cache if cfg.attn_type == "mla" else gqa_cache
        one = mk(cfg, batch, max_seq, self.dtype)
        nd = cfg.first_dense_layers
        cache: Cache = {
            "layers": jax.tree.map(
                lambda a: jnp.zeros((cfg.num_layers - nd,) + a.shape, a.dtype), one
            )
        }
        if nd:
            cache["dense_layers"] = jax.tree.map(
                lambda a: jnp.zeros((nd,) + a.shape, a.dtype), one
            )
        return cache

    def prefill(
        self, params: Params, cache: Cache, tokens: jax.Array,
        offsets: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, Cache]:
        """One-pass prompt ingestion: runs the full (B, S_prompt) forward,
        attending over the prompt's own keys, and writes K/V at slots
        [0, S) of ``cache``; returns last-token logits + the cache.  The
        production serving path: prompt cost is one forward instead of
        S_prompt decode steps.

        ``offsets`` (B,) marks per-row left-padding: row i's logical token
        positions become arange(S) - offsets[i], so its padding slots sit
        at negative positions and attention masks them out -- a prompt
        left-padded into a bucket decodes exactly as it would alone."""
        positions = jnp.arange(tokens.shape[1])
        if offsets is not None:
            positions = positions[None, :] - offsets[:, None]
        return self._cached_forward(params, cache, tokens, positions, None,
                                    offsets)

    def decode_step(
        self, params: Params, cache: Cache, tokens: jax.Array, pos: jax.Array,
        offsets: Optional[jax.Array] = None, layouts: Optional[Cache] = None,
    ) -> Tuple[jax.Array, Cache]:
        """tokens: (B, 1); pos: scalar int32 (the absolute cache slot).
        Returns (logits (B, V), cache).  ``offsets`` as in ``prefill``:
        row i's logical query position is pos - offsets[i].  ``layouts``
        (a tree like ``cache`` of ``jax.experimental.layout.Layout``) holds
        each stacked cache to its device layout through the layer loop;
        without it XLA may relayout the whole stack on the loop's entry and
        exit, to suit the attention."""
        if offsets is not None:
            positions = pos - offsets[:, None]  # (B, 1) logical positions
        else:
            positions = jnp.full((1,), pos, jnp.int32)
        return self._cached_forward(params, cache, tokens, positions, pos,
                                    offsets, layouts)

    def _cached_forward(
        self, params: Params, cache: Cache, tokens: jax.Array,
        positions: jax.Array, pos: Optional[jax.Array],
        offsets: Optional[jax.Array] = None, layouts: Optional[Cache] = None,
    ) -> Tuple[jax.Array, Cache]:
        """The layer scan carries each stacked cache: layer i writes its new
        slots into the stack at i and attends over the stack's layer i, so
        a donated cache is updated in place, with no per-layer copy in or
        out of the scan."""
        cfg = self.cfg
        x = embed(params["embed"], tokens)
        new_cache: Cache = {}
        stacks = (("dense_layers", "attn_mlp"), ("layers", self.kind))
        for name, kind in stacks:
            if name not in cache:
                continue

            def body(carry, lp_i, kind=kind):
                x, stack = carry
                lp, i = lp_i
                if layouts is not None:
                    stack = with_layout_constraint(stack, layouts[name])
                x, _, stack = block_apply(lp, x, cfg, kind, positions, stack,
                                          pos, offsets, at=(i,))
                return (x, stack), None

            n = jax.tree.leaves(cache[name])[0].shape[0]
            (x, new_cache[name]), _ = jax.lax.scan(
                body, (x, cache[name]), (params[name], jnp.arange(n)))
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = unembed(params["embed"], x, cfg.vocab_size)[:, -1]
        return logits, new_cache


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """logits fp32 (B, S, V); labels (B, S) with -100 = ignore."""
    valid = labels >= 0
    labels_c = jnp.clip(labels, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels_c[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * valid
    return jnp.sum(nll) / jnp.clip(jnp.sum(valid), 1)
