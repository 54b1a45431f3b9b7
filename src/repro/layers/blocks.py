"""Residual blocks assembling the layer zoo, + per-block param init.

Block kinds:
  attn_mlp   -- pre-norm attention + dense SwiGLU (llama family, chameleon)
  attn_moe   -- pre-norm attention + MoE (qwen3-moe, deepseek-moe)
  mamba      -- pre-norm Mamba-2 only (zamba2 backbone)
  mlstm/slstm-- xLSTM blocks (no FFN at 350m scale)
  enc_attn_mlp / dec block variants live in models/encdec.py

Every block returns (x, aux, new_cache); aux carries the MoE load-balance
loss.  Activation sharding constraints pin (batch, seq, d_model) layouts at
block boundaries so GSPMD propagates TP shardings inward.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.runtime.sharding import constrain
from .attention import (gqa_attention, gqa_params, mla_attention, mla_params)
from .mamba2 import mamba2, mamba2_params
from .mlp import mlp, mlp_params
from .moe import moe, moe_params
from .norms import rms_norm, rms_norm_params
from .xlstm import mlstm, mlstm_params, slstm, slstm_params

Params = Dict


def block_params(key, cfg: ModelConfig, kind: str, dtype=jnp.bfloat16) -> Params:
    d = cfg.d_model
    k1, k2 = jax.random.split(key)
    if kind == "attn_mlp":
        attn = mla_params if cfg.attn_type == "mla" else gqa_params
        return {
            "attn_norm": rms_norm_params(d),
            "attn": attn(k1, cfg, dtype),
            "mlp_norm": rms_norm_params(d),
            "mlp": mlp_params(k2, d, cfg.d_ff, dtype),
        }
    if kind == "attn_moe":
        attn = mla_params if cfg.attn_type == "mla" else gqa_params
        return {
            "attn_norm": rms_norm_params(d),
            "attn": attn(k1, cfg, dtype),
            "mlp_norm": rms_norm_params(d),
            "moe": moe_params(k2, cfg, dtype),
        }
    if kind == "mamba":
        return {"norm": rms_norm_params(d), "mamba": mamba2_params(k1, cfg, dtype)}
    if kind == "mlstm":
        return {"norm": rms_norm_params(d), "mlstm": mlstm_params(k1, cfg, dtype)}
    if kind == "slstm":
        return {"norm": rms_norm_params(d), "slstm": slstm_params(k1, cfg, dtype)}
    raise ValueError(kind)


def block_apply(
    p: Params, x: jax.Array, cfg: ModelConfig, kind: str,
    positions: jax.Array,
    cache: Optional[Dict] = None,
    pos: Optional[jax.Array] = None,
    offsets: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, Optional[Dict]]:
    """``offsets`` (B,) carries per-row left-padding amounts down to the
    attention layers (logical-position masking for padded serving batches);
    the recurrent kinds have no position concept and ignore it."""
    aux = jnp.zeros((), jnp.float32)
    x = constrain(x, "batch", None, None)
    if kind in ("attn_mlp", "attn_moe"):
        attn_fn = mla_attention if cfg.attn_type == "mla" else gqa_attention
        h = rms_norm(x, p["attn_norm"], cfg.norm_eps)
        a, new_cache = attn_fn(p["attn"], h, cfg, positions, cache, pos,
                               offsets=offsets)
        x = x + constrain(a, "batch", None, None)
        h = rms_norm(x, p["mlp_norm"], cfg.norm_eps)
        if kind == "attn_mlp":
            m = mlp(p["mlp"], h)
        else:
            # a left-padded row's padding slots sit at negative positions:
            # no expert computes them
            valid = (jnp.broadcast_to(positions >= 0, h.shape[:2])
                     if offsets is not None else None)
            m, aux = moe(p["moe"], h, cfg, valid)
        x = x + constrain(m, "batch", None, None)
        return x, aux, new_cache
    if kind == "mamba":
        h = rms_norm(x, p["norm"], cfg.norm_eps)
        m, new_cache = mamba2(p["mamba"], h, cfg, cache, pos)
        return x + m, aux, new_cache
    if kind == "mlstm":
        h = rms_norm(x, p["norm"], cfg.norm_eps)
        m, new_cache = mlstm(p["mlstm"], h, cfg, cache, pos)
        return x + m, aux, new_cache
    if kind == "slstm":
        h = rms_norm(x, p["norm"], cfg.norm_eps)
        m, new_cache = slstm(p["slstm"], h, cfg, cache, pos)
        return x + m, aux, new_cache
    raise ValueError(kind)
