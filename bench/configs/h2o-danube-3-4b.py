"""h2o-danube-3-4b: its layer shapes, its weights made from a seed, and a
plain float32 forward pass that the served logits are compared with.

The architecture is a Llama-style decoder: RMSNorm before attention and
before the MLP, rotary positions (rotate-half), grouped-query attention
(32 query heads share 8 key/value heads of 120), a SiLU-gated MLP and an
untied output head.  The reference below follows that description with
``jax.numpy`` in float32 at ``Precision.HIGHEST`` and imports nothing of
the program.  It runs one layer at a time, so that the float32 copy of a
single layer's weights is all it adds to the chip's memory.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
# the linear weights of each layer
_LINEARS = ("q", "k", "v", "o", "gate", "up", "down")


def layers(cfg) -> int:
    return cfg["num_hidden_layers"]


def linears(cfg):
    """(name, k, n, input) of each GEMM of one layer, in the order the
    layer calls them; ``input`` names the activation it multiplies: the
    normed hidden state ``x``, the attention output ``attn`` or the gated
    MLP hidden ``ffn``."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    hq = cfg["num_attention_heads"] * cfg["head_dim"]
    hkv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return [("q", d, hq, "x"), ("k", d, hkv, "x"), ("v", d, hkv, "x"),
            ("o", hq, d, "attn"), ("gate", d, ff, "x"), ("up", d, ff, "x"),
            ("down", ff, d, "ffn")]


def head_params(cfg) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def kv_width(cfg) -> int:
    return cfg["num_key_value_heads"] * cfg["head_dim"]


def attn_width(cfg) -> int:
    return cfg["num_attention_heads"] * cfg["head_dim"]


def make_weights(cfg, key):
    """Every weight, made on the device in one jitted call from ``key``:
    linears stacked over layers as (L, k, n) bf16; norm scales float32."""
    return _make_weights(_frozen(cfg))(key)


def _frozen(cfg):
    keys = ("hidden_size", "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "head_dim", "intermediate_size",
            "vocab_size")
    return tuple((k, cfg[k]) for k in keys)


@functools.lru_cache(maxsize=None)
def _make_weights(frozen):
    cfg = dict(frozen)
    L, d, v = cfg["num_hidden_layers"], cfg["hidden_size"], cfg["vocab_size"]

    def make(key):
        ks = iter(jax.random.split(key, 16))
        w = {}
        for name, k, n, _ in linears(cfg):
            w[name] = (jax.random.normal(next(ks), (L, k, n), jnp.float32)
                       / math.sqrt(k)).astype(jnp.bfloat16)
        w["embedding"] = (0.02 * jax.random.normal(
            next(ks), (v, d), jnp.float32)).astype(jnp.bfloat16)
        w["lm_head"] = (0.02 * jax.random.normal(
            next(ks), (d, v), jnp.float32)).astype(jnp.bfloat16)
        for name, shape in (("attn_norm", (L, d)), ("mlp_norm", (L, d)),
                            ("final_norm", (d,))):
            w[name] = 1.0 + 0.1 * jax.random.normal(next(ks), shape,
                                                    jnp.float32)
        return w

    return jax.jit(make)


def to_program(w):
    """The same arrays in the parameter tree of the program's decoder
    (``repro.models.lm.DecoderLM``); no copy is made."""
    return {
        "embed": {"embedding": w["embedding"], "lm_head": w["lm_head"]},
        "final_norm": w["final_norm"],
        "layers": {
            "attn_norm": w["attn_norm"], "mlp_norm": w["mlp_norm"],
            "attn": {"wq": w["q"], "wk": w["k"], "wv": w["v"],
                     "wo": w["o"]},
            "mlp": {"w_gate": w["gate"], "w_up": w["up"],
                    "w_down": w["down"]},
        },
    }


def program_overrides(cfg):
    """The program's model-config fields, set from this configuration."""
    return dict(num_layers=cfg["num_hidden_layers"],
                d_model=cfg["hidden_size"],
                num_heads=cfg["num_attention_heads"],
                num_kv_heads=cfg["num_key_value_heads"],
                head_dim=cfg["head_dim"], d_ff=cfg["intermediate_size"],
                vocab_size=cfg["vocab_size"],
                window=0, rope_theta=cfg["rope_theta"],   # 0: no window
                norm_eps=cfg["rms_norm_eps"], tie_embeddings=False,
                dtype="bfloat16")


# -- plain reference ------------------------------------------------------------

def forward(cfg, w, tokens, quantize=None):
    """Logits (n, S, vocab) in float32 for ``tokens`` (n, S); position p
    of each row sees positions 0..p of that row.  ``quantize``, when
    given, rounds both operands of every matmul (as a function of
    (operand, axis to scale over)) before it multiplies: the lower-
    precision control."""
    fc = _frozen(cfg) + (("rms_norm_eps", cfg["rms_norm_eps"]),
                         ("rope_theta", cfg["rope_theta"]))
    layer, head = _programs(fc, quantize)
    x = jnp.take(w["embedding"], tokens, axis=0).astype(jnp.float32)
    for i in range(layers(cfg)):
        x = layer(x, {n: w[n][i] for n in _LINEARS + ("attn_norm",
                                                        "mlp_norm")})
    return head(x, w["final_norm"], w["lm_head"])


@functools.lru_cache(maxsize=None)
def _programs(frozen, quantize):
    cfg = dict(frozen)
    H, KV, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]

    def mm(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        if quantize is not None:
            a, b = quantize(a, -1), quantize(b, 0)
        return jnp.matmul(a, b, precision=HIGHEST)

    def norm(x, scale):
        return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale

    def rope(x, pos):             # x (n, S, heads, hd)
        inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
        ang = pos[:, None] * inv[None, :]            # (S, hd/2)
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def layer(x, p):
        n, S, _ = x.shape
        pos = jnp.arange(S, dtype=jnp.float32)
        h = norm(x, p["attn_norm"])
        q = rope(mm(h, p["q"]).reshape(n, S, H, hd), pos)
        k = rope(mm(h, p["k"]).reshape(n, S, KV, hd), pos)
        v = mm(h, p["v"]).reshape(n, S, KV, hd)
        k, v = jnp.repeat(k, H // KV, axis=2), jnp.repeat(v, H // KV, axis=2)
        if quantize is not None:
            q, k = quantize(q, -1), quantize(k, -1)
        s = jnp.einsum("nqhd,nkhd->nhqk", q, k,
                       precision=HIGHEST) / math.sqrt(hd)
        qi, ki = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
        s = jnp.where(ki <= qi, s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        if quantize is not None:
            a, v = quantize(a, -1), quantize(v, 1)
        o = jnp.einsum("nhqk,nkhd->nqhd", a, v, precision=HIGHEST)
        x = x + mm(o.reshape(n, S, H * hd), p["o"])
        h = norm(x, p["mlp_norm"])
        return x + mm(jax.nn.silu(mm(h, p["gate"])) * mm(h, p["up"]),
                      p["down"])

    def head(x, scale, w_head):
        return mm(norm(x, scale), w_head)

    return jax.jit(layer), jax.jit(head)
