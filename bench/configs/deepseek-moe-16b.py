"""deepseek-moe-16b at one chip's expert share: its layer shapes, its
weights made from a seed, and a plain float32 forward pass that the
served logits are compared with, plus the held experts' part of one MoE
layer, which the engine cell's output is compared with.

The architecture (DeepSeekMoE, arXiv:2401.06066) is a Llama-style decoder
whose MLPs after the first layer are MoE layers: RMSNorm before attention
and before the MLP, rotary positions (rotate-half), multi-head attention
(16 heads of 128), then either a SiLU-gated MLP (layer 0) or the router's
softmax over all 64 routed experts, the six largest probabilities taken as
gates without renormalising (``norm_topk_prob`` false), each chosen
expert a SiLU-gated MLP of 1408, and the two shared experts, one always-on
SiLU-gated MLP of 2816; an untied output head.  This chip holds routed
experts ``first_held_expert`` .. + ``n_routed_experts`` - 1; the others
add nothing here, in the program and in this reference alike.

The reference follows that description with ``jax.numpy`` in float32 at
``Precision.HIGHEST`` and imports nothing of the program.  It runs one
layer at a time, so that the float32 copy of a single layer's weights is
all it adds to the chip's memory, and it applies each held expert to every
token, weighted by that token's gate (zero where the expert is not among
its six).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
_ATTN = ("q", "k", "v", "o")
_MLP = ("gate", "up", "down")
# the arrays of each MoE layer, stacked over the MoE layers
MOE_LAYER = (_ATTN + ("attn_norm", "mlp_norm", "router")
             + tuple("expert_" + n for n in _MLP)
             + tuple("shared_" + n for n in _MLP))


def layers(cfg) -> int:
    return cfg["num_hidden_layers"]


def moe_layers(cfg) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def linears(cfg):
    """(name, k, n, input) of the GEMMs every token of an MoE layer
    multiplies: attention and the shared experts.  The routed experts'
    GEMMs are ``expert_linears``."""
    d = cfg["hidden_size"]
    hq = cfg["num_attention_heads"] * cfg["head_dim"]
    sh = cfg["n_shared_experts"] * cfg["moe_intermediate_size"]
    return [("q", d, hq, "x"), ("k", d, kv_width(cfg), "x"),
            ("v", d, kv_width(cfg), "x"), ("o", hq, d, "attn"),
            ("shared_gate", d, sh, "x"), ("shared_up", d, sh, "x"),
            ("shared_down", sh, d, "ffn")]


def expert_linears(cfg):
    """(name, k, n) of one routed expert's GEMMs."""
    d, ff = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return [("gate", d, ff), ("up", d, ff), ("down", ff, d)]


def head_params(cfg) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def kv_width(cfg) -> int:
    return cfg["num_key_value_heads"] * cfg["head_dim"]


def attn_width(cfg) -> int:
    return cfg["num_attention_heads"] * cfg["head_dim"]


def make_weights(cfg, key):
    """Every weight, made on the device in one jitted call from ``key``:
    linears bf16, stacked over layers (the dense first layer's under
    ``dense_*``, the MoE layers' under ``MOE_LAYER`` names, routed experts
    as (L, held, k, n)); router and norm scales float32."""
    return _make_weights(_frozen(cfg))(key)


def _frozen(cfg):
    keys = ("hidden_size", "num_hidden_layers", "first_k_dense_replace",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "intermediate_size", "moe_intermediate_size", "n_routed_experts",
            "n_shared_experts", "num_experts_per_tok", "norm_topk_prob",
            "vocab_size", "first_held_expert")
    return tuple((k, cfg[k]) for k in keys) + (
        ("router_width", cfg["published"]["n_routed_experts"]),)


def _normal(key, shape, fan_in, dtype=jnp.bfloat16):
    return (jax.random.normal(key, shape, jnp.float32)
            / math.sqrt(fan_in)).astype(dtype)


@functools.lru_cache(maxsize=None)
def _make_weights(frozen):
    cfg = dict(frozen)
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    nd, L = cfg["first_k_dense_replace"], moe_layers(cfg)
    held = cfg["n_routed_experts"]

    def make(key):
        ks = iter(jax.random.split(key, 32))
        w = {}
        for name, k, n, _ in linears(cfg)[:4]:
            w["dense_" + name] = _normal(next(ks), (nd, k, n), k)
            w[name] = _normal(next(ks), (L, k, n), k)
        for name, k, n in (("gate", d, cfg["intermediate_size"]),
                           ("up", d, cfg["intermediate_size"]),
                           ("down", cfg["intermediate_size"], d)):
            w["dense_" + name] = _normal(next(ks), (nd, k, n), k)
        for name, k, n, _ in linears(cfg)[4:]:
            w[name] = _normal(next(ks), (L, k, n), k)
        for name, k, n in expert_linears(cfg):
            w["expert_" + name] = _normal(next(ks), (L, held, k, n), k)
        w["router"] = _normal(next(ks), (L, d, cfg["router_width"]), d,
                              jnp.float32)
        w["embedding"] = (0.02 * jax.random.normal(
            next(ks), (v, d), jnp.float32)).astype(jnp.bfloat16)
        w["lm_head"] = (0.02 * jax.random.normal(
            next(ks), (d, v), jnp.float32)).astype(jnp.bfloat16)
        for name, shape in (("dense_attn_norm", (nd, d)),
                            ("dense_mlp_norm", (nd, d)),
                            ("attn_norm", (L, d)), ("mlp_norm", (L, d)),
                            ("final_norm", (d,))):
            w[name] = 1.0 + 0.1 * jax.random.normal(next(ks), shape,
                                                    jnp.float32)
        return w

    return jax.jit(make)


def to_program(w):
    """The same arrays in the parameter tree of the program's decoder
    (``repro.models.lm.DecoderLM``); no copy is made."""
    def attn(prefix):
        return {"w" + n: w[prefix + n] for n in _ATTN}

    return {
        "embed": {"embedding": w["embedding"], "lm_head": w["lm_head"]},
        "final_norm": w["final_norm"],
        "dense_layers": {
            "attn_norm": w["dense_attn_norm"],
            "mlp_norm": w["dense_mlp_norm"],
            "attn": attn("dense_"),
            "mlp": {"w_" + n: w["dense_" + n] for n in _MLP},
        },
        "layers": {
            "attn_norm": w["attn_norm"], "mlp_norm": w["mlp_norm"],
            "attn": attn(""),
            "moe": dict(router=w["router"],
                        shared={"w_" + n: w["shared_" + n] for n in _MLP},
                        **{"w_" + n: w["expert_" + n] for n in _MLP}),
        },
    }


def program_overrides(cfg):
    """The program's model-config fields, set from this configuration: the
    router keeps the published width, the model holds this chip's share."""
    return dict(num_layers=cfg["num_hidden_layers"],
                first_dense_layers=cfg["first_k_dense_replace"],
                d_model=cfg["hidden_size"],
                num_heads=cfg["num_attention_heads"],
                num_kv_heads=cfg["num_key_value_heads"],
                head_dim=cfg["head_dim"], d_ff=cfg["intermediate_size"],
                moe_d_ff=cfg["moe_intermediate_size"],
                num_experts=cfg["published"]["n_routed_experts"],
                experts_held=cfg["n_routed_experts"],
                first_held_expert=cfg["first_held_expert"],
                num_shared_experts=cfg["n_shared_experts"],
                top_k=cfg["num_experts_per_tok"],
                norm_topk_prob=cfg["norm_topk_prob"],
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
    dense, moe, head = _programs(fc, quantize)
    x = jnp.take(w["embedding"], tokens, axis=0).astype(jnp.float32)
    for i in range(cfg["first_k_dense_replace"]):
        x = dense(x, {n: w["dense_" + n][i]
                      for n in _ATTN + _MLP + ("attn_norm", "mlp_norm")})
    for i in range(moe_layers(cfg)):
        x = moe(x, {n: w[n][i] for n in MOE_LAYER})
    return head(x, w["final_norm"], w["lm_head"])


def route(cfg, w_layer, x, quantize=None):
    """(gates (N, k), experts (N, k)) the reference routes tokens x (N, d)
    with: softmax over every router output, the top k."""
    return _routing(_frozen(cfg), quantize)(w_layer["router"], x)


def expert_layer(cfg, w_layer, x, quantize=None):
    """The held experts' part of one MoE layer for tokens x (N, d), in
    float32: no shared experts, no residual."""
    return _experts(_frozen(cfg), quantize)(w_layer, x)


def _mm(quantize):
    def mm(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        if quantize is not None:
            a, b = quantize(a, -1), quantize(b, 0)
        return jnp.matmul(a, b, precision=HIGHEST)
    return mm


def _route_fn(cfg, quantize):
    mm = _mm(quantize)

    def route_(router, x):
        probs = jax.nn.softmax(mm(x, router), axis=-1)
        gates, chosen = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
        if cfg["norm_topk_prob"]:
            gates = gates / jnp.sum(gates, -1, keepdims=True)
        return gates, chosen
    return route_


def _experts_fn(cfg, quantize):
    mm, route_ = _mm(quantize), _route_fn(cfg, quantize)

    def experts(p, x):
        gates, chosen = route_(p["router"], x)
        out = jnp.zeros(x.shape, jnp.float32)
        for e in range(cfg["n_routed_experts"]):
            weight = jnp.sum(jnp.where(chosen == cfg["first_held_expert"] + e,
                                       gates, 0.0), -1)
            h = (jax.nn.silu(mm(x, p["expert_gate"][e]))
                 * mm(x, p["expert_up"][e]))
            out = out + weight[:, None] * mm(h, p["expert_down"][e])
        return out
    return experts


@functools.lru_cache(maxsize=None)
def _routing(frozen, quantize):
    return jax.jit(_route_fn(dict(frozen), quantize))


@functools.lru_cache(maxsize=None)
def _experts(frozen, quantize):
    return jax.jit(_experts_fn(dict(frozen), quantize))


@functools.lru_cache(maxsize=None)
def _programs(frozen, quantize):
    cfg = dict(frozen)
    H, KV, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    mm, experts = _mm(quantize), _experts_fn(cfg, quantize)

    def norm(x, scale):
        return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale

    def rope(x, pos):             # x (n, S, heads, hd)
        inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
        ang = pos[:, None] * inv[None, :]            # (S, hd/2)
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def attention(x, p):
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
        return x + mm(o.reshape(n, S, H * hd), p["o"])

    def swiglu(h, gate, up, down):
        return mm(jax.nn.silu(mm(h, gate)) * mm(h, up), down)

    def dense(x, p):
        x = attention(x, p)
        return x + swiglu(norm(x, p["mlp_norm"]), p["gate"], p["up"],
                          p["down"])

    def moe(x, p):
        x = attention(x, p)
        h = norm(x, p["mlp_norm"])
        n, S, d = h.shape
        routed = experts(p, h.reshape(n * S, d)).reshape(n, S, d)
        return x + routed + swiglu(h, p["shared_gate"], p["shared_up"],
                                   p["shared_down"])

    def head(x, scale, w_head):
        return mm(norm(x, scale), w_head)

    return jax.jit(dense), jax.jit(moe), jax.jit(head)


def expert_weights(cfg, key):
    """The routed part of every MoE layer, each layer its own arrays, made
    on the device in one jitted call from ``key``: the router (d, E)
    float32 and the held experts' (held, k, n) bf16 weights, under the
    names ``route`` and ``expert_layer`` read."""
    return _make_expert_weights(_frozen(cfg))(key)


@functools.lru_cache(maxsize=None)
def _make_expert_weights(frozen):
    cfg = dict(frozen)
    d, held = cfg["hidden_size"], cfg["n_routed_experts"]

    def make(key):
        out = []
        for i in range(moe_layers(cfg)):
            ks = jax.random.split(jax.random.fold_in(key, i), 4)
            w = {"router": _normal(ks[0], (d, cfg["router_width"]), d,
                                   jnp.float32)}
            for kk, (name, k, n) in zip(ks[1:], expert_linears(cfg)):
                w["expert_" + name] = _normal(kk, (held, k, n), k)
            out.append(w)
        return out

    return jax.jit(make)
