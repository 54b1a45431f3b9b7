"""Plain float32 reference of a decoder with DeepSeekMoE's MoE layers, for
the tests: the whole forward in ``jax.numpy`` at
``default_matmul_precision("highest")``, with no kernel, cache or batching.

Written from the published description (arXiv:2401.06066): pre-norm
RMSNorm blocks, rotate-half rotary positions, causal multi-head attention
(query heads share ``num_heads / num_kv_heads`` key/value heads), and in
each layer after the leading dense ones an MoE: softmax over every routed
expert's logit, the top-k by probability, gates renormalised only under
``norm_topk_prob``, each chosen expert a SiLU-gated MLP, plus the shared
experts as one always-on SiLU-gated MLP.  Experts outside the share
``first_held_expert .. + experts_held - 1`` add nothing, as on the chip
that holds this share.  It reads the program's parameter tree and imports
nothing of ``repro.layers``.
"""
import math

import jax
import jax.numpy as jnp


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _f32(scale)


def rope(x, pos, theta):
    """x (S, heads, hd), pos (S,)."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(cfg, p, x):
    """Causal attention of one sequence x (S, d)."""
    s = x.shape[0]
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    pos = jnp.arange(s, dtype=jnp.float32)
    q = rope((x @ _f32(p["wq"])).reshape(s, h, hd), pos, cfg.rope_theta)
    k = rope((x @ _f32(p["wk"])).reshape(s, kv, hd), pos, cfg.rope_theta)
    v = (x @ _f32(p["wv"])).reshape(s, kv, hd)
    k, v = jnp.repeat(k, h // kv, axis=1), jnp.repeat(v, h // kv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
    return o.reshape(s, h * hd) @ _f32(p["wo"])


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ _f32(w_gate)) * (x @ _f32(w_up))) @ _f32(w_down)


def expert_layer(cfg, p, x):
    """The held experts' part of an MoE layer for tokens x (N, d), without
    the shared experts: every token, every choice, one at a time."""
    probs = jax.nn.softmax(x @ _f32(p["router"]), -1)
    gates, chosen = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        gates = gates / jnp.sum(gates, -1, keepdims=True)
    out = jnp.zeros_like(x)
    for e in range(cfg.experts_held):
        weight = jnp.sum(jnp.where(chosen == cfg.first_held_expert + e,
                                   gates, 0.0), -1)
        y = swiglu(x, p["w_gate"][e], p["w_up"][e], p["w_down"][e])
        out = out + weight[:, None] * y
    return out


def moe_layer(cfg, p, x):
    """The layer as the share's chip gives it: held experts and the shared
    experts."""
    y = expert_layer(cfg, p, x)
    if "shared" in p:
        sh = p["shared"]
        y = y + swiglu(x, sh["w_gate"], sh["w_up"], sh["w_down"])
    return y


def block(cfg, p, x, moe):
    x = x + attention(cfg, p["attn"], norm(x, p["attn_norm"], cfg.norm_eps))
    h = norm(x, p["mlp_norm"], cfg.norm_eps)
    if moe:
        return x + moe_layer(cfg, p["moe"], h)
    m = p["mlp"]
    return x + swiglu(h, m["w_gate"], m["w_up"], m["w_down"])


def forward(cfg, params, tokens):
    """Logits (B, S, vocab) in float32 of ``tokens`` (B, S), each row a
    sequence of its own."""
    with jax.default_matmul_precision("highest"):
        rows = []
        for seq in tokens:
            x = _f32(params["embed"]["embedding"])[seq]
            for i in range(cfg.first_dense_layers):
                x = block(cfg, jax.tree.map(lambda a: a[i],
                                            params["dense_layers"]), x, False)
            for i in range(cfg.num_layers - cfg.first_dense_layers):
                x = block(cfg, jax.tree.map(lambda a: a[i], params["layers"]),
                          x, True)
            x = norm(x, params["final_norm"], cfg.norm_eps)
            head = params["embed"].get("lm_head")
            head = params["embed"]["embedding"].T if head is None else head
            rows.append((x @ _f32(head))[:, :cfg.vocab_size])
        return jnp.stack(rows)
