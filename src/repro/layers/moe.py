"""Mixture-of-Experts: top-k token-choice routing over every expert, the
experts this model holds applied to the rows routed to them, no token
dropped (qwen3-moe, deepseek-moe), plus always-on shared experts.

A model may hold only a share of each layer's routed experts
(``cfg.experts_held`` experts from ``cfg.first_held_expert``): one chip's
part of an expert-parallel deployment.  The router still scores all
``cfg.num_experts`` and picks ``cfg.top_k`` for every token; the
(token, choice) pairs that land on held experts are sorted by expert and
multiplied by ``repro.kernels.matmul.grouped_matmul``, and their outputs,
weighted by the gates, are added back to each token's row.  A pair that
lands on an expert held elsewhere adds nothing here: that is the part of
the result the other shares give.

The router runs in float32 at ``Precision.HIGHEST`` and picks the top-k by
logit (the softmax is monotone), so a plain float32 reference routes the
same input alike.  The gates are the softmax probabilities of the chosen
experts, renormalised to sum to one only under ``cfg.norm_topk_prob``.  A
Switch-style load-balancing loss over all router outputs rides along.

The routed rows are gathered into a buffer of static size.  Routing near
even fits ``_rows_bound``'s smaller buffer; any routing that sends more
rows takes the branch sized for every pair a token can send here, so the
result never depends on how the rows fall.  The padding slots of a
left-padded serving batch are routed but sent to no expert.

Inside a ``use_mesh`` context with a ``model`` axis, where the sharding
rules put the expert stacks, each device holds its slice of the held
experts and computes their part for its tokens, and the parts are summed
over ``model``: expert parallelism without a token exchange.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.kernels.matmul.grouped import ROW_TILE, grouped_matmul
from repro.runtime.sharding import MODEL_AXIS, current_mesh, resolve_axis

from .mlp import mlp, mlp_params

Params = Dict[str, jax.Array]

# rows the smaller dispatch buffer holds beyond an even share
_SPARE = 1.25


def moe_params(key, cfg, dtype=jnp.bfloat16) -> Params:
    d, e, ff = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    held = cfg.experts_held
    ks = jax.random.split(key, 5)
    p: Params = {
        "router": jax.random.normal(ks[0], (d, e), jnp.float32) * 0.02,
        # the held experts' weights, stacked: (held, d, ff) / (held, ff, d)
        "w_gate": (jax.random.normal(ks[1], (held, d, ff), jnp.float32) * d ** -0.5).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (held, d, ff), jnp.float32) * d ** -0.5).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (held, ff, d), jnp.float32) * ff ** -0.5).astype(dtype),
    }
    if cfg.num_shared_experts:
        p["shared"] = mlp_params(
            ks[4], d, cfg.moe_d_ff * cfg.num_shared_experts, dtype
        )
    return p


def route(router: jax.Array, x: jax.Array, cfg
          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x (N, d) -> gates (N, k) float32, experts (N, k) int32 and the
    router's probabilities (N, E)."""
    logits = jnp.matmul(x.astype(jnp.float32), router,
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    _, experts = jax.lax.top_k(logits, cfg.top_k)
    gates = jnp.take_along_axis(probs, experts, axis=-1)
    if cfg.norm_topk_prob:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return gates, experts.astype(jnp.int32), probs


def _rows_bound(tokens: int, held: int, cfg) -> Tuple[int, int]:
    """(rows of an even share with some to spare, the most rows ``held``
    experts can receive): every token sends at most min(k, held) pairs."""
    k = cfg.top_k
    most = tokens * min(k, held)
    even = math.ceil(tokens * k * held / cfg.num_experts * _SPARE)
    return min(-(-even // ROW_TILE) * ROW_TILE, most), most


def routed_experts(p: Params, x: jax.Array, cfg,
                   valid: Optional[jax.Array] = None
                   ) -> Tuple[jax.Array, jax.Array]:
    """The held experts' part of the layer for tokens x (N, d): (float32
    (N, d), load-balance loss).  Rows where ``valid`` (N,) is false, the
    padding slots of a left-padded serving batch, are routed but sent to
    no expert."""
    if valid is None:
        valid = jnp.ones(x.shape[:1], bool)
    if obs.enabled():
        obs.counter("moe.experts_held").inc(
            cfg.experts_held, router_width=cfg.num_experts,
            first=cfg.first_held_expert)
    mesh = current_mesh()
    ways = mesh.shape.get(MODEL_AXIS, 1) if mesh is not None else 1
    if ways > 1 and cfg.experts_held % ways == 0:
        y, load, prob = _expert_parallel(p, x, valid, cfg, mesh, ways)
    else:
        y, load, prob = _routed(p, x, valid, cfg, cfg.first_held_expert,
                                cfg.experts_held)
    # Switch load-balance loss over every router output: E * sum_e f_e * P_e
    return y, cfg.num_experts * jnp.sum(load * prob)


def _expert_parallel(p, x, valid, cfg, mesh, ways):
    """Each device along ``model`` holds experts_held / ways of the held
    experts; tokens stay split over the batch axes where they divide."""
    per = cfg.experts_held // ways
    batch = resolve_axis("batch", mesh)
    split = batch is not None and x.shape[0] % math.prod(
        mesh.shape[a] for a in batch) == 0
    tokens = P(batch) if split else P()
    weights = {"router": P(), "w_gate": P(MODEL_AXIS), "w_up": P(MODEL_AXIS),
               "w_down": P(MODEL_AXIS)}

    def local(p, x, valid):
        first = cfg.first_held_expert + jax.lax.axis_index(MODEL_AXIS) * per
        y, load, prob = _routed(p, x, valid, cfg, first, per)
        if split:
            load, prob = jax.lax.pmean((load, prob), batch)
        return jax.lax.psum(y, MODEL_AXIS), load, prob

    return jax.shard_map(local, mesh=mesh,
                         in_specs=(weights, tokens, tokens),
                         out_specs=(tokens, P(), P()), check_vma=False)(
        {k: p[k] for k in weights}, x, valid)


def _routed(p, x, valid, cfg, first, held):
    """The part of experts first .. first + held - 1 (``first`` may be
    traced), whose stacked weights ``p`` holds: (float32 (N, d), each
    expert's share of the choices, its mean router probability)."""
    n, d = x.shape
    k = cfg.top_k
    with jax.named_scope("moe.route"):
        gates, experts, probs = route(p["router"], x, cfg)
        load = jnp.mean(jnp.sum(jax.nn.one_hot(experts, cfg.num_experts,
                                               dtype=jnp.float32), axis=1),
                        axis=0)
    with jax.named_scope("moe.dispatch"):
        local = experts.reshape(-1) - first            # (N * k,) pairs
        mine = (local >= 0) & (local < held) & jnp.repeat(valid, k)
        key = jnp.where(mine, local, held)             # absent pairs last
        order = jnp.argsort(key, stable=True)
        sizes = jnp.sum(key[:, None] == jnp.arange(held), axis=0,
                        dtype=jnp.int32)
        token = (order // k).astype(jnp.int32)
        weight = jnp.where(mine, gates.reshape(-1), 0.0)[order]

    def apply(rows):
        with jax.named_scope("moe.dispatch"):
            tok = token[:rows]
            xs = jnp.take(x, tok, axis=0)
        with jax.named_scope("moe.experts"):
            h = (jax.nn.silu(grouped_matmul(xs, p["w_gate"], sizes))
                 * grouped_matmul(xs, p["w_up"], sizes))
            ys = grouped_matmul(h, p["w_down"], sizes)
        with jax.named_scope("moe.combine"):
            return jnp.zeros((n, d), jnp.float32).at[tok].add(
                ys.astype(jnp.float32) * weight[:rows, None])

    even, most = _rows_bound(n, held, cfg)
    if even == most:
        y = apply(most)
    else:
        y = jax.lax.cond(jnp.sum(sizes) <= even, lambda: apply(even),
                         lambda: apply(most))
    return y, load, jnp.mean(probs, axis=0)


def moe(p: Params, x: jax.Array, cfg,
        valid: Optional[jax.Array] = None) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (y, aux_loss); ``valid`` (B, S) marks the real
    tokens of a padded batch (default: all)."""
    b, s, d = x.shape
    y, aux = routed_experts(p, x.reshape(b * s, d), cfg,
                            None if valid is None else valid.reshape(-1))
    y = y.astype(x.dtype).reshape(b, s, d)
    if "shared" in p:
        with jax.named_scope("moe.shared"):
            y = y + mlp(p["shared"], x)
    return y, aux
