"""granite-20b (GPTBigCode, multi-query attention): the shapes of one
layer's linears as the published model calls them.

The cell that runs this configuration drives the plan engine with these
GEMMs alone, so the plain reference is that of a GEMM
(``bench/lib/gemm.py``); the layer's attention, norms and activation are
not run.
"""
from __future__ import annotations


def layers(cfg) -> int:
    return cfg["n_layer"]


def linears(cfg):
    """(name, k, n, input) of each GEMM of one layer, in the order the
    layer calls them: the fused query/key/value projection (48 query heads
    and one shared key and value head of 128), the attention output, and
    the non-gated MLP's up and down projections."""
    d, ff, heads = cfg["n_embd"], cfg["n_inner"], cfg["n_head"]
    hd = d // heads
    kv = 1 if cfg["multi_query"] else heads
    return [("qkv", d, d + 2 * kv * hd, "x"), ("o", d, d, "attn"),
            ("up", d, ff, "x"), ("down", ff, d, "ffn")]
