"""Operation and byte counts the metrics divide by, computed from shapes.

These are the benchmark's own arithmetic; nothing here is read from the
program.  A GEMM counts 2*m*n*k useful FLOPs (padding not counted) and,
at least, its operands read once and its output written once.
"""
from __future__ import annotations

from typing import Iterable, Tuple

from .peaks import Peaks


def gemm_flops(m: int, k: int, n: int) -> float:
    return 2.0 * m * k * n


def gemm_bytes(m: int, k: int, n: int, in_bytes: int, out_bytes: int) -> float:
    return float((m * k + k * n) * in_bytes + m * n * out_bytes)


def gemm_min_s(m: int, k: int, n: int, in_bytes: int, out_bytes: int,
               peaks: Peaks, chips: int = 1) -> float:
    """Least time ``chips`` chips can take for one GEMM: the larger of its
    FLOPs over their peak and its bytes over their HBM bandwidth."""
    return max(gemm_flops(m, k, n) / (chips * peaks.bf16_flops),
               gemm_bytes(m, k, n, in_bytes, out_bytes)
               / (chips * peaks.hbm_bytes_per_s))


def linear_params(linears: Iterable[Tuple[str, int, int, str]]) -> int:
    """Weights of one layer's linears, from (name, k, n, input) tuples."""
    return sum(k * n for _, k, n, _ in linears)


def decoder_flops(layer_params: int, layers: int, head_params: int,
                  attn_width: int, context: int, head: bool) -> float:
    """FLOPs of one token through a decoder: 2 per weight it multiplies in
    every layer's linears (and in the output head where ``head``), plus
    QK^T and PV against ``context`` positions in each layer (``attn_width``
    = query heads * head size)."""
    return (2.0 * (layer_params * layers + (head_params if head else 0))
            + 4.0 * attn_width * context * layers)


def request_flops(layer_params: int, layers: int, head_params: int,
                  attn_width: int, prompt: int, generated: int) -> float:
    """Useful FLOPs of one served request: its ``prompt`` real tokens
    (position p attends to p + 1 positions; the head runs on the last one,
    which yields the first generated token), then one step for each
    further generated token, attending to every position held."""
    total = 0.0
    for p in range(prompt):
        total += decoder_flops(layer_params, layers, head_params, attn_width,
                               p + 1, head=p == prompt - 1)
    for j in range(1, generated):
        total += decoder_flops(layer_params, layers, head_params, attn_width,
                               prompt + j, head=True)
    return total


def kv_bytes(tokens: int, layers: int, kv_width: int, dtype_bytes: int) -> int:
    """Bytes of keys and values held for ``tokens`` positions
    (``kv_width`` = key/value heads * head size)."""
    return 2 * tokens * layers * kv_width * dtype_bytes
