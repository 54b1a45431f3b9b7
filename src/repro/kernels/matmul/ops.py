"""jit'd public wrapper for the Z-order matmul kernel.

Handles arbitrary shapes by padding to block multiples, chooses VMEM-fitting
MXU-aligned blocks, and falls back to the jnp oracle for shapes too small to
tile (the kernel is a throughput kernel; tiny matmuls belong to XLA).

With ``repro.obs`` recording enabled, eager (non-traced) calls of tileable
shapes record the ragged-shape padding overhead in the ``kernel.pad_waste``
histogram (padded FLOPs / useful FLOPs).  Disabled mode and calls under
tracing (tracer operands inside shard_map/jit bodies) add no work.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import obs

from .kernel import default_blocks, zorder_matmul
from .ref import matmul_ref

_MIN_TILE = 128


def _resolve_blocks(m, n, k, dtype_bytes, out_dtype_bytes,
                    block_m, block_n, block_k):
    """The block shapes the kernel will actually run: VMEM-fitting defaults
    sized by the real input/output byte widths, explicit overrides winning,
    everything clamped to the problem dims.  Shared by the jit'd kernel path
    and the eager pad-waste accounting so both see the same blocks."""
    bm, bn, bk = default_blocks(m, n, k, dtype_bytes, out_dtype_bytes)
    bm, bn, bk = block_m or bm, block_n or bn, block_k or bk
    return min(bm, m), min(bn, n), min(bk, k)


def matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    block_m: int | None = None,
    block_n: int | None = None,
    block_k: int | None = None,
    order: str = "zorder",
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    """Z-order Pallas matmul (see module docstring); obs-instrumented."""
    out = _matmul_jit(a, b, block_m=block_m, block_n=block_n,
                      block_k=block_k, order=order, interpret=interpret,
                      out_dtype=out_dtype)
    if not obs.enabled() or isinstance(a, jax.core.Tracer) \
            or isinstance(b, jax.core.Tracer):
        return out
    m, k = a.shape
    n = b.shape[1]
    if min(m, n, k) >= _MIN_TILE:
        # ragged shapes are padded to block multiples silently inside the
        # jit; surface the overhead as padded FLOPs / useful FLOPs
        dbytes = jnp.dtype(a.dtype).itemsize
        obytes = jnp.dtype(out_dtype or a.dtype).itemsize
        bm, bn, bk = _resolve_blocks(m, n, k, dbytes, obytes,
                                     block_m, block_n, block_k)
        padded = (m + (-m) % bm) * (n + (-n) % bn) * (k + (-k) % bk)
        obs.histogram("kernel.pad_waste").observe(padded / (m * n * k))
    return out


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "order", "interpret",
                     "out_dtype"),
)
def _matmul_jit(
    a: jax.Array,
    b: jax.Array,
    *,
    block_m: int | None = None,
    block_n: int | None = None,
    block_k: int | None = None,
    order: str = "zorder",
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    m, k = a.shape
    k2, n = b.shape
    assert k == k2
    if min(m, n, k) < _MIN_TILE:
        return matmul_ref(a, b, out_dtype=out_dtype)
    dbytes = jnp.dtype(a.dtype).itemsize
    obytes = jnp.dtype(out_dtype or a.dtype).itemsize
    bm, bn, bk = _resolve_blocks(m, n, k, dbytes, obytes,
                                 block_m, block_n, block_k)

    pm, pn, pk = (-m) % bm, (-n) % bn, (-k) % bk
    ap = jnp.pad(a, ((0, pm), (0, pk))) if (pm or pk) else a
    bp = jnp.pad(b, ((0, pk), (0, pn))) if (pk or pn) else b
    out = zorder_matmul(
        ap, bp, block_m=bm, block_n=bn, block_k=bk, order=order,
        interpret=interpret, out_dtype=out_dtype or a.dtype,
    )
    if pm or pn:
        out = out[:m, :n]
    return out
