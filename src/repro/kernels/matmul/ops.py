"""jit'd public wrapper for the Z-order matmul kernel.

Handles arbitrary shapes by padding to block multiples, chooses VMEM-fitting
MXU-aligned blocks, and falls back to the jnp oracle for shapes too small to
tile (the kernel is a throughput kernel; tiny matmuls belong to XLA).

With ``repro.obs`` tracing enabled, eager (non-traced) calls are wrapped in
a ``kernel.matmul`` span: wall time (block_until_ready'd) lands in the
``kernel.matmul.us`` histogram and achieved FLOPs are recorded against the
output device's published bf16 peak (``kernel.matmul.roofline_fraction``;
only for a ``device_kind`` in ``repro.core.cost.DEVICE_PEAKS``).  Disabled mode and
calls under tracing (tracer operands inside shard_map/jit bodies) go
straight to the jit'd kernel with zero added work.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.cost import DEVICE_PEAKS

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
    kw = dict(block_m=block_m, block_n=block_n, block_k=block_k,
              order=order, interpret=interpret, out_dtype=out_dtype)
    if not obs.enabled() or isinstance(a, jax.core.Tracer) \
            or isinstance(b, jax.core.Tracer):
        return _matmul_jit(a, b, **kw)
    m, k = a.shape
    n = b.shape[1]
    with obs.span("kernel.matmul", m=m, n=n, k=k, order=order):
        t0 = time.perf_counter()
        out = _matmul_jit(a, b, **kw)
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
    flops = 2.0 * m * n * k
    obs.histogram("kernel.matmul.us").observe(dt * 1e6)
    obs.counter("kernel.matmul.flops").inc(flops)
    peaks = DEVICE_PEAKS.get(next(iter(out.devices())).device_kind)
    if peaks is not None:
        obs.histogram("kernel.matmul.roofline_fraction").observe(
            flops / dt / peaks.bf16_flops)
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
