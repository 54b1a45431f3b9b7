"""Z-order (space-bounded) blocked matmul Pallas TPU kernel.

This is the Sec.-4.3 level of the paper mapped onto the TPU memory
hierarchy: the HBM -> VMEM block schedule follows the iterated-wreath-product
(Morton / Z-order) traversal over the (i, j) output-block grid, which is the
cache-oblivious order -- each VMEM-resident A-row-panel and B-column-panel is
reused across neighbouring output blocks at every "virtual cache level"
simultaneously.  The contraction axis k stays innermost (contiguous revisits
of the output block are required for legal accumulation on TPU, and k is the
"time" axis of the systolic MXU -- the paper's Delta).

Hardware adaptation notes: ``default_blocks`` takes each block side from
the divisors of its dimension that are multiples of the 128-wide MXU/VREG
tiling (or the whole dimension), so no operand is padded, and sizes the
output tile past v5e's ridge point, since every grid step fetches a fresh
A and B block.  The fp32 accumulator lives in a VMEM scratch so
low-precision inputs (bf16) accumulate at full precision.  The kernel asks
Mosaic for ``VMEM_LIMIT_BYTES`` of scoped VMEM, and every block choice
(``default_blocks`` here, ``repro.tune.candidate_space``) keeps its working
set within ``VMEM_BUDGET_BYTES`` of it.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.cost import HBM_BW, PEAK_FLOPS_BF16
from repro.core.zorder import zorder_schedule

# Scoped VMEM the kernel may use.  Mosaic's default scoped limit on v5e is
# 16 MiB; an fp32 (512, 512, 2048) block set needs about 19 MiB and is
# refused there.  32 MiB is a quarter of a v5e core's 128 MiB VMEM.
VMEM_LIMIT_BYTES = 32 * 1024 * 1024
# What a block choice's working set (``vmem_working_set_bytes``) may claim:
# the limit less a quarter kept for Mosaic's own scratch.
VMEM_BUDGET_BYTES = VMEM_LIMIT_BYTES * 3 // 4


def _matmul_kernel(oi_ref, oj_ref, a_ref, b_ref, o_ref, acc_ref, *, nk: int):
    del oi_ref, oj_ref  # consumed by the index maps (scalar prefetch)
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def zorder_matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 512,
    out_dtype=None,
    order: str = "zorder",
    interpret: bool = False,
) -> jax.Array:
    """C = A @ B with a Z-order HBM->VMEM block schedule.

    a: (m, k), b: (k, n); m, n, k must be divisible by the block sizes
    (``ops.matmul`` pads arbitrary shapes before calling this).
    order: "zorder" (paper Sec. 4.3 schedule) or "rowmajor" (baseline).
    """
    m, kdim = a.shape
    k2, n = b.shape
    assert kdim == k2, f"contraction mismatch {kdim} vs {k2}"
    assert m % block_m == 0 and n % block_n == 0 and kdim % block_k == 0, (
        f"shape ({m},{kdim},{n}) not divisible by blocks "
        f"({block_m},{block_k},{block_n})"
    )
    out_dtype = out_dtype or a.dtype
    gm, gn, gk = m // block_m, n // block_n, kdim // block_k

    if order == "zorder":
        ij_order = [(i, j) for (i, j, _z) in zorder_schedule(gm, gn, 1)]
    elif order == "rowmajor":
        ij_order = [(i, j) for i in range(gm) for j in range(gn)]
    else:
        raise ValueError(f"unknown order {order!r}")
    oi = jnp.asarray([i for i, _ in ij_order], dtype=jnp.int32)
    oj = jnp.asarray([j for _, j in ij_order], dtype=jnp.int32)

    # The block-visit order is data the index maps must read: this is what
    # scalar prefetch is for on TPU (the table sits in SMEM ahead of the grid).
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(gm * gn, gk),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda s, k, oi, oj: (oi[s], k)),
            pl.BlockSpec((block_k, block_n), lambda s, k, oi, oj: (k, oj[s])),
        ],
        out_specs=pl.BlockSpec(
            (block_m, block_n), lambda s, k, oi, oj: (oi[s], oj[s])
        ),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_matmul_kernel, nk=gk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(oi, oj, a, b)


def vmem_working_set_bytes(
    block_m: int, block_n: int, block_k: int, dtype_bytes: int = 2,
    out_dtype_bytes: int | None = None,
) -> int:
    """VMEM bytes claimed by one grid step (A, B blocks + fp32 acc + out).

    ``dtype_bytes`` is the *input* element width; the output block is sized
    by ``out_dtype_bytes`` when it differs (the accumulator is always fp32).
    The pipeline double-buffers the streamed A, B and output blocks.  Block
    choices keep this within ``VMEM_BUDGET_BYTES``."""
    a = block_m * block_k * dtype_bytes * 2  # double-buffered
    b = block_k * block_n * dtype_bytes * 2
    acc = block_m * block_n * 4
    out = block_m * block_n * (out_dtype_bytes or dtype_bytes) * 2
    return a + b + acc + out


# FLOP per byte of HBM traffic at which v5e turns from memory- to
# compute-bound: 197 TFLOP/s of bf16 over 819 GB/s, about 240.
RIDGE_FLOP_PER_BYTE = PEAK_FLOPS_BF16 / HBM_BW


def _sides(d: int) -> list[int]:
    """Block sides that tile ``d`` with nothing padded: its divisors that
    are multiples of 128, or ``d`` whole where it is no multiple of 128."""
    return [b for b in range(128, d + 1, 128) if d % b == 0] or [d]


def default_blocks(m: int, n: int, k: int, dtype_bytes: int = 2,
                   out_dtype_bytes: int | None = None) -> Tuple[int, int, int]:
    """The (block_m, block_n, block_k) that tile an (m, k) x (k, n) GEMM.

    Each block side divides its dimension (``_sides``), so ``ops.matmul``
    pads nothing; only where no such set fits ``VMEM_BUDGET_BYTES`` (a
    dimension that is no multiple of 128 and too large to take whole) are
    the dimensions rounded up to multiples of 128 and padded.  Every grid
    step fetches an A and a B block, 2 * bm * bn * bk FLOPs against
    (bm + bn) * bk * dtype_bytes bytes, so the output tile sets the
    arithmetic intensity.  Of the tiles that fit the budget with some k
    block, the rule prefers the highest intensity up to v5e's ridge point
    (``RIDGE_FLOP_PER_BYTE``); past it every tile is compute-bound, and
    the rule prefers the fewest grid steps, each costing a fixed overhead,
    then the higher intensity.  ``block_k`` is the largest side of k that
    fits beside the tile."""
    out_b = out_dtype_bytes or dtype_bytes

    def best(m, n, k):
        scored, k_sides = [], _sides(k)
        for bm in _sides(m):
            for bn in _sides(n):
                bks = [bk for bk in k_sides if vmem_working_set_bytes(
                    bm, bn, bk, dtype_bytes, out_b) <= VMEM_BUDGET_BYTES]
                if not bks:
                    continue
                bk = bks[-1]
                reuse = 2 * bm * bn / ((bm + bn) * dtype_bytes)
                steps = (m // bm) * (n // bn) * (k // bk)
                scored.append(((min(reuse, RIDGE_FLOP_PER_BYTE), -steps,
                                reuse), (bm, bn, bk)))
        return max(scored)[1] if scored else None

    return best(m, n, k) or best(*(-(-d // 128) * 128 for d in (m, n, k)))
