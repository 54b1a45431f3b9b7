"""Grouped GEMM: rows sorted by group, each group multiplied by its own
weight matrix, with group sizes known only on the device.

``grouped_matmul(x, w, group_sizes)`` computes, for every group ``g``,
``x[o_g : o_g + s_g] @ w[g]`` where ``s`` is ``group_sizes`` and ``o`` its
exclusive prefix sum, accumulating in float32.  Rows at or past
``sum(group_sizes)`` belong to no group and come back as zeros.  It is the
one place that decides how the experts an MoE layer holds multiply their
rows (``repro.layers.moe``).

The Pallas kernel walks *work items*: one (row tile, group) pair for each
row tile a non-empty group touches, so a tile that straddles a group
boundary is visited once per group and each visit stores only its group's
rows.  The items are built on the device from the group sizes and handed
to the kernel by scalar prefetch; their count is the grid's dynamic
extent, so empty groups and the rows past the last group cost no grid
step.  The row tile is ``default_blocks``' choice for ``ROW_TILE`` rows:
every group boundary inside a tile computes that tile twice, so the tile
is kept near the ridge point rather than as large as the rule would take
for the whole row count.  Output blocks revisited by consecutive items
stay resident, which is why the n tiles form the grid's outer axis.

Timed on a v5e chip against XLA's ragged dot at deepseek-moe's expert
GEMMs, the kernel took 0.33-0.68x the ragged dot's time at a prefill's
rows (3072 to 24576 held rows, even or skewed) and 0.91-1.01x at a
decode step's few rows, so the TPU takes the kernel at every shape.  The
ragged dot is the fallback off the TPU and gives the kernel's cotangents.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kernel import VMEM_LIMIT_BYTES, default_blocks

# Rows a work item spans at most: (512, 1408) and (512, 2048) output
# tiles are past v5e's ridge point, and each of a layer's G - 1 group
# boundaries wastes at most one such tile
ROW_TILE = 512


def grouped_blocks(m: int, n: int, k: int, dtype_bytes: int = 2,
                   out_dtype_bytes: int | None = None):
    """(row tile, block_n, block_k) of the kernel for ``m`` rows."""
    tm = min(ROW_TILE, -(-m // 16) * 16)
    return default_blocks(tm, n, k, dtype_bytes, out_dtype_bytes)


def _work_items(group_sizes, tm: int, m_tiles: int):
    """Group offsets, each item's group and row tile, and the item count."""
    g = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    tiles = jnp.where(group_sizes > 0, (ends - 1) // tm - starts // tm + 1, 0)
    slots = m_tiles + g - 1          # the most items any sizes can need
    item_group = jnp.repeat(jnp.arange(g, dtype=jnp.int32), tiles,
                            total_repeat_length=slots)
    first_item = jnp.cumsum(tiles) - tiles
    item_tile = (starts[item_group] // tm + jnp.arange(slots, dtype=jnp.int32)
                 - first_item[item_group])
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    return (offsets.astype(jnp.int32), item_group,
            jnp.minimum(item_tile, m_tiles - 1).astype(jnp.int32),
            jnp.sum(tiles).astype(jnp.int32))


def _gmm_kernel(offsets_ref, group_ref, tile_ref, x_ref, w_ref, o_ref,
                acc_ref, *, nk: int, tm: int):
    item, kk = pl.program_id(1), pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(kk == nk - 1)
    def _store():
        g = group_ref[item]
        row = tile_ref[item] * tm + jax.lax.broadcasted_iota(
            jnp.int32, acc_ref.shape, 0)
        mine = (row >= offsets_ref[g]) & (row < offsets_ref[g + 1])
        o_ref[...] = jnp.where(mine, acc_ref[...].astype(o_ref.dtype),
                               o_ref[...])


def gmm_pallas(x, w, group_sizes, *, out_dtype=None, interpret=False):
    """The Pallas grouped kernel (see module docstring)."""
    m, k = x.shape
    g, _, n = w.shape
    out_dtype = jnp.dtype(out_dtype or x.dtype)
    tm, bn, bk = grouped_blocks(m, n, k, x.dtype.itemsize, out_dtype.itemsize)
    pn, pk = (-n) % bn, (-k) % bk
    mp = m + (-m) % tm
    if mp != m or pk:
        x = jnp.pad(x, ((0, mp - m), (0, pk)))
    if pn or pk:
        w = jnp.pad(w, ((0, 0), (0, pk), (0, pn)))
    nk = (k + pk) // bk
    offsets, item_group, item_tile, items = _work_items(
        group_sizes.astype(jnp.int32), tm, mp // tm)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=((n + pn) // bn, items, nk),
        in_specs=[
            pl.BlockSpec((tm, bk), lambda j, i, kk, off, grp, til:
                         (til[i], kk)),
            pl.BlockSpec((None, bk, bn), lambda j, i, kk, off, grp, til:
                         (grp[i], kk, j)),
        ],
        out_specs=pl.BlockSpec((tm, bn), lambda j, i, kk, off, grp, til:
                               (til[i], j)),
        scratch_shapes=[pltpu.VMEM((tm, bn), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_gmm_kernel, nk=nk, tm=tm),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((mp, n + pn), out_dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="grouped_matmul",
    )(offsets, item_group, item_tile, x, w)
    return _past_groups_zeroed(out, group_sizes)[:m, :n]


def _past_groups_zeroed(out, group_sizes):
    held = jnp.arange(out.shape[0])[:, None] < jnp.sum(group_sizes)
    return jnp.where(held, out, jnp.zeros((), out.dtype))


def gmm_ragged(x, w, group_sizes, *, out_dtype=None):
    """XLA's ragged dot over the same groups (on the TPU it leaves the rows
    past the groups unwritten, so they are zeroed here)."""
    out = jax.lax.ragged_dot(x, w, group_sizes.astype(jnp.int32),
                             preferred_element_type=jnp.float32)
    return _past_groups_zeroed(out.astype(out_dtype or x.dtype), group_sizes)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _gmm_pallas_vjp(x, w, group_sizes, out_dtype, interpret):
    return gmm_pallas(x, w, group_sizes, out_dtype=out_dtype,
                      interpret=interpret)


def _gmm_fwd(x, w, group_sizes, out_dtype, interpret):
    return (_gmm_pallas_vjp(x, w, group_sizes, out_dtype, interpret),
            (x, w, group_sizes))


def _gmm_bwd(out_dtype, interpret, res, g):
    """The kernel's cotangents are those of the same product by XLA's
    ragged dot."""
    x, w, group_sizes = res
    _, vjp = jax.vjp(lambda x, w: gmm_ragged(x, w, group_sizes,
                                             out_dtype=out_dtype), x, w)
    dx, dw = vjp(g)
    return _past_groups_zeroed(dx, group_sizes), dw, None


_gmm_pallas_vjp.defvjp(_gmm_fwd, _gmm_bwd)


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret"))
def grouped_matmul(x, w, group_sizes, *, out_dtype=None, interpret=False):
    """``x`` (rows, k) sorted by group, ``w`` (G, k, n), ``group_sizes``
    (G,) int32 on the device -> (rows, n) in ``out_dtype`` (default
    ``x.dtype``), accumulated in float32; rows past the groups are zero.
    Off the TPU, and outside interpret mode, XLA's ragged dot."""
    if interpret or jax.default_backend() == "tpu":
        return _gmm_pallas_vjp(x, w, group_sizes, out_dtype, interpret)
    return gmm_ragged(x, w, group_sizes, out_dtype=out_dtype)
