"""Inter-chip lowering: a ``SchedulePlan`` as a shard_map/ppermute program.

Each strategy is one lowering *rule* that composes
  pad -> shard_map(body) -> slice
where the body comes from the dist modules (``torus_body`` for anything
with a ``TorusSchedule``, the ring chains from ``repro.dist.ring``, the
all-gather / pod-reduce bodies from ``repro.dist.summa`` /
``repro.dist.pod25d``) and the per-device block multiply comes from the
plan's tiling via ``lower_pallas``.

``execute_plan`` adds the batching layer: leading batch dims of the left
operand are folded into the row dimension before the 2-D program runs
(exact -- it is the same global matmul with m' = prod(batch) * m); a
batched right operand is handled per batch element.
"""
from __future__ import annotations

import functools
import math

import jax
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.dist._util import pad_to
from repro.dist.cannon import (torus_program_body,
                               torus_program_body_overlapped)
from repro.dist.fattree import fattree_body
from repro.dist.pod25d import (cannon25d_body, pod25d_slab_body,
                               pod25d_summa_body,
                               pod25d_summa_overlapped_body)
from repro.dist.ring import ring_ag_matmul, ring_rs_matmul
from repro.dist.summa import summa_body, summa_overlapped_body

from .ir import SchedulePlan
from .lower_pallas import lower_pallas


# Lowering observers: ``repro.verify`` hooks here to learn which plan is
# behind the collectives its interceptor counts.  Callbacks receive the
# plan on EVERY lowering request (cached or not).
_LOWER_OBSERVERS = []


def on_lower(callback):
    """Register ``callback(plan)`` to fire on each ``lower_shard_map`` call;
    returns a zero-argument unregister function."""
    _LOWER_OBSERVERS.append(callback)

    def remove():
        try:
            _LOWER_OBSERVERS.remove(callback)
        except ValueError:
            pass

    return remove


def _notify_lower(plan: SchedulePlan) -> None:
    for cb in tuple(_LOWER_OBSERVERS):
        cb(plan)


def lower_shard_map(plan: SchedulePlan):
    """Compile ``plan`` to a callable executing one global 2-D matmul
    (m, k) x (k, n) -> (m, n) as the planned shard_map/ppermute program.

    Memoized per plan (``SchedulePlan`` is frozen, and hashable whenever
    its mesh is -- always true for jax meshes): repeated dispatches of a
    cached plan reuse the compiled closure instead of rebuilding bodies --
    together with the plan cache this makes a repeat ``symmetric_matmul``
    call pure dictionary lookups down to the jit boundary.  Plans built on
    unhashable duck-typed meshes (tests) lower uncached."""
    _notify_lower(plan)
    with obs.span("plan.lower", strategy=plan.strategy,
                  overlap=plan.overlap):
        try:
            return _lower_shard_map_cached(plan)
        except TypeError:
            return _lower_shard_map(plan)


@functools.lru_cache(maxsize=256)
def _lower_shard_map_cached(plan: SchedulePlan):
    return _lower_shard_map(plan)


def _lower_shard_map(plan: SchedulePlan):
    local_fn = lower_pallas(plan)
    out_dtype = plan.out_dtype

    if plan.strategy == "local" or plan.mesh is None or plan.mesh.size == 1:
        return lambda a, b: local_fn(a, b, out_dtype=out_dtype)

    mesh = plan.mesh

    if plan.torus is not None and plan.strategy != "cannon25d":
        # cannon / any valid 2-D torus solution: execute the reified program
        ax, ay = plan.axes
        body_fn = (torus_program_body_overlapped if plan.overlap
                   else torus_program_body)
        body = body_fn(plan.torus, ax, ay, local_fn=local_fn)
        f = _shard_map(
            lambda ab, bb: body(ab, bb).astype(out_dtype),
            mesh=mesh,
            in_specs=(P(ax, ay), P(ax, ay)),
            out_specs=P(ax, ay),
        )
        return _padded(f, plan)

    if plan.strategy == "summa":
        ax, ay = plan.axes
        summa_fn = summa_overlapped_body if plan.overlap else summa_body
        f = _shard_map(
            summa_fn(ax, ay, out_dtype, local_fn=local_fn),
            mesh=mesh,
            in_specs=(P(ax, ay), P(ax, ay)),
            out_specs=P(ax, ay),
        )
        return _padded(f, plan)

    if plan.strategy == "fattree":
        tr, ax, ay = plan.axes
        f = _shard_map(
            fattree_body(tr, ax, ay, plan.grid[0], out_dtype,
                         local_fn=local_fn),
            mesh=mesh,
            in_specs=(P(ax, (tr, ay)), P(ax, (tr, ay))),
            out_specs=P(ax, (tr, ay)),
        )
        return _padded(f, plan)

    if plan.strategy == "cannon25d":
        pod, ax, ay = plan.axes
        f = _shard_map(
            cannon25d_body(pod, ax, ay, plan.torus, out_dtype,
                           local_fn=local_fn, overlap=plan.overlap),
            mesh=mesh,
            in_specs=(P(ax, (pod, ay)), P((pod, ax), ay)),
            out_specs=P(ax, ay),
        )
        return _padded(f, plan)

    if plan.strategy == "pod25d":
        pod = plan.axes[0]
        if len(plan.axes) >= 3:
            ax, ay = plan.axes[1], plan.axes[2]
            pod_fn = (pod25d_summa_overlapped_body if plan.overlap
                      else pod25d_summa_body)
            f = _shard_map(
                pod_fn(pod, ax, ay, out_dtype, local_fn=local_fn),
                mesh=mesh,
                in_specs=(P(ax, (pod, ay)), P((pod, ax), ay)),
                out_specs=P(ax, ay),
            )
        else:
            f = _shard_map(
                pod25d_slab_body(pod, out_dtype, local_fn=local_fn),
                mesh=mesh,
                in_specs=(P(None, pod), P(pod, None)),
                out_specs=P(None, None),
            )
        return _padded(f, plan)

    if plan.strategy in ("ring_ag", "ring_rs"):
        axis = plan.axes[0] if len(plan.axes) == 1 else tuple(plan.axes)
        if plan.strategy == "ring_ag":
            # sharded dims: m (rows of a) and n (cols of b)
            f = _shard_map(
                lambda xl, wl: ring_ag_matmul(xl, wl, axis,
                                              out_dtype=out_dtype,
                                              local_fn=local_fn),
                mesh=mesh,
                in_specs=(P(axis, None), P(None, axis)),
                out_specs=P(None, axis),
            )
        else:
            # sharded dims: the contraction k and the output rows m
            f = _shard_map(
                lambda yl, wl: ring_rs_matmul(yl, wl, axis,
                                              out_dtype=out_dtype,
                                              local_fn=local_fn),
                mesh=mesh,
                in_specs=(P(None, axis), P(axis, None)),
                out_specs=P(axis, None),
            )
        return _padded(f, plan)

    raise ValueError(f"no shard_map lowering rule for {plan.strategy!r}")


def _shard_map(body, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` without the varying-axes (vma) type check.  On the
    TPU a body's local multiply may be the Pallas kernel
    (``repro.dist.local``), whose output type carries no vma, and the
    checker refuses such an output inside a checked shard_map."""
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _padded(f, plan: SchedulePlan):
    """Wrap a shard_map program with the plan's zero-pad / slice-back."""

    def run(a, b):
        m, n = a.shape[0], b.shape[1]
        out = f(pad_to(a, plan.pad_a), pad_to(b, plan.pad_b))
        return out[:m, :n] if out.shape != (m, n) else out

    return run


def execute_plan(plan: SchedulePlan, a: jax.Array, b: jax.Array) -> jax.Array:
    """Run ``plan`` on concrete operands, handling leading batch dims.

    a: (batch..., m, k); b: (k, n) or (batch..., k, n).  A batched left
    operand against a 2-D right operand is folded into the rows (vmap of a
    matmul over shared weights IS that bigger matmul); batched-both pairs
    run the 2-D program per flattened batch element.
    """
    if a.shape[-1] != b.shape[-2 if b.ndim > 1 else 0]:
        raise ValueError(f"contraction mismatch: {a.shape} x {b.shape}")
    run = lower_shard_map(plan)
    # the span covers tracing of the shard_map body, so every collective
    # recorded at the dist seam inherits the strategy tag
    with obs.span("plan.execute", strategy=plan.strategy,
                  overlap=plan.overlap, m=plan.m, n=plan.n, k=plan.k):
        if a.ndim == 2 and b.ndim == 2:
            return run(a, b)
        if a.ndim > 2 and b.ndim == 2:
            batch = a.shape[:-2]
            m, k = a.shape[-2], a.shape[-1]
            flat = a.reshape((math.prod(batch) * m, k))
            out = run(flat, b)
            return out.reshape(batch + (m, b.shape[-1]))
        if a.ndim == b.ndim and a.ndim > 2 and a.shape[:-2] == b.shape[:-2]:
            batch = a.shape[:-2]
            af = a.reshape((-1,) + a.shape[-2:])
            bf = b.reshape((-1,) + b.shape[-2:])
            # one traced program scanned over the batch, not B dispatches
            out = jax.lax.map(lambda ab: run(ab[0], ab[1]), (af, bf))
            return out.reshape(batch + out.shape[-2:])
    raise ValueError(
        f"unsupported operand ranks for planned matmul: {a.shape} x {b.shape}")
