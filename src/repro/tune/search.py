"""The autotune search: measured candidate timing under MXU/VMEM constraints.

``candidate_space`` enumerates the (block_m, block_n, block_k, order)
candidates for a shape -- every block a multiple of the 128-wide MXU tile,
every working set within the kernel's ``VMEM_BUDGET_BYTES``, the budget
``default_blocks`` also keeps to, orders the paper's Z-order schedule vs the row-major baseline.
``tune_shape`` times each candidate at the shape's bucket (best of
``reps`` timed calls, ``jax.block_until_ready``, discarded compile+warmup
calls first) under ``tune.search`` obs spans and returns the winner as a
:class:`repro.tune.table.TunedBlocks`.

:class:`Tuner` is the planner-facing front end: a mutable search-on-miss
cache over table entries, hashable by identity so it can ride in plan-cache
keys and the serving harness's memoized closures.  ``serve.Server.warmup``
passes one in: every bucket's local kernel shapes get tuned at AOT-warmup
trace time, so the serve window never searches (the tuning twin of the
plan-cache 100%-hit-rate pin).
"""
from __future__ import annotations

import time
from typing import Dict, Iterable, Optional, Tuple

from repro import obs
from repro.kernels.matmul.kernel import (VMEM_BUDGET_BYTES,
                                         vmem_working_set_bytes)

from .table import (MXU, Key, TunedBlocks, TuningTable, pad_up,
                    scaled_call_seconds, shape_bucket, table_key)

Candidate = Tuple[int, int, int, str]

BLOCK_CANDIDATES = (128, 256, 512)
BLOCK_K_CANDIDATES = (128, 256, 512, 1024, 2048)
ORDERS = ("zorder", "rowmajor")


def candidate_space(m: int, n: int, k: int, dtype_bytes: int = 2, *,
                    out_dtype_bytes: Optional[int] = None,
                    max_candidates: Optional[int] = None
                    ) -> Tuple[Candidate, ...]:
    """Every legal candidate for an (m, k) x (k, n) call: MXU-aligned
    blocks no larger than the padded dims, VMEM-feasible at the given byte
    widths, in both traversal orders.  Shapes below one tile run the jnp
    reference kernel, where blocks are moot -- a single canonical candidate.
    ``max_candidates`` stride-samples a deterministic subset (largest
    footprints first) for bounded CI searches."""
    if min(m, n, k) < MXU:
        return ((MXU, MXU, MXU, "zorder"),)
    pm, pn, pk = pad_up(m), pad_up(n), pad_up(k)
    cands = []
    for bm in BLOCK_CANDIDATES:
        if bm > pm:
            continue
        for bn in BLOCK_CANDIDATES:
            if bn > pn:
                continue
            for bk in BLOCK_K_CANDIDATES:
                if bk > pk:
                    continue
                if vmem_working_set_bytes(
                        bm, bn, bk, dtype_bytes,
                        out_dtype_bytes) > VMEM_BUDGET_BYTES:
                    continue
                for order in ORDERS:
                    cands.append((bm, bn, bk, order))
    if max_candidates is not None and 0 < max_candidates < len(cands):
        cands.sort(key=lambda c: (-(c[0] * c[1] * c[2]), c[3]))
        step = len(cands) / max_candidates
        cands = [cands[int(i * step)] for i in range(max_candidates)]
    return tuple(cands)


def time_candidate(m: int, n: int, k: int, dtype, cand: Candidate, *,
                   reps: int = 3, interpret: Optional[bool] = None) -> float:
    """Best wall seconds of one kernel call with ``cand``'s blocks/order:
    two calls compile and warm (discarded), then the min of ``reps`` timed
    ``block_until_ready`` calls -- min, not median, because dispatch noise
    is strictly additive and heavy-tailed, so the fastest rep is the least
    contaminated estimate of the kernel itself.  ``interpret`` defaults to
    the backend's need (Pallas interpret mode off TPU/GPU)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.matmul import matmul

    if interpret is None:
        interpret = jax.default_backend() not in ("tpu", "gpu")
    bm, bn, bk, order = cand
    a = jnp.ones((m, k), jnp.dtype(dtype))
    b = jnp.ones((k, n), jnp.dtype(dtype))

    def run():
        return matmul(a, b, block_m=bm, block_n=bn, block_k=bk,
                      order=order, interpret=interpret)

    # compile + first dispatches, discarded: the first post-compile calls
    # still carry cold caches and would inflate the first candidate tried
    jax.block_until_ready(run())
    jax.block_until_ready(run())
    ts = []
    for _ in range(max(int(reps), 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(run())
        ts.append(time.perf_counter() - t0)
    return float(min(ts))


def tune_shape(m: int, n: int, k: int, dtype="bfloat16", *,
               reps: int = 3, max_candidates: Optional[int] = None,
               interpret: Optional[bool] = None) -> TunedBlocks:
    """Search the candidate space at the shape's bucket and return the
    winner.  Timing happens at the *bucket* shape, so every shape sharing
    the bucket shares one honest measurement."""
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)
    bucket = shape_bucket(m, n, k)
    cands = candidate_space(*bucket, dt.itemsize,
                            max_candidates=max_candidates)
    best: Optional[Candidate] = None
    best_t = float("inf")
    with obs.span("tune.search", m=m, n=n, k=k, dtype=dt.name,
                  bucket="x".join(str(x) for x in bucket),
                  candidates=len(cands)):
        for cand in cands:
            t = time_candidate(*bucket, dt.name, cand, reps=reps,
                               interpret=interpret)
            if obs.enabled():
                obs.histogram("tune.candidate_us").observe(t * 1e6)
            if t < best_t:
                best, best_t = cand, t
        if obs.enabled():
            obs.counter("tune.searches").inc()
    return TunedBlocks(block_m=best[0], block_n=best[1], block_k=best[2],
                       order=best[3], seconds=best_t, bucket=bucket)


class Tuner:
    """Search-on-miss front end over tuning entries (see module docstring).

    Deliberately NOT a dataclass: hashable by object identity, so one live
    tuner can sit in plan-cache keys and ``functools.lru_cache``'d serving
    closures while its entry dict and stats mutate underneath."""

    def __init__(self, *, table: Optional[TuningTable] = None,
                 reps: int = 3, max_candidates: Optional[int] = None,
                 interpret: Optional[bool] = None,
                 device_kind: Optional[str] = None):
        self._entries: Dict[Key, TunedBlocks] = (
            dict(table.entries) if table is not None else {})
        self.reps = reps
        self.max_candidates = max_candidates
        self.interpret = interpret
        self._device_kind = device_kind
        self.stats: Dict[str, int] = {"hits": 0, "misses": 0, "searches": 0}

    def device_kind(self) -> str:
        if self._device_kind is None:
            import jax

            self._device_kind = jax.devices()[0].device_kind
        return self._device_kind

    def keys(self) -> Tuple[Key, ...]:
        return tuple(self._entries)

    def lookup_key(self, key: Key, count: bool = True) -> Optional[TunedBlocks]:
        entry = self._entries.get(key)
        if count:
            self.stats["hits" if entry is not None else "misses"] += 1
        return entry

    def lookup(self, m: int, n: int, k: int, dtype: str = "bfloat16",
               count: bool = True) -> Optional[TunedBlocks]:
        return self.lookup_key(table_key(m, n, k, dtype), count=count)

    def entry_for(self, m: int, n: int, k: int,
                  dtype: str = "bfloat16") -> TunedBlocks:
        """The bucket's entry, searching (and caching the winner) on miss."""
        key = table_key(m, n, k, dtype)
        entry = self._entries.get(key)
        if entry is not None:
            self.stats["hits"] += 1
            return entry
        self.stats["misses"] += 1
        self.stats["searches"] += 1
        entry = tune_shape(m, n, k, dtype, reps=self.reps,
                           max_candidates=self.max_candidates,
                           interpret=self.interpret)
        self._entries[key] = entry
        return entry

    def compute_seconds(self, m: int, n: int, k: int,
                        dtype: str = "bfloat16") -> float:
        """Measured seconds of one (m, k) x (k, n) call -- never None: a
        live tuner searches the bucket on demand."""
        return scaled_call_seconds(self.entry_for(m, n, k, dtype), m, n, k)

    def table(self) -> TuningTable:
        """Frozen snapshot of the current entries for persistence/embedding
        (``MachineProfile.tuning``)."""
        from datetime import datetime, timezone

        return TuningTable(
            device_kind=self.device_kind(),
            entries=tuple(sorted(self._entries.items())),
            created=datetime.now(timezone.utc).isoformat())


def tune_shapes(shapes: Iterable[Tuple[int, int, int]], dtype="bfloat16", *,
                reps: int = 3, max_candidates: Optional[int] = None,
                interpret: Optional[bool] = None) -> TuningTable:
    """One-call batch search (``perf_probe --tune`` uses this): tune every
    shape's bucket and return the frozen table."""
    tuner = Tuner(reps=reps, max_candidates=max_candidates,
                  interpret=interpret)
    for m, n, k in shapes:
        tuner.entry_for(m, n, k, dtype=dtype)
    return tuner.table()
