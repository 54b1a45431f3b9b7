"""repro.dist -- schedule-execution engine.

Lowers the equivariant schedules of ``repro.core`` (solutions of the
paper's commutative-diagram equations) to executable shard_map/ppermute
programs:

  cannon    -- the solver's Cannon solution run verbatim: placement perms
               for the skew, movement-homomorphism perms for the shifts
  summa     -- the broadcast (all-gather) stationary-C contrast strategy
  pod25d    -- Torus25DSchedule's replicate--compute--reduce over a pod
               axis, composable with an in-layer strategy (cannon25d)
  ring      -- the 1-D torus solutions: all-gather / reduce-scatter
               decomposed into one-hop ppermute chains overlapped with
               per-chunk matmuls
  api       -- analytic cost model (estimate), strategy selection (choose),
               and dispatch (symmetric_matmul)

Since the ``repro.plan`` refactor the strategy modules hold the lowering
*rules* (shard_map bodies); program composition -- padding, specs,
batch folding, plan caching -- lives in ``repro.plan.lower_shard_map``
and the entry points here are thin facades over it.

Local block multiplies route through the Pallas matmul kernel on TPU/GPU
and jnp.matmul with fp32 accumulation elsewhere (repro.dist.local).
"""
from ._util import pad_to
from .api import (Estimate, applicable_strategies, choose, estimate,
                  symmetric_matmul)
from .cannon import (cannon_matmul, executed_shift_vectors, lowered_plan,
                     torus_body, torus_schedule_matmul)
from .fattree import fattree_matmul
from .local import local_matmul
from .pod25d import cannon25d_matmul, pod25d_matmul
from .ring import ring_ag_matmul, ring_rs_matmul
from .summa import summa_matmul

__all__ = [
    "Estimate", "applicable_strategies", "choose", "estimate",
    "symmetric_matmul", "cannon_matmul", "executed_shift_vectors",
    "fattree_matmul", "lowered_plan", "torus_body", "torus_schedule_matmul",
    "local_matmul",
    "cannon25d_matmul", "pod25d_matmul", "pad_to", "ring_ag_matmul",
    "ring_rs_matmul", "summa_matmul",
]
