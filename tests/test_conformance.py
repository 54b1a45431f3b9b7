"""Executed-schedule conformance (acceptance criteria).

The subprocess job forces 16 host devices and asserts, for cannon, summa,
pod25d, cannon25d and both ring strategies on >= 3 mesh shapes each, that
the collectives the real shard_map lowering emits (captured at the
``repro.dist._collectives`` seam) form exactly the multiset the schedule
trace predicts, with word counts equal to the ``core.cost`` /
``dist.api.estimate`` analytics -- and that an injected wrong-permutation
mutation is caught, both statically and at the interceptor.

The ``conformance``-marked test runs the full strategy x mesh x
{square, ragged, batched} x dtype matrix in-process; tier-1 deselects it
(``addopts = -m "not conformance"``) and the dedicated CI job runs it at
``--xla_force_host_platform_device_count`` in {4, 8, 16}.
"""
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import dataclasses
import jax, jax.numpy as jnp, numpy as np

from repro.plan import build_plan
from repro.mesh import make_mesh
from repro.verify import (ConformanceError, check, compare_records,
                          matrix_cells, measure_plan, run_matrix, trace_plan)

# --- measured triangle: every strategy on >= 3 mesh shapes ------------------
rows = run_matrix(cases=("square",), dtypes=(jnp.float32,))
bad = [r for r in rows if not r["ok"]]
assert not bad, f"non-conforming cells: {bad}"
per_strategy = {}
for r in rows:
    per_strategy.setdefault(r["strategy"], set()).add(r["mesh"])
for strat in ("cannon", "summa", "pod25d", "cannon25d", "ring_ag", "ring_rs"):
    assert len(per_strategy.get(strat, ())) >= 3, (strat, per_strategy)

# --- one ragged + one batched + one bf16 measured cell ----------------------
devs = np.array(jax.devices())
mesh22 = make_mesh((2, 2), ("x", "y"), devices=devs[:4])
for kwargs in ({"m": 13, "n": 7, "k": 11},
               {"m": 5, "n": 8, "k": 12, "batch": (3,)},
               {"m": 16, "n": 16, "k": 16, "a_dtype": jnp.bfloat16,
                "b_dtype": jnp.bfloat16}):
    m, n, k = kwargs.pop("m"), kwargs.pop("n"), kwargs.pop("k")
    plan = build_plan(m, n, k, mesh=mesh22, strategy="cannon", **kwargs)
    check(plan, measure=True)

# --- hlo leg: compiled program's collective bytes visible to roofline -------
plan = build_plan(24, 24, 24, mesh=mesh22, strategy="cannon")
rep = check(plan, measure=True, hlo=True)
assert rep.hlo_collective_bytes and rep.hlo_collective_bytes > 0

# --- injected wrong-permutation mutations -----------------------------------
prog = plan.torus
pairs = list(prog.step_a)
pairs[0], pairs[1] = (pairs[0][0], pairs[1][1]), (pairs[1][0], pairs[0][1])
bad_plan = dataclasses.replace(
    plan, torus=dataclasses.replace(prog, step_a=tuple(pairs)))
try:
    check(bad_plan)
    raise SystemExit("static mutation not caught")
except ConformanceError:
    pass
# executed-program mutation: run the mutated lowering, compare against the
# unmutated plan's trace -- the interceptor multiset must diverge
cap = measure_plan(bad_plan)
try:
    compare_records(trace_plan(plan).records, cap.records)
    raise SystemExit("executed mutation not caught by interceptor")
except ConformanceError:
    pass

print("CONFORMANCE_OK")
"""


@pytest.mark.timeout(600)
def test_conformance_subprocess():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(_root(), "src")
    res = subprocess.run(
        [sys.executable, "-c", _SCRIPT], capture_output=True, text=True,
        env=env, timeout=590,
    )
    assert "CONFORMANCE_OK" in res.stdout, (
        f"stdout:\n{res.stdout[-3000:]}\nstderr:\n{res.stderr[-3000:]}"
    )


@pytest.mark.conformance
@pytest.mark.timeout(1800)
def test_conformance_matrix_full():
    """Full matrix at whatever forced-host device count the job set; the CI
    conformance job runs this at 4, 8, and 16 devices."""
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 devices (set "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    from repro.verify import run_matrix

    rows = run_matrix()
    bad = [r for r in rows if not r["ok"]]
    assert not bad, f"{len(bad)}/{len(rows)} non-conforming cells: {bad[:5]}"
    assert rows, "empty conformance matrix"


def _root():
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
