"""One benchmark per paper table/figure.  Each returns (name, us_per_call,
derived) rows for the CSV emitted by benchmarks.run.

``us_per_call`` is ``None`` for derived-only benches (pure model
evaluations with no timed call) -- the driver emits an empty CSV field and
``"us_per_call": null`` in the JSON, never a fake ``0.0``.

Multi-device benches (collective-byte measurements) run in a subprocess
with fake devices so the parent process keeps the default 1-device view.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import List, Optional, Tuple

import numpy as np

Row = Tuple[str, Optional[float], str]


def _timeit(fn, reps: int = 3) -> float:
    fn()  # warmup
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return (time.perf_counter() - t0) / reps * 1e6


# -- Sec. 4.1 / Fig. 13: Cannon on the 2D torus ----------------------------


def bench_cannon_solver() -> List[Row]:
    """The systematic procedure itself: solve the commutative diagram for
    q=7 and verify it recovers the Cannon family at minimum cost."""
    from repro.core import solve_torus, is_cannon_like, cannon_schedule

    q = 7
    us = _timeit(lambda: solve_torus(q), reps=1)
    sols = solve_torus(q)
    cs = cannon_schedule(q)
    exact = any(s.schedule.M == cs.M for s in sols)
    return [
        ("cannon_solver_q7", us,
         f"solutions={len(sols)};min_hops={sols[0].hop_cost};"
         f"cannon_found={exact};first_is_cannon_like={is_cannon_like(sols[0])}"),
    ]


def _link_weighted(by_kind: dict, q: int) -> float:
    """Paper Sec. 2.4: cost = bytes x link transits under a torus routing
    policy.  One-hop collective-permute = 1 transit/byte (Cannon's mu);
    ring all-reduce = 2(q-1)/q x q ~ 2(q-1); all-gather/reduce-scatter =
    q-1; all-to-all ~ q/2."""
    w = {"collective-permute": 1.0, "all-reduce": 2.0 * (q - 1),
         "all-gather": float(q - 1), "reduce-scatter": float(q - 1),
         "all-to-all": q / 2.0}
    return sum(by_kind.get(k, 0) * f for k, f in w.items())


def bench_cannon_comm() -> List[Row]:
    """Fig. 13 / Sec. 4.1: Cannon vs SUMMA on a 4x4 torus (subprocess, 16
    fake devices).  Per-device HLO collective bytes + the paper's
    link-transit-weighted cost vs the analytic one-hop model."""
    out = _run_dist_probe("cannon_summa")
    rows = []
    n, q = out["n"], out["q"]
    # analytic: A and B each move one hop per step for q steps (incl. the
    # skew); per device = 2 tensors x q steps x block bytes
    block = (n // q) * (n // q) * 2
    analytic = 2 * q * block
    cw = _link_weighted(out["cannon_kinds"], q)
    sw = _link_weighted(out["summa_kinds"], q)
    rows.append((
        "cannon_comm_4x4", out["cannon_us"],
        f"perdev_bytes={out['cannon_bytes']:.3e};analytic={analytic:.3e};"
        f"ratio={out['cannon_bytes']/analytic:.2f};linkweighted={cw:.3e}",
    ))
    rows.append((
        "summa_comm_4x4", out["summa_us"],
        f"perdev_bytes={out['summa_bytes']:.3e};linkweighted={sw:.3e};"
        f"linkweighted_vs_cannon={sw/max(cw,1):.2f}x",
    ))
    return rows


# -- Sec. D.1: 2.5D replication ---------------------------------------------


def bench_25d_comm() -> List[Row]:
    """Sec. D.1: with c-fold replication each layer runs only t = q/c of
    the Cannon steps; per-device communication drops while p grows by c
    (the memory-for-communication trade).  Compares 2D Cannon on q x q
    against the composed 2.5D schedule on q x q x c for the same matmul."""
    out = _run_dist_probe("pod25d")
    c1_dev = out["c1_bytes"]          # 2D cannon p=q^2, per device
    c2_dev = out["c2_bytes"]          # 2.5D p=c q^2, per device
    p_ratio = out["c"]
    return [(
        "comm_25d_c2_vs_c1", out["us"],
        f"cannon_p{out['q']**2}_perdev={c1_dev:.3e};"
        f"c25d_p{out['c']*out['q']**2}_perdev={c2_dev:.3e};"
        f"perdev_reduction={c1_dev/max(c2_dev,1):.2f}x_at_{p_ratio}x_devices",
    )]


# -- Sec. 4.2 Fig. 11-12: fat-tree recursive schedule -----------------------


def bench_fattree() -> List[Row]:
    from repro.core.fattree import FatTreeSchedule

    rows = []
    for d in (2, 3):
        ft = FatTreeSchedule(d=d)
        us = _timeit(lambda ft=ft: ft.link_traffic(), reps=1)
        traffic = ft.link_traffic()
        top = ft.top_level_words()
        n2 = ft.n ** 2
        rows.append((
            f"fattree_d{d}", us,
            f"valid={ft.validate()};top_words={top};n^2={n2};"
            f"matches_paper_min={top == n2}",
        ))
    return rows


# -- Sec. 4.3: space-bounded / Z-order --------------------------------------


def bench_spacebounded() -> List[Row]:
    from repro.core.zorder import (block_reuse_distance_traffic,
                                   rowmajor_schedule, zorder_schedule)

    g = 16  # 16^3 = 4096-step block grid
    rows = []
    z = zorder_schedule(g, g, g)
    r = rowmajor_schedule(g, g, g)
    for cache in (48, 192, 768):
        tz = block_reuse_distance_traffic(z, cache)
        tr = block_reuse_distance_traffic(r, cache)
        rows.append((
            f"zorder_traffic_M{cache}", None,
            f"zorder={tz};rowmajor={tr};saving={tr/tz:.2f}x",
        ))
    us = _timeit(lambda: zorder_schedule(g, g, g), reps=1)
    rows.append((f"zorder_gen_{g}^3", us, f"steps={len(z)}"))
    return rows


# -- Sec. D.2: hexagonal systolic array -------------------------------------


def bench_hex() -> List[Row]:
    from repro.core.hexarray import HexSchedule

    q = 8
    hs = HexSchedule(q=q)
    A = np.random.rand(q, q)
    B = np.random.rand(q, q)
    us = _timeit(lambda: hs.simulate(A, B), reps=1)
    props = hs.systolic_properties()
    ok = np.allclose(hs.simulate(A, B), hs.reference(A, B))
    return [(
        f"hex_systolic_q{q}", us,
        f"correct={ok};steps={hs.num_steps};props={all(props.values())}",
    )]


# -- Sec. 2.4 + [20,11]: lower bounds ----------------------------------------


def bench_lowerbound() -> List[Row]:
    from repro.core.cost import (bandwidth_lower_bound, cannon_comm_total,
                                 memory_independent_lower_bound)

    n, p = 8192, 64
    M = 3 * n * n / p  # one copy of A,B,C
    per_node = cannon_comm_total(n, p) / p
    lb = max(bandwidth_lower_bound(n, p, M), memory_independent_lower_bound(n, p))
    return [(
        "lowerbound_gap_n8192_p64", None,
        f"cannon_per_node={per_node:.3e};bound={lb:.3e};"
        f"factor_above_bound={per_node/lb:.2f}",
    )]


# -- kernels ------------------------------------------------------------------


def bench_matmul_kernel() -> List[Row]:
    import jax
    import jax.numpy as jnp
    from repro.kernels.matmul import matmul, matmul_ref

    a = jax.random.normal(jax.random.PRNGKey(0), (256, 256), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(1), (256, 256), jnp.float32)
    out = matmul(a, b, block_m=128, block_n=128, block_k=128, interpret=True)
    err = float(jnp.max(jnp.abs(out - matmul_ref(a, b))))
    ref = jax.jit(matmul_ref)
    us = _timeit(lambda: jax.block_until_ready(ref(a, b)))
    return [(
        "zorder_matmul_256", us, f"interpret_max_err={err:.2e}",
    )]


def bench_flash_kernel() -> List[Row]:
    import jax
    import jax.numpy as jnp
    from repro.kernels.flash_attention import attention_ref, mha

    B, S, H, D = 1, 512, 4, 64
    q = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, D), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, 2, D), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, 2, D), jnp.float32)
    out = mha(q, k, v, causal=True, block_q=128, block_kv=128, interpret=True)

    def ref():
        qh = q.transpose(0, 2, 1, 3).reshape(-1, S, D)
        kh = k.transpose(0, 2, 1, 3).reshape(-1, S, D)
        vh = v.transpose(0, 2, 1, 3).reshape(-1, S, D)
        o = attention_ref(qh, kh, vh, causal=True)
        return o.reshape(B, H, S, D).transpose(0, 2, 1, 3)

    err = float(jnp.max(jnp.abs(out - ref())))
    import jax as _jax
    jref = _jax.jit(lambda: ref())
    us = _timeit(lambda: _jax.block_until_ready(jref()))
    return [("flash_attention_512", us, f"interpret_max_err={err:.2e}")]


# -- strategy cost model -------------------------------------------------------


def bench_strategy_choice() -> List[Row]:
    from repro.dist.api import choose, estimate

    m, n, k, tp = 32768, 8192, 2048, 16
    rows = []
    best = choose(m, n, k, tp=tp)
    xla = estimate("xla_ag", m, n, k, tp)
    ring = estimate("ring_ag", m, n, k, tp)
    rows.append((
        "strategy_autoselect", None,
        f"choice={best};xla_total={xla.total_s:.2e};ring_total={ring.total_s:.2e};"
        f"overlap_speedup={xla.total_s/ring.total_s:.2f}x",
    ))
    return rows


def bench_plan_dispatch() -> List[Row]:
    """Plan-engine dispatch overhead + cache behaviour: repeated
    ``symmetric_matmul`` calls must hit the plan cache (a miss storm here
    is a dispatch regression -- this bench raises so the CI smoke job
    fails loudly)."""
    import jax
    import jax.numpy as jnp
    from repro import plan as planlib
    from repro.dist.api import symmetric_matmul

    planlib.cache_clear()
    a = jax.random.normal(jax.random.PRNGKey(0), (192, 160), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(1), (160, 128), jnp.float32)
    us = _timeit(lambda: jax.block_until_ready(symmetric_matmul(a, b)))
    s = planlib.cache_stats()
    if s["hits"] < 3:  # warmup + 3 timed reps -> >= 3 hits after 1 miss
        raise RuntimeError(f"plan cache not hitting on repeat calls: {s}")
    # batched dispatch reuses the same plan entry family
    xb = jax.random.normal(jax.random.PRNGKey(2), (4, 48, 160), jnp.float32)
    out = symmetric_matmul(xb, b)
    assert out.shape == (4, 48, 128)
    return [(
        "plan_dispatch_local", us,
        f"hits={s['hits']};misses={s['misses']};entries={s['size']}",
    )]


# -- overlapped vs staged execution -------------------------------------------

_OVERLAP_PROBE = r"""
import os, sys
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=" + sys.argv[1])
import json, time
import jax, jax.numpy as jnp, numpy as np
sys.path.insert(0, "src")
from repro.plan import build_plan
from repro.plan.lower_shard_map import _lower_shard_map
from repro.mesh import make_mesh

q, n = 2, 512
devs = np.array(jax.devices())
mesh = make_mesh((q, q), ("x", "y"), devices=devs[:q*q])
rng = np.random.default_rng(0)
a = jnp.asarray(rng.standard_normal((n, n)), jnp.float32)
b = jnp.asarray(rng.standard_normal((n, n)), jnp.float32)
out = {"q": q, "n": n}
results = {}
for name, ov in (("staged", False), ("overlapped", True)):
    plan = build_plan(n, n, n, mesh=mesh, strategy="cannon",
                      a_dtype=a.dtype, b_dtype=b.dtype,
                      overlap=ov, use_cache=False)
    f = jax.jit(_lower_shard_map(plan))
    results[name] = np.asarray(jax.block_until_ready(f(a, b)))
    best = float("inf")
    for _ in range(7):
        t0 = time.perf_counter()
        jax.block_until_ready(f(a, b))
        best = min(best, time.perf_counter() - t0)
    out[name + "_us"] = best * 1e6
out["bitwise_equal"] = bool(
    np.array_equal(results["staged"], results["overlapped"]))
print("PROBE_JSON:" + json.dumps(out))
"""


def bench_overlap_vs_staged() -> List[Row]:
    """Paired staged-vs-overlapped cannon on a forced-host 2x2 mesh: both
    variants' us_per_call plus the speedup ratio.  CI guard: raises when
    the overlapped body is slower than the staged one beyond the
    ``OVERLAP_DRIFT_MARGIN`` fraction (default 10%) -- host-CPU timing is
    noisy, so the margin absorbs jitter while still catching a pessimized
    double-buffer lowering.  Also asserts bitwise-identical outputs (the
    overlapped torus body is a pure dataflow reorder)."""
    margin = float(os.environ.get("OVERLAP_DRIFT_MARGIN", "0.10"))
    env = _cpu_env()
    res = subprocess.run(
        [sys.executable, "-c", _OVERLAP_PROBE, "4"],
        capture_output=True, text=True, env=env, cwd=_repo_root(),
        timeout=600,
    )
    out = None
    for line in res.stdout.splitlines():
        if line.startswith("PROBE_JSON:"):
            out = json.loads(line[len("PROBE_JSON:"):])
    if out is None:
        raise RuntimeError(
            f"overlap probe failed:\n{res.stdout[-2000:]}\n"
            f"{res.stderr[-2000:]}")
    staged, over = out["staged_us"], out["overlapped_us"]
    speedup = staged / max(over, 1e-9)
    rows = [
        ("overlap_vs_staged_cannon_2x2", over,
         f"staged_us={staged:.1f};overlapped_us={over:.1f};"
         f"speedup={speedup:.2f}x;bitwise_equal={out['bitwise_equal']};"
         f"margin={margin:.2f}"),
        ("overlap_vs_staged_cannon_2x2_staged_ref", staged,
         f"n={out['n']};q={out['q']}"),
    ]
    if not out["bitwise_equal"]:
        raise RuntimeError(
            "overlapped cannon output differs bitwise from staged")
    if over > staged * (1.0 + margin):
        raise RuntimeError(
            f"overlapped cannon slower than staged beyond margin: "
            f"{over:.1f}us vs {staged:.1f}us (margin {margin:.0%})")
    return rows


# -- hierarchical fat-tree vs flat pod execution ------------------------------

_FATTREE_PROBE = r"""
import os, sys
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=" + sys.argv[1])
import json, time
import jax, jax.numpy as jnp, numpy as np
sys.path.insert(0, "src")
from repro.plan import build_plan
from repro.plan.lower_shard_map import _lower_shard_map
from repro.mesh import make_mesh

n = 512
devs = np.array(jax.devices())
mesh = make_mesh((2, 2, 2), ("tree", "x", "y"), devices=devs[:8])
rng = np.random.default_rng(0)
a = jnp.asarray(rng.standard_normal((n, n)), jnp.float32)
b = jnp.asarray(rng.standard_normal((n, n)), jnp.float32)
ref = np.asarray(a @ b)
out = {"n": n, "mesh": "2x2x2"}
for name in ("fattree", "pod25d"):
    plan = build_plan(n, n, n, mesh=mesh, strategy=name,
                      a_dtype=a.dtype, b_dtype=b.dtype, use_cache=False)
    f = jax.jit(_lower_shard_map(plan))
    got = np.asarray(jax.block_until_ready(f(a, b)))
    out[name + "_ok"] = bool(np.allclose(got, ref, atol=1e-2))
    best = float("inf")
    for _ in range(7):
        t0 = time.perf_counter()
        jax.block_until_ready(f(a, b))
        best = min(best, time.perf_counter() - t0)
    out[name + "_us"] = best * 1e6
print("PROBE_JSON:" + json.dumps(out))
"""


def bench_fattree_vs_flat() -> List[Row]:
    """The hierarchical fat-tree lowering against the flat 2.5D pod plan on
    the same pod-of-pods mesh (2 pods x 2x2, 8 forced-host devices): both
    must be numerically correct; the timings contrast the recursive
    tree-axis exchange program with the replicate--reduce program.  No
    speed guard -- on host CPU the two are link-indistinguishable; the
    ranking between them is the calibrated profile's job (see
    tests/test_fattree_exec.py's flip pin)."""
    env = _cpu_env()
    res = subprocess.run(
        [sys.executable, "-c", _FATTREE_PROBE, "8"],
        capture_output=True, text=True, env=env, cwd=_repo_root(),
        timeout=600,
    )
    out = None
    for line in res.stdout.splitlines():
        if line.startswith("PROBE_JSON:"):
            out = json.loads(line[len("PROBE_JSON:"):])
    if out is None:
        raise RuntimeError(
            f"fattree probe failed:\n{res.stdout[-2000:]}\n"
            f"{res.stderr[-2000:]}")
    if not (out["fattree_ok"] and out["pod25d_ok"]):
        raise RuntimeError(f"fattree-vs-flat numeric mismatch: {out}")
    ft, flat = out["fattree_us"], out["pod25d_us"]
    return [
        ("fattree_vs_flat_2x2x2", ft,
         f"fattree_us={ft:.1f};pod25d_us={flat:.1f};"
         f"ratio={ft / max(flat, 1e-9):.2f};n={out['n']};ok=True"),
    ]


# -- subprocess probe ----------------------------------------------------------

_PROBE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=48"
import json, sys, time
import jax, jax.numpy as jnp, numpy as np
import functools
sys.path.insert(0, "src")
from repro.dist import cannon_matmul, summa_matmul, pod25d_matmul
from repro.dist.pod25d import cannon25d_matmul
from repro.mesh import make_mesh
from repro.roofline.hlo_stats import analyze

mode = sys.argv[1]
devs = np.array(jax.devices())
out = {}
if mode == "cannon_summa":
    q, n = 4, 1024
    mesh = make_mesh((q, q), ("x", "y"), devices=devs[:q*q])
    a = jax.ShapeDtypeStruct((n, n), jnp.bfloat16)
    b = jax.ShapeDtypeStruct((n, n), jnp.bfloat16)
    for name, fn in (("cannon", cannon_matmul), ("summa", summa_matmul)):
        f = jax.jit(functools.partial(fn, mesh=mesh, axis_x="x", axis_y="y"))
        t0 = time.perf_counter()
        comp = f.lower(a, b).compile()
        stats = analyze(comp.as_text())
        out[name + "_bytes"] = stats.coll_bytes       # per device
        out[name + "_kinds"] = {k: int(v) for k, v in stats.coll.items()}
        out[name + "_us"] = (time.perf_counter() - t0) * 1e6
    out["n"], out["q"] = n, q
elif mode == "pod25d":
    n = 1024
    a = jax.ShapeDtypeStruct((n, n), jnp.bfloat16)
    b = jax.ShapeDtypeStruct((n, n), jnp.bfloat16)
    q, c = 4, 2
    mesh1 = make_mesh((q, q), ("x", "y"), devices=devs[:q*q])
    f1 = jax.jit(functools.partial(cannon_matmul, mesh=mesh1, axis_x="x", axis_y="y"))
    t0 = time.perf_counter()
    s1 = analyze(f1.lower(a, b).compile().as_text())
    mesh2 = make_mesh((c, q, q), ("pod", "x", "y"), devices=devs[:c*q*q])
    f2 = jax.jit(functools.partial(cannon25d_matmul, mesh=mesh2,
                                   pod_axis="pod", axis_x="x", axis_y="y"))
    s2 = analyze(f2.lower(a, b).compile().as_text())
    out["c1_bytes"] = s1.coll_bytes   # per device (2D cannon, p=16)
    out["c2_bytes"] = s2.coll_bytes   # per device (2.5D c=2, p=32)
    out["c1_kinds"] = {k: int(v) for k, v in s1.coll.items()}
    out["c2_kinds"] = {k: int(v) for k, v in s2.coll.items()}
    out["q"], out["c"] = q, c
    out["us"] = (time.perf_counter() - t0) * 1e6
print("PROBE_JSON:" + json.dumps(out))
"""


def _run_dist_probe(mode: str) -> dict:
    env = _cpu_env()
    res = subprocess.run(
        [sys.executable, "-c", _PROBE, mode],
        capture_output=True, text=True, env=env, cwd=_repo_root(), timeout=600,
    )
    for line in res.stdout.splitlines():
        if line.startswith("PROBE_JSON:"):
            return json.loads(line[len("PROBE_JSON:"):])
    raise RuntimeError(
        f"probe {mode} failed:\n{res.stdout[-2000:]}\n{res.stderr[-2000:]}"
    )


def _cpu_env() -> dict:
    """Environment for a forced-host device-farm child: the parent's, minus
    its device-count flag (each child sets its own), pinned to the CPU so
    the child never contends for a chip."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    return env


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- repro.tune: measured autotuning vs the default_blocks heuristic --------


def bench_tuned_vs_default() -> List[Row]:
    """Tuned blocks vs ``default_blocks`` on three shapes: square, ragged,
    and the MoE expert GEMM from ``configs/deepseek_moe_16b`` (per-token
    expert d_model x moe_d_ff, clamped for CI).  The searched winner must
    not lose to the heuristic beyond the ``TUNE_DRIFT_MARGIN`` noise
    margin (default 10%).  On the square and expert shapes the search
    space contains the heuristic's own blocks, so a regression there means
    the measurement harness lies; on the ragged one the heuristic takes
    m = 384 whole, which the tuner's power-of-two bucket pads to 512."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.kernels.matmul import matmul
    from repro.tune import Tuner

    margin = float(os.environ.get("TUNE_DRIFT_MARGIN", "0.10"))
    interpret = jax.default_backend() not in ("tpu", "gpu")
    cfg = get_config("deepseek_moe_16b")
    shapes = (
        ("square", (256, 256, 256)),
        ("ragged", (384, 128, 256)),
        ("moe_expert", (128, min(cfg.moe_d_ff, 512), min(cfg.d_model, 512))),
    )
    tuner = Tuner(reps=3, max_candidates=8, interpret=interpret)

    def best_us(fn, reps: int = 5) -> float:
        # min-of-N, not mean: interpret-mode dispatch has heavy-tailed
        # stragglers that would swamp the 10% gate with pure noise
        fn()
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            ts.append(time.perf_counter() - t0)
        return min(ts) * 1e6

    rows: List[Row] = []
    for label, (m, n, k) in shapes:
        a = jnp.ones((m, k), jnp.bfloat16)
        b = jnp.ones((k, n), jnp.bfloat16)
        entry = tuner.entry_for(m, n, k, dtype="bfloat16")

        def run_default():
            jax.block_until_ready(matmul(a, b, interpret=interpret))

        def run_tuned():
            jax.block_until_ready(matmul(
                a, b, block_m=entry.block_m, block_n=entry.block_n,
                block_k=entry.block_k, order=entry.order,
                interpret=interpret))

        default_us = best_us(run_default)
        tuned_us = best_us(run_tuned)
        speedup = default_us / max(tuned_us, 1e-9)
        rows.append((f"tuned_vs_default_{label}", tuned_us,
                     f"default_us={default_us:.1f};tuned_us={tuned_us:.1f};"
                     f"speedup={speedup:.2f}x;blocks={entry.label};"
                     f"margin={margin:.2f}"))
        if speedup < 1.0 - margin:
            raise RuntimeError(
                f"tuned blocks regressed on {label} ({m}x{n}x{k}): "
                f"{tuned_us:.1f}us vs default {default_us:.1f}us "
                f"(speedup {speedup:.2f}x < {1.0 - margin:.2f}x)")
    return rows


ALL_BENCHES = (
    bench_cannon_solver,
    bench_cannon_comm,
    bench_25d_comm,
    bench_fattree,
    bench_spacebounded,
    bench_hex,
    bench_lowerbound,
    bench_matmul_kernel,
    bench_flash_kernel,
    bench_strategy_choice,
    bench_plan_dispatch,
    bench_overlap_vs_staged,
    bench_fattree_vs_flat,
    bench_tuned_vs_default,
)

# bounded autotuning subset (`benchmarks/run.py --tune-smoke`): interpret-
# mode searches on forced-host CPU; gates the measured-autotuning path
TUNE_BENCHES = (
    bench_tuned_vs_default,
)

# tiny-shape subset for CI (`benchmarks/run.py --smoke`): no big compiles,
# one small 4-device subprocess; surfaces plan-cache, dispatch, and
# overlap-lowering regressions before merge
SMOKE_BENCHES = (
    bench_lowerbound,
    bench_spacebounded,
    bench_strategy_choice,
    bench_plan_dispatch,
    bench_overlap_vs_staged,
    bench_fattree_vs_flat,
)
