"""Bring-up check on a TPU: the plan engine's Pallas kernel and full-width
serving, through the entry points a user calls.

    python3 chip_smoke.py              # one chip: phases engine, serve
    python3 chip_smoke.py --chips 4    # four chips: phase strategies only

Phase ``engine`` runs ``build_plan`` + ``execute_plan`` on a one-device mesh
at the local shapes of h2o-danube-3-4b's linears (4096 rows) and of a
deepseek-moe-16b expert, bf16 in with bf16 and fp32 out.  Each program must
hold the compiled Pallas kernel (``tpu_custom_call``) and agree with an fp32
``jnp.dot``.  Phase ``serve`` serves h2o-danube-3-4b at its published
config with random weights through ``repro.launch.serve``: 8 prompts of
64-512 tokens in one (8, 512) bucket, 32 new tokens each.

Phase ``strategies`` (``--chips 4``) runs every strategy ``build_plan``
admits on the 4-chip meshes, staged and overlapped, at the MLP shape
4096x3840 -> 10240 against a one-chip ``jnp.dot``, checks each program's
collectives against the README's table, and compares a plan-routed
``Server`` on a 2x2 mesh with the unrouted one.

Without a TPU the script exits nonzero and prints no result line.  The
last line of standard output is the result:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Any failed check raises, and the script exits nonzero.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import itertools
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import compile_totals, enable_compile_cache  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.launch.serve import (init_model, print_run, serve,  # noqa: E402
                                synthetic_prompts)
from repro.mesh import make_mesh  # noqa: E402
from repro.plan import build_plan, execute_plan  # noqa: E402
from repro.roofline.hlo_stats import analyze  # noqa: E402
from repro.runtime.serve import ServeConfig  # noqa: E402

SERVED = "h2o-danube-3-4b"
EXPERT = "deepseek-moe-16b"
ROWS = 4096

# Normalised error max|out - ref| / max|ref| against an fp32 jnp.dot.
# Both sides accumulate exact bf16 products in fp32 and differ only in
# summation order: ~sqrt(k) * 2**-24 relative, far below 1e-4 at k <= 10240.
TOL_F32_OUT = 1e-4
# A bf16 output is the fp32 result rounded to nearest at 8 significant
# bits: off by at most 2**-8 of the element, so 2**-8 of the largest.  The
# fp32 summation-order difference above comes on top of that bound.
TOL_BF16_OUT = 2.0 ** -8 + TOL_F32_OUT
# Last-token logits of prefill vs the plain forward, as a share of the
# largest logit: the same bf16 model through cached and uncached attention,
# whose fusions round in a different order.  A wrong cache slot or mask
# moves logits by O(1).
TOL_LOGITS = 5e-2
# Plan-routed vs local prefill logits: every routed matmul splits its
# contraction over chips and rounds bf16 partial products in another
# order.  One full-width layer on four CPU devices differed by 6.2e-3;
# the depth used here compounds it.  A misrouted shard moves logits by O(1).
TOL_ROUTED = 1e-1

# Collective kinds each strategy's program may hold: the README's table
# ("collectives emitted"), with the overlapped twins' one-hop chains.
ALLOWED_COLLECTIVES = {
    ("cannon", False): {"collective-permute"},
    ("cannon", True): {"collective-permute"},
    ("summa", False): {"all-gather"},
    ("summa", True): {"collective-permute"},
    ("cannon25d", False): {"collective-permute", "all-reduce"},
    ("cannon25d", True): {"collective-permute", "all-reduce"},
    ("pod25d", False): {"all-gather", "all-reduce"},
    ("pod25d", True): {"collective-permute", "all-reduce"},
    ("fattree", False): {"collective-permute", "all-gather"},
    ("fattree", True): {"collective-permute", "all-gather"},
    ("ring_ag", True): {"collective-permute"},
    ("ring_rs", True): {"collective-permute"},
}

# The (strategy, overlap) pairs ``build_plan`` admits on each 4-chip mesh
# at the MLP shape, 60 programs; "name+" is the overlapped twin.  A refusal
# or an admission outside this table fails the phase.
ADMITTED = {
    "2x2(x,y)": "cannon cannon+ summa summa+ pod25d ring_ag+ ring_rs+",
    "4(t)": "pod25d ring_ag+ ring_rs+",
    "4(pod)": "pod25d ring_ag+ ring_rs+",
    "1x1x4(pod,x,y)": "cannon cannon+ summa summa+ pod25d pod25d+ "
                      "ring_ag+ ring_rs+",
    "1x2x2(pod,x,y)": "summa summa+ cannon25d cannon25d+ pod25d pod25d+ "
                      "ring_ag+ ring_rs+",
    "1x4x1(pod,x,y)": "summa summa+ pod25d pod25d+ ring_ag+ ring_rs+",
    "2x1x2(pod,x,y)": "summa summa+ pod25d pod25d+ fattree ring_ag+ "
                      "ring_rs+",
    "2x2x1(pod,x,y)": "cannon cannon+ summa summa+ pod25d pod25d+ fattree "
                      "ring_ag+ ring_rs+",
    "4x1x1(pod,x,y)": "summa summa+ cannon25d cannon25d+ pod25d pod25d+ "
                      "fattree ring_ag+ ring_rs+",
}


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def _norm_err(out, ref) -> float:
    """max|out - ref| / max|ref|, on the host (the two may live on
    different devices)."""
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    return float(np.max(np.abs(out - ref)) / np.max(np.abs(ref)))


def _reference(a, b):
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


class _Clock:
    """Compile seconds and persistent-cache hits spent inside a phase."""

    def __enter__(self):
        self.s0, self.h0 = compile_totals()
        return self

    def __exit__(self, *exc):
        s, h = compile_totals()
        self.compile_s, self.cache_hits = s - self.s0, h - self.h0


def _timed(f, *args, reps: int = 5) -> float:
    """Best warm wall time of ``f(*args)`` in seconds."""
    jax.block_until_ready(f(*args))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(f(*args))
        best = min(best, time.perf_counter() - t0)
    return best


# -- phase engine ------------------------------------------------------------

def engine_shapes():
    """(k, n) of the served model's linears and of one routed expert."""
    c, e = get_config(SERVED), get_config(EXPERT)
    return ((c.d_model, c.d_ff), (c.d_ff, c.d_model),
            (c.d_model, c.num_heads * c.head_dim),
            (c.d_model, c.num_kv_heads * c.head_dim),
            (c.d_model, c.vocab_size), (e.d_model, e.moe_d_ff))


def engine_program(m: int, k: int, n: int, out_dtype, mesh):
    """The plan engine's jitted program for one bf16 (m, k) x (k, n)."""
    plan = build_plan(m, n, k, mesh=mesh, a_dtype=jnp.bfloat16,
                      b_dtype=jnp.bfloat16, out_dtype=out_dtype)
    return plan, jax.jit(functools.partial(execute_plan, plan))


def phase_engine(kind: str, seed: int) -> None:
    mesh = make_mesh((1,), ("x",), devices=jax.devices()[:1])
    key = jax.random.PRNGKey(seed)
    with _Clock() as clock:
        for (k, n), out_dtype in itertools.product(
                engine_shapes(), (jnp.bfloat16, jnp.float32)):
            ka, kb, key = jax.random.split(key, 3)
            a = jax.random.normal(ka, (ROWS, k), jnp.bfloat16)
            b = jax.random.normal(kb, (k, n), jnp.bfloat16)
            plan, f = engine_program(ROWS, k, n, out_dtype, mesh)
            compiled = f.lower(a, b).compile()
            _check("tpu_custom_call" in compiled.as_text(),
                   f"engine {ROWS}x{k}x{n}: no tpu_custom_call in the HLO")
            out = compiled(a, b)
            _check(out.shape == (ROWS, n) and out.dtype == out_dtype,
                   f"engine {ROWS}x{k}x{n}: got {out.shape} {out.dtype}")
            err = _norm_err(out, _reference(a, b))
            tol = TOL_BF16_OUT if out_dtype == jnp.bfloat16 else TOL_F32_OUT
            warm = _timed(compiled, a, b)
            print(f"[engine] {ROWS}x{k} @ {k}x{n} bf16->"
                  f"{jnp.dtype(out_dtype).name} strategy={plan.strategy} "
                  f"tpu_custom_call=yes err={err:.3e} tol={tol:.3e} "
                  f"warm={warm * 1e3:.3f}ms on {kind}")
            _check(err <= tol, f"engine {ROWS}x{k}x{n}: err {err} > {tol}")
    print(f"[engine] compile {clock.compile_s:.2f}s "
          f"(persistent-cache hits {clock.cache_hits}) on {kind}")


# -- phase serve -------------------------------------------------------------

def phase_serve(kind: str, seed: int) -> None:
    cfg = get_config(SERVED)
    batch, seq, new, max_seq = 8, 512, 32, 2048
    with _Clock() as clock:
        model, params = init_model(cfg, seed)
        # prompt 0 fills the bucket: no left padding, so its prefill is the
        # plain forward's last position
        prompts = (synthetic_prompts(seed + 1, 1, seq, seq + 1,
                                     cfg.vocab_size)
                   + synthetic_prompts(seed, batch - 1, 64, seq + 1,
                                       cfg.vocab_size))
        sc = ServeConfig(max_new_tokens=new, max_seq=max_seq)
        run = serve(model, params, sc, prompts, buckets=[(batch, seq)],
                    seed=seed)
    print_run(run, cfg.name, routed=False)
    res = run.result
    _check(not run.errors, f"serve errors: {run.errors}")
    _check(len(res.new_tokens) == batch
           and all(len(t) == new for t in res.new_tokens),
           "serve: not every request was answered in full")
    toks = np.asarray(res.new_tokens)
    _check(bool(((toks >= 0) & (toks < cfg.vocab_size)).all()),
           "serve: a token outside the vocabulary")
    logits = np.asarray(res.prefill_logits, np.float32)
    _check(logits.shape == (batch, cfg.vocab_size)
           and bool(np.isfinite(logits).all()),
           f"serve: prefill logits {logits.shape} not finite")
    fwd = jax.jit(lambda p, t: model.forward(p, t)[0][:, -1])(
        params, jnp.asarray([prompts[0]], jnp.int32))
    err = _norm_err(logits[0], fwd[0])
    print(f"[serve] prefill vs forward, unpadded prompt of {seq}: "
          f"err={err:.3e} tol={TOL_LOGITS:.3e}")
    _check(err <= TOL_LOGITS, f"serve: prefill vs forward err {err}")
    q = res.latency_quantiles_ms()
    warm_s = sum(w["warm_s"] for w in run.warmup.values())
    peak = jax.devices()[0].memory_stats()["peak_bytes_in_use"]
    print(f"[serve] {cfg.name} on {kind}: warmup {warm_s:.2f}s, compile "
          f"{clock.compile_s:.2f}s (persistent-cache hits "
          f"{clock.cache_hits}), ttft {res.ttft_s * 1e3:.2f}ms, per-token "
          f"p50 {q['p50_ms']:.3f}ms p99 {q['p99_ms']:.3f}ms, "
          f"{res.tokens_per_s:.1f} tokens/s, peak_bytes_in_use {peak}")


# -- phase strategies (four chips) -------------------------------------------

def four_chip_meshes(devices):
    """(label, mesh) for the 2x2 torus, the 4-ring, the 4-chip pod axis and
    every 3-axis 4-chip mesh."""
    specs = [((2, 2), ("x", "y")), ((4,), ("t",)), ((4,), ("pod",))]
    specs += [(s, ("pod", "x", "y"))
              for s in itertools.product((1, 2, 4), repeat=3)
              if np.prod(s) == 4]
    for shape, names in specs:
        label = "x".join(map(str, shape)) + "(" + ",".join(names) + ")"
        yield label, make_mesh(shape, names, devices=devices[:4])


def admitted_plans(devices, m: int, k: int, n: int):
    """[(mesh label, plan)] for every (strategy, overlap) ``build_plan``
    admits on each 4-chip mesh; bf16 in, fp32 out.  Each refusal is
    printed, and the admitted set must be ``ADMITTED``."""
    plans, got = [], set()
    for label, mesh in four_chip_meshes(devices):
        for strategy, overlap in ALLOWED_COLLECTIVES:
            try:
                plan = build_plan(m, n, k, mesh=mesh, strategy=strategy,
                                  overlap=overlap, a_dtype=jnp.bfloat16,
                                  b_dtype=jnp.bfloat16,
                                  out_dtype=jnp.float32)
            except ValueError as e:
                print(f"[strategies] mesh {label} {strategy} "
                      f"overlap={overlap} refused: {e}")
                continue
            plans.append((label, plan))
            got.add((label, strategy, overlap))
    want = {(label, s.rstrip("+"), s.endswith("+"))
            for label, names in ADMITTED.items() for s in names.split()}
    _check(got == want, f"admitted beyond the table: {sorted(got - want)}; "
                        f"refused from it: {sorted(want - got)}")
    return plans


def collective_kinds(hlo_text: str) -> set:
    return {kind for kind, b in analyze(hlo_text).coll.items() if b > 0}


def phase_strategies(kind: str, seed: int) -> None:
    c = get_config(SERVED)
    m, k, n = ROWS, c.d_model, c.d_ff
    ka, kb = jax.random.split(jax.random.PRNGKey(seed))
    a = jax.random.normal(ka, (m, k), jnp.bfloat16)
    b = jax.random.normal(kb, (k, n), jnp.bfloat16)
    ref = _reference(a, b)
    plans = admitted_plans(jax.devices(), m, k, n)
    with _Clock() as clock:
        for label, plan in plans:
            f = jax.jit(functools.partial(execute_plan, plan))
            compiled = f.lower(a, b).compile()
            kinds = collective_kinds(compiled.as_text())
            out = compiled(a, b)
            err = _norm_err(out, ref)
            shards = [s.device.id for s in out.addressable_shards]
            allowed = ALLOWED_COLLECTIVES[(plan.strategy, plan.overlap)]
            # a plan over size-1 axes only replicates over the mesh: it
            # needs no collective (the compiler may keep or drop trivial ones)
            spread = math.prod(plan.mesh.shape[ax] for ax in plan.axes) > 1
            print(f"[strategies] mesh {label} {plan.strategy} "
                  f"overlap={plan.overlap} err={err:.3e} "
                  f"tol={TOL_F32_OUT:.0e} collectives={sorted(kinds)} "
                  f"kernel={'tpu_custom_call' in compiled.as_text()} "
                  f"shard devices={shards} on {kind}")
            _check(err <= TOL_F32_OUT,
                   f"{label} {plan.strategy}: err {err}")
            _check(kinds <= allowed and (kinds or not spread),
                   f"{label} {plan.strategy} over {plan.axes}: collectives "
                   f"{kinds}, README allows {allowed}")
    print(f"[strategies] {len(plans)} programs, compile "
          f"{clock.compile_s:.2f}s "
          f"on {kind}")
    routed_server(kind, seed)


def routed_server(kind: str, seed: int, layers: int = 4) -> None:
    """Plan-routed ``Server`` on a 2x2 mesh vs the unrouted one, at full
    width and ``layers`` deep."""
    cfg = dataclasses.replace(get_config(SERVED), num_layers=layers)
    model, params = init_model(cfg, seed)
    mesh = make_mesh((2, 2), ("x", "y"), devices=jax.devices()[:4])
    prompts = synthetic_prompts(seed, 8, 64, 513, cfg.vocab_size)
    sc = ServeConfig(max_new_tokens=4, max_seq=1024)
    local = serve(model, params, sc, prompts, buckets=[(8, 512)], seed=seed)
    routed = serve(model, params, sc, prompts, mesh=mesh,
                   buckets=[(8, 512)], seed=seed)
    print_run(routed, cfg.name, routed=True)
    _check(not routed.errors, f"routed serving: {routed.errors}")
    ref = np.asarray(local.result.prefill_logits, np.float32)
    out = np.asarray(routed.result.prefill_logits, np.float32)
    _check(bool(np.isfinite(out).all()), "routed logits not finite")
    err = _norm_err(out, ref)
    same = float(np.mean(np.asarray(routed.result.new_tokens)
                         == np.asarray(local.result.new_tokens)))
    print(f"[strategies] plan-routed Server 2x2 vs unrouted, {cfg.name} "
          f"{layers} layers: prefill logits err={err:.3e} "
          f"tol={TOL_ROUTED:.0e}, equal tokens {same:.3f} on {kind}")
    _check(err <= TOL_ROUTED, f"routed vs unrouted logits err {err}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips; "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2
    print(f"[setup] compile cache {enable_compile_cache()}; "
          f"{len(devices)} x {dev.device_kind}")
    if args.chips == 4:
        phase_strategies(dev.device_kind, args.seed)
    else:
        phase_engine(dev.device_kind, args.seed)
        phase_serve(dev.device_kind, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
