"""Window driver for plan-engine cells: every linear GEMM of one prefill,
layer after layer, through ``repro.plan.build_plan`` + ``execute_plan``.

Set-up builds each GEMM's plan once on the workload's mesh, jits one
program that applies a layer's GEMMs, compiles it ahead of time, and reads
the operand shardings the compiled program expects.  The weights of every
layer (each its own arrays) and the activations are then made from the
seed on the device, in one jitted call each, straight into those
shardings: the window measures the schedule, not the placement.

The window dispatches whole steps (every layer once) back to back,
keeping at most ``in_flight_layers`` layers queued ahead of the one the
host waits for, until ``--seconds`` have passed; the step under way then
completes.  The window keeps no output: held buffers would change where
the allocator places the next ones, and with them the speed, from seed to
seed.  Afterwards the same compiled program runs once more, on the same
operands, for a seeded sample of layers, and every GEMM output of those
layers is compared with the plain float32 GEMM.
"""
from __future__ import annotations

import collections
import contextlib
import functools
import math
import time

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from bench.lib import gemm
from bench.lib import trace as tr
from bench.lib.counts import gemm_flops, gemm_min_s
from bench.lib.harness import (TRACE_DIR, Check, CompileCount, Outcome,
                               memory_peak_bytes)
from bench.lib.seeds import jax_key, rng

def _layer(plans, ws, xs):
    from repro.plan import execute_plan

    return tuple(execute_plan(p, x, w) for p, x, w in zip(plans, xs, ws))


def build(cell, devices):
    """Plans and the compiled layer program, and the shardings it expects
    for (weights, activations)."""
    from repro.mesh import make_mesh
    from repro.plan import build_plan

    wl = cell.workload
    dtype, out_dtype = jnp.dtype(wl["dtype"]), jnp.dtype(wl["out_dtype"])
    m = wl["rows"]
    gemms = cell.model.linears(cell.config)
    mesh = make_mesh(wl["mesh"]["shape"], wl["mesh"]["axes"],
                     devices=devices)
    plans = tuple(build_plan(m, n, k, mesh=mesh, a_dtype=dtype, b_dtype=dtype,
                             out_dtype=out_dtype) for _, k, n, _ in gemms)
    program = jax.jit(functools.partial(_layer, plans)).lower(
        tuple(jax.ShapeDtypeStruct((k, n), dtype) for _, k, n, _ in gemms),
        tuple(jax.ShapeDtypeStruct((m, k), dtype) for _, k, n, _ in gemms),
    ).compile()
    w_shard, x_shard = program.input_shardings[0]
    return plans, program, w_shard, x_shard


def make_operands(cell, seed, w_shard, x_shard):
    """Every layer's weights (N(0, 1/k)) and one activation per GEMM input
    (N(0, 1)), on the device, from the seed."""
    wl = cell.workload
    dtype, m = jnp.dtype(wl["dtype"]), wl["rows"]
    gemms = cell.model.linears(cell.config)
    layers = cell.model.layers(cell.config)
    inputs = sorted({inp for *_, inp in gemms})

    def weights(key):
        return [tuple((jax.random.normal(jax.random.fold_in(key, i * 64 + g),
                                         (k, n), jnp.float32)
                       / math.sqrt(k)).astype(dtype)
                      for g, (_, k, n, _) in enumerate(gemms))
                for i in range(layers)]

    def acts(key):
        return tuple(jax.random.normal(
            jax.random.fold_in(key, inputs.index(inp)), (m, k),
            jnp.float32).astype(dtype) for _, k, _, inp in gemms)

    ws = jax.jit(weights, out_shardings=[w_shard] * layers)(
        jax_key(seed, 1))
    xs = jax.jit(acts, out_shardings=x_shard)(jax_key(seed, 2))
    return ws, xs


def run(cell, devices, peaks, *, seed, seconds, trace, t0):
    wl = cell.workload
    gemms = cell.model.linears(cell.config)
    layers = cell.model.layers(cell.config)
    m, chips = wl["rows"], len(devices)
    in_b = jnp.dtype(wl["dtype"]).itemsize
    out_b = jnp.dtype(wl["out_dtype"]).itemsize
    plans, program, w_shard, x_shard = build(cell, devices)
    ws, xs = make_operands(cell, seed, w_shard, x_shard)
    sample = sorted(rng(seed, 3).choice(layers, min(wl["check_layers"],
                                                    layers), replace=False))

    inflight = collections.deque()

    def step():
        for i in range(layers):
            with TraceAnnotation("bench.dispatch"):
                out = program(ws[i], xs)
            inflight.append(out)
            if len(inflight) > wl["in_flight_layers"]:
                with TraceAnnotation("bench.wait"):
                    jax.block_until_ready(inflight.popleft())

    def drain():
        while inflight:
            jax.block_until_ready(inflight.popleft())

    step()                         # warm: every program has run once
    drain()
    span = min(seconds, wl["trace_seconds"]) if trace else seconds
    profiler = (tr.record(TRACE_DIR) if trace
                else contextlib.nullcontext())
    compiles = CompileCount()
    with profiler:
        with TraceAnnotation(tr.WINDOW):
            t_start = time.perf_counter()
            steps = 0
            while True:
                step()
                steps += 1
                if time.perf_counter() - t_start >= span:
                    break
            drain()
            window_s = time.perf_counter() - t_start
    setup_s = t_start - t0
    peak = memory_peak_bytes(devices)
    print(f"{steps} steps of {layers} layers in {window_s:.3f} s, "
          f"{compiles.n} programs compiled or loaded in the window; plans: "
          f"{[(p.strategy, p.overlap) for p in plans]}", flush=True)

    limit = wl["limits"]["gemm_err"]
    errs = [float(gemm.error(xs[g], ws[i][g], out))
            for i in sample for g, out in enumerate(program(ws[i], xs))]
    worst = max(errs, key=lambda e: math.inf if math.isnan(e) else e)
    flops = steps * layers * sum(gemm_flops(m, k, n) for _, k, n, _ in gemms)
    out = Outcome(
        end_to_end={"matmul_tflops": flops / window_s / 1e12,
                    "setup_s": setup_s},
        attempted=steps * layers * len(gemms),
        failed=sum(not e <= limit for e in errs),
        checks=[Check("gemm_err", worst, limit)],
        memory_peak_bytes=peak,
        control=lambda: max(
            float(gemm.error(xs[g], ws[i][g], gemm.control(xs[g], ws[i][g])))
            for i in sample for g in range(len(gemms))))
    if trace:
        out.trace = tr.load(tr.latest_xplane(TRACE_DIR))
        out.work = {
            "useful_flops": flops,
            "gemm_min_s": steps * layers * sum(
                gemm_min_s(m, k, n, in_b, out_b, peaks, chips)
                for _, k, n, _ in gemms),
            "strategies": [p.strategy for p in plans],
        }
    return out
