"""Window driver for expert-layer cells: the routed part of the program's
own MoE layer (``repro.layers.moe.routed_experts``: route over every
expert, dispatch the pairs this chip's experts hold, the grouped expert
GEMMs, combine), applied to one prefill's tokens, layer after layer.

Set-up makes every MoE layer's router and held experts from the seed (each
layer its own arrays) and one activation, on the device in one jitted call
each, and compiles, ahead of time, one program that applies one layer's
routed part; that program runs once for each layer's weights.  The shared
experts, attention and the rest of the model are not run here.

The window dispatches whole steps (every MoE layer once) back to back,
keeping at most ``in_flight_layers`` layers queued ahead of the one the
host waits for, until ``--seconds`` have passed; the step under way then
completes.  The window keeps no output.

Afterwards one routing pass per layer (the program's ``route``) counts the
rows each held expert got, which every FLOP and byte count is taken from.
For a seeded sample of layers the same compiled program runs once more
and its output is compared with the reference's ``expert_layer``: the
(token, choice) pairs the program routes differently from the float32
reference are counted (``route_mismatch``), and over the tokens routed
alike the output's error is max|out - ref| / max|ref| (``moe_err``).  A
token routed differently would show the two experts' difference, whatever
the GEMMs' precision; ``route_mismatch`` holds those to their own limit.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from bench.lib import gemm
from bench.lib import trace as tr
from bench.lib.counts import gemm_flops, gemm_min_s
from bench.lib.harness import (TRACE_DIR, Check, CompileCount, Outcome,
                               memory_peak_bytes)
from bench.lib.seeds import jax_key, rng

# the program's names of a layer's routed weights, from the reference's
_PROGRAM_NAMES = {"router": "router", "expert_gate": "w_gate",
                  "expert_up": "w_up", "expert_down": "w_down"}


def program_config(cell):
    from repro.configs import get_config

    cfg = cell.config
    return dataclasses.replace(get_config(cfg["program_config"]),
                               **cell.model.program_overrides(cfg))


def build(mcfg, weights, x):
    """The compiled program applying one layer's routed part, and the
    program's routing."""
    from repro.layers.moe import route, routed_experts

    def layer(p, x):
        return routed_experts(p, x, mcfg)[0].astype(x.dtype)

    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                          (to_program(weights[0]), x))
    program = jax.jit(layer).lower(*shapes).compile()
    routing = jax.jit(lambda router, x: route(router, x, mcfg)[1])
    return program, routing


def to_program(w):
    return {_PROGRAM_NAMES[k]: a for k, a in w.items()}


@functools.partial(jax.jit, static_argnames=("first", "held"))
def held_rows(chosen, first, held):
    """Rows each held expert gets from the routing ``chosen`` (N, k)."""
    local = chosen.reshape(-1) - first
    return jnp.sum(local[:, None] == jnp.arange(held), axis=0)


@jax.jit
def compare(out, ref, chosen, ref_chosen):
    """(error over the tokens routed alike, pairs routed differently)."""
    hit = jnp.any(chosen[:, :, None] == ref_chosen[:, None, :], axis=-1)
    alike = jnp.all(hit, axis=-1)
    diff = jnp.abs(out.astype(jnp.float32) - ref)
    err = jnp.max(jnp.where(alike[:, None], diff, 0.0)) / jnp.max(
        jnp.abs(ref))
    return err, jnp.sum(~hit)


def run(cell, devices, peaks, *, seed, seconds, trace, t0):
    wl, cfg, ref = cell.workload, cell.config, cell.model
    layers = ref.moe_layers(cfg)
    n, d = wl["rows"], cfg["hidden_size"]
    held, first = cfg["n_routed_experts"], cfg["first_held_expert"]
    mcfg = program_config(cell)
    ws = ref.expert_weights(cfg, jax_key(seed, 1))
    x = jax.jit(lambda k: jax.random.normal(k, (n, d), jnp.float32).astype(
        wl["dtype"]))(jax_key(seed, 2))
    program, routing = build(mcfg, ws, x)
    params = [to_program(w) for w in ws]
    sample = sorted(rng(seed, 3).choice(layers, min(wl["check_layers"],
                                                    layers), replace=False))

    inflight = collections.deque()

    def step():
        for i in range(layers):
            with TraceAnnotation("bench.dispatch"):
                out = program(params[i], x)
            inflight.append(out)
            if len(inflight) > wl["in_flight_layers"]:
                with TraceAnnotation("bench.wait"):
                    jax.block_until_ready(inflight.popleft())

    def drain():
        while inflight:
            jax.block_until_ready(inflight.popleft())

    step()                         # warm: the program has run once
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
    compiled = compiles.n

    rows = np.asarray(jnp.stack([held_rows(routing(w["router"], x), first,
                                           held) for w in ws]))
    print(f"{steps} steps of {layers} layers in {window_s:.3f} s, "
          f"{compiled} programs compiled or loaded in the window; rows per "
          f"held expert, layer 0: {rows[0].tolist()}, all layers: "
          f"{int(rows.min())}-{int(rows.max())}, {int(rows.sum())} in all",
          flush=True)

    def check(i, quantize=None):
        w = ws[i]
        ref_chosen = ref.route(cfg, w, x)[1]
        want = ref.expert_layer(cfg, w, x)
        if quantize is None:
            got, chosen = program(params[i], x), routing(w["router"], x)
        else:
            got = ref.expert_layer(cfg, w, x, quantize=quantize)
            chosen = ref.route(cfg, w, x, quantize=quantize)[1]
        err, mismatch = compare(got, want, chosen, ref_chosen)
        return float(err), int(mismatch)

    readings = [check(i) for i in sample]
    limits = wl["limits"]
    worst = max((e for e, _ in readings),
                key=lambda e: math.inf if math.isnan(e) else e)

    def control():
        found = [check(i, quantize=gemm.fp8) for i in sample]
        print(f"control: route_mismatch {max(m for _, m in found)}",
              flush=True)
        return max(e for e, _ in found)

    expert = ref.expert_linears(cfg)
    router_flops = gemm_flops(n, d, cfg["published"]["n_routed_experts"])
    flops = steps * sum(router_flops + sum(gemm_flops(int(m), k, nn)
                                           for m in r for _, k, nn in expert)
                        for r in rows)
    out = Outcome(
        end_to_end={"matmul_tflops": flops / window_s / 1e12,
                    "setup_s": setup_s},
        attempted=steps * layers,
        failed=sum(not (e <= limits["moe_err"]
                        and m <= limits["route_mismatch"])
                   for e, m in readings),
        checks=[Check("moe_err", worst, limits["moe_err"]),
                Check("route_mismatch", max(m for _, m in readings),
                      limits["route_mismatch"])],
        memory_peak_bytes=peak,
        control=control)
    if trace:
        out.trace = tr.load(tr.latest_xplane(TRACE_DIR))
        out.work = {
            "useful_flops": flops,
            "expert_rows": rows.tolist(),
            "gmm_min_s": steps * sum(
                gemm_min_s(int(m), k, nn, 2, 2, peaks, len(devices))
                for r in rows for m in r if m for _, k, nn in expert),
        }
    return out
