"""Window driver for serving cells: ``repro.serve.Server.generate`` after
``Server.warmup``, over the configuration's model with weights made from
the seed, driven as a closed loop.

Set-up builds the program's model from the configuration file, makes every
weight on the device in one jitted call, checks that they fill the
program's parameter tree exactly, warms the server's bucket and serves one
warm batch drawn apart from the window's traffic, so that every program
the window runs has run once.

One client sends batches of ``batch`` requests back to back: ``Server``
admits no request while a batch decodes, so a queue of its own would only
time the harness.  The window closes at the first batch boundary after
``--seconds``.  Tokens per second are the generated tokens of every batch
over the whole window; the tails are over every request of the window:
time to first token one sample per request, inter-token latency one per
decode step of each request, both as the server's host clock reads them
around ``block_until_ready`` (``ServeResult.ttft_s``,
``ServeResult.step_latencies_s``), since ``generate`` hands back a whole
batch at once.

Afterwards the server is dropped and the plain reference runs once over a
seeded sample of the requests served (the longest among them), prompt and
served tokens together: at each served position the reference's best
logit may lie above that of the token served by no more than the limit.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from bench.lib import gemm, traffic
from bench.lib import trace as tr
from bench.lib.counts import kv_bytes, linear_params, request_flops
from bench.lib.harness import (TRACE_DIR, Check, CompileCount, Outcome,
                               memory_peak_bytes)
from bench.lib.seeds import jax_key, rng


@dataclasses.dataclass
class Batch:
    """What the window keeps of one served batch."""
    prompts: list
    new_tokens: list
    ttft_s: float
    step_latencies_s: np.ndarray
    wall_s: float


def build(cell, seed):
    """The program's model, its parameters (the seed's weights in its
    tree) and the weights themselves."""
    from repro.configs import get_config
    from repro.models.registry import build_model

    cfg, ref = cell.config, cell.model
    mcfg = dataclasses.replace(get_config(cfg["program_config"]),
                               **ref.program_overrides(cfg))
    model = build_model(mcfg)
    weights = ref.make_weights(cfg, jax_key(seed, 1))
    params = ref.to_program(weights)
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if jax.tree.structure(got) != jax.tree.structure(want) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
            zip(jax.tree.leaves(got), jax.tree.leaves(want))):
        raise ValueError("the configuration's weights do not fill the "
                         "program's parameter tree")
    return model, params, weights


def run(cell, devices, peaks, *, seed, seconds, trace, t0):
    from repro.runtime.serve import ServeConfig
    from repro.serve import Server

    wl, cfg, ref = cell.workload, cell.config, cell.model
    batch, new = wl["batch"], wl["new_tokens"]
    model, params, weights = build(cell, seed)
    server = Server(model, params,
                    ServeConfig(max_new_tokens=new, max_seq=wl["max_seq"]),
                    buckets=[tuple(wl["bucket"])])
    server.warmup()
    server.generate(traffic.prompts(rng(seed, 4), batch, wl))

    span = min(seconds, wl["trace_seconds"]) if trace else seconds
    draw = rng(seed, 2)
    served = []
    compiles = CompileCount()
    profiler = tr.record(TRACE_DIR) if trace else contextlib.nullcontext()
    with profiler:
        with TraceAnnotation(tr.WINDOW):
            t_start = time.perf_counter()
            while True:
                prompts = traffic.prompts(draw, batch, wl)
                with TraceAnnotation("bench.generate"):
                    res = server.generate(prompts, key=jax.random.PRNGKey(0))
                served.append(Batch(prompts, res.new_tokens, res.ttft_s,
                                    res.step_latencies_s, res.wall_s))
                if time.perf_counter() - t_start >= span:
                    break
            window_s = time.perf_counter() - t_start
    setup_s = t_start - t0
    peak = memory_peak_bytes(devices)
    generated = sum(len(t) for b in served for t in b.new_tokens)
    print(f"served {len(served)} batches in {window_s:.3f} s, {compiles.n} "
          f"programs compiled or loaded in the window; batch walls (ms): "
          f"{[round(b.wall_s * 1e3, 1) for b in served]}", flush=True)
    del server
    gc.collect()

    requests = [(p, t) for b in served
                for p, t in zip(b.prompts, b.new_tokens)]
    short = sum(len(t) != new for _, t in requests)
    gap = widest_gap(cell, weights, requests, seed)
    limit = wl["limits"]["logit_gap"]
    out = Outcome(
        end_to_end={
            "tokens_per_s": generated / window_s,
            "ttft_p95_ms": 1e3 * float(np.percentile(
                [b.ttft_s for b in served for _ in b.prompts], 95)),
            "itl_p95_ms": 1e3 * float(np.percentile(
                [dt for b in served for _ in b.prompts
                 for dt in b.step_latencies_s], 95)),
            "setup_s": setup_s},
        attempted=len(requests), failed=short,
        checks=[Check("logit_gap", gap, limit),
                Check("short_answers", short, 0)],
        memory_peak_bytes=peak,
        control=lambda: widest_gap(cell, weights, requests, seed,
                                   quantize=gemm.fp8))
    if trace:
        out.trace = tr.load(tr.latest_xplane(TRACE_DIR))
        out.work = work(cell, weights, served)
    return out


def check_sample(requests, count, seed):
    """Indices of the requests the reference reads: the longest, and the
    rest drawn from the seed."""
    sizes = [len(p) + len(t) for p, t in requests]
    longest = int(np.argmax(sizes))
    rest = [i for i in range(len(requests)) if i != longest]
    pick = rng(seed, 5).choice(len(rest), min(count - 1, len(rest)),
                               replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def widest_gap(cell, weights, requests, seed, quantize=None):
    """Over a sample of served requests: the widest gap by which a served
    token's reference logit lies below the reference's best at that
    position.  With ``quantize`` the gap read is that of the token the
    quantized reference puts first (the control)."""
    wl, ref = cell.workload, cell.model
    picked = [requests[i] for i in check_sample(requests,
                                                wl["check_requests"], seed)]
    vocab = cell.config["vocab_size"]
    if any(not 0 <= x < vocab for _, t in picked for x in t):
        return math.inf
    width = wl["bucket"][1] + wl["new_tokens"] - 1
    toks = np.zeros((len(picked), width), np.int32)
    rows, pos, served = [], [], []
    for r, (p, t) in enumerate(picked):
        seq = list(p) + list(t[:-1])
        toks[r, :len(seq)] = seq
        for j, x in enumerate(t):
            rows.append(r)
            pos.append(len(p) - 1 + j)
            served.append(x)
    rows, pos = jnp.asarray(rows), jnp.asarray(pos)
    logits = ref.forward(cell.config, weights, jnp.asarray(toks))[rows, pos]
    if quantize is not None:
        low = ref.forward(cell.config, weights, jnp.asarray(toks),
                          quantize=quantize)[rows, pos]
        served = jnp.argmax(low, axis=-1)
    chosen = jnp.take_along_axis(logits, jnp.asarray(served)[:, None], 1)
    return float(jnp.max(jnp.max(logits, axis=-1) - chosen[:, 0]))


def work(cell, weights, served):
    """What the per-layer readers divide by, for the batches of the
    traced window."""
    cfg, ref, wl = cell.config, cell.model, cell.workload
    layers = ref.layers(cfg)
    lp = linear_params(ref.linears(cfg))
    flops = 0.0
    kv_steps = []
    for b in served:
        for p, t in zip(b.prompts, b.new_tokens):
            flops += request_flops(lp, layers, ref.head_params(cfg),
                                   ref.attn_width(cfg), len(p), len(t))
        for j in range(1, wl["new_tokens"]):
            kv_steps.append(kv_bytes(sum(len(p) + j for p in b.prompts),
                                     layers, ref.kv_width(cfg), 2))
    step_weights = sum(a.nbytes for k, a in weights.items()
                       if k != "embedding")
    step_weights += wl["batch"] * cfg["hidden_size"] * 2   # rows looked up
    return {
        "model_flops": flops,
        "decode_weight_bytes": step_weights,
        "decode_kv_bytes": float(np.mean(kv_steps)),
    }
