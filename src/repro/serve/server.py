"""Plan-routed serving server: persistent compiled steps, bucket routing,
AOT plan-cache warmup, latency accounting.

The seed-era ``runtime.serve.generate`` builds a fresh ``jax.jit`` wrapper
per call, so every request pays trace + compile + planning.  ``Server``
holds one compiled prefill and one compiled decode step per (batch, seq)
shape for the lifetime of the process, and warms those of a declared
bucket grid:

  * ``warmup()`` AOT-compiles each bucket's prefill + decode step
    (``runtime.serve.compile_bucket``: the cache donated and updated in
    place) and runs them once on a dummy batch.
    Tracing routes every layer matmul through ``repro.plan.build_plan``
    inside the ``planned_matmuls(mesh)`` scope, so the plan cache fills
    with each bucket's ``SchedulePlan``s.  The plans inserted per bucket
    are snapshotted (key -> plan).
  * ``generate()`` routes the request batch to the nearest warm bucket
    (left-padding prompts to ``bucket.seq`` with per-row position offsets,
    padding the batch with dummy rows to ``bucket.batch``), re-``get``s the
    bucket's plan keys from the cache -- all hits after warmup; an evicted
    plan is re-pinned from the snapshot -- and decodes with the bucket's
    compiled pair (a batch that fits no bucket is served at its own shape,
    compiled on first use).  Per-token wall latencies and TTFT are measured
    around the blocking device calls.

Observability: ``obs`` spans at every phase, which reach the profiler's
trace whenever a ``jax.profiler`` session records.  ``serve.warmup``
(``bucket``, ``plans``) per warmed bucket, and the counter
``serve.cache_alias`` (label ``bucket``): the compiled step's aliased
bytes over its cache's bytes; per ``generate`` call,
``serve.generate`` (``requests``, ``bucket``: a label or ``"cold"``)
around ``serve.prepare`` (routing, the plan probe, batching, the token
upload, the cache; ``repinned``: plans re-pinned by the probe),
``serve.prefill`` (``batch``, ``seq``, ``tokens``: the real prompt
tokens), ``serve.first_token``, ``serve.decode`` (the loop: one
``serve.key_split`` and one ``serve.decode_step`` per token after the
first) and ``serve.collect``.  Span args are host values: no span syncs
the device or moves data.  ``ServeResult`` carries the latencies and
counts.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.plan.cache import plan_cache
from repro.runtime.serve import (BucketPrograms, ServeConfig, _sample,
                                 batch_requests, compile_bucket,
                                 planned_scope)

from .buckets import Bucket, as_bucket, route

DEFAULT_BUCKETS = ((4, 16), (4, 32), (8, 16), (8, 32))


@dataclasses.dataclass
class ServeResult:
    """One served batch: per-request token sequences + latency breakdown."""

    sequences: List[List[int]]        # prompt + generated, padding stripped
    new_tokens: List[List[int]]       # generated suffix per request
    bucket: Optional[str]             # routed bucket label, None = cold
    ttft_s: float                     # prefill + first sampled token
    step_latencies_s: np.ndarray      # per-token decode latency (after 1st)
    wall_s: float
    plan_probe: Dict[str, int]        # warm-plan cache probe accounting
    prefill_logits: Optional[jax.Array] = None  # (requests, vocab), last
                                                # prompt token's logits

    @property
    def generated_tokens(self) -> int:
        return sum(len(t) for t in self.new_tokens)

    @property
    def tokens_per_s(self) -> float:
        return self.generated_tokens / self.wall_s if self.wall_s > 0 else 0.0

    def latency_quantiles_ms(self) -> Dict[str, Optional[float]]:
        """p50/p99 per-token decode latency in ms; None when fewer than one
        timed step was taken (max_new_tokens <= 1 -- the sweep report
        renders these as '-')."""
        if self.step_latencies_s.size == 0:
            return {"p50_ms": None, "p99_ms": None}
        return {
            "p50_ms": float(np.percentile(self.step_latencies_s, 50) * 1e3),
            "p99_ms": float(np.percentile(self.step_latencies_s, 99) * 1e3),
        }


class Server:
    """Production serving harness over one model + mesh (see module doc).

    ``mesh=None`` serves the local (unrouted) baseline path -- same
    bucketing and warmup, no plan engine -- which the sweep harness uses
    as the bitwise-comparison baseline for plan-routed decode.
    """

    def __init__(self, model, params, cfg: ServeConfig, *, mesh=None,
                 strategy: Optional[str] = None,
                 tuning=None,
                 buckets: Sequence = DEFAULT_BUCKETS,
                 pad_id: int = 0, dummy_token: int = 1):
        self.model = model
        self.params = params
        self.cfg = cfg
        self.mesh = mesh
        self.strategy = strategy
        self.tuning = tuning
        self.pad_id = pad_id
        self.dummy_token = dummy_token
        self.buckets: Tuple[Bucket, ...] = tuple(
            sorted(as_bucket(b) for b in buckets))
        for b in self.buckets:
            cfg.validate_prompt_len(b.seq)
        self._uses_offsets = bool(
            getattr(model, "supports_position_offsets", False))
        # per served (batch, seq) shape, warmed bucket or cold: its
        # compiled programs, for the server's lifetime; the plan scope
        # lives INSIDE the traced functions, so this server's programs
        # are its own (see runtime.serve._prefill_fn)
        self._programs: Dict[Bucket, BucketPrograms] = {}
        # per warmed bucket: the share of its cache the compiled step
        # aliases
        self._cache_alias: Dict[Bucket, float] = {}
        # per-bucket plan snapshot: key -> SchedulePlan inserted by warmup
        self._bucket_plans: Dict[Bucket, Dict] = {}
        # per-bucket tuning keys the warmup searches populated (a live
        # Tuner tunes each bucket's local kernel shapes at trace time)
        self._bucket_tune_keys: Dict[Bucket, Tuple] = {}
        self._warm_cache_info: Optional[Dict[str, int]] = None
        self._warm_tune_stats: Optional[Dict[str, int]] = None

    # -- warmup --------------------------------------------------------------

    def warmup(self, buckets: Optional[Sequence] = None) -> Dict:
        """AOT-warm every bucket: compile the (prefill, step) program pair
        and populate the plan cache with the bucket's ``SchedulePlan``s.
        Returns per-bucket accounting ``{label: {plans, warm_s,
        cache_alias}}``; after this, requests routed to a warm bucket pay
        zero planning/compile cost and ``cache_report()`` pins the
        serve-window hit rate."""
        buckets = self.buckets if buckets is None else tuple(
            as_bucket(b) for b in buckets)
        report: Dict[str, Dict] = {}
        for bucket in buckets:
            t0 = time.perf_counter()
            with obs.span("serve.warmup", bucket=bucket.label) as span:
                n_plans = self._warm_bucket(bucket)
                span.set_metadata(plans=n_plans)
            report[bucket.label] = {
                "plans": n_plans,
                "warm_s": time.perf_counter() - t0,
                "cache_alias": self._cache_alias[bucket],
            }
        self._warm_cache_info = plan_cache.info()
        if self.tuning is not None and hasattr(self.tuning, "stats"):
            self._warm_tune_stats = dict(self.tuning.stats)
        return report

    def _warm_bucket(self, bucket: Bucket) -> int:
        """Trace/compile one bucket's programs; snapshot the plan-cache
        entries it inserted so the router can probe (and re-pin) them."""
        before = set(plan_cache.keys())
        tune_before = (set(self.tuning.keys())
                       if self.tuning is not None
                       and hasattr(self.tuning, "keys") else set())
        progs = self._programs_for(bucket)
        toks = jnp.full((bucket.batch, bucket.seq), self.dummy_token,
                        jnp.int32)
        offsets = (jnp.zeros((bucket.batch,), jnp.int32)
                   if self._uses_offsets else None)
        logits, cache = self._call(progs.prefill, progs.init_cache(), toks,
                                   offsets)
        # the compiled step's aliased bytes over the device bytes of the
        # cache it takes: 1.0 when it updates the whole cache in place
        alias = (progs.step.memory_analysis().alias_size_in_bytes
                 / sum(a.on_device_size_in_bytes()
                       for a in jax.tree.leaves(cache)))
        self._cache_alias[bucket] = alias
        if obs.enabled():
            obs.counter("serve.cache_alias").inc(alias, bucket=bucket.label)
        if self.cfg.max_new_tokens > 0:
            cur = _sample(logits, self.cfg, jax.random.PRNGKey(0))
            logits, cache = self._call(progs.step, cache, cur[:, None],
                                       jnp.int32(bucket.seq), offsets)
        jax.block_until_ready(logits)
        new_keys = [k for k in plan_cache.keys() if k not in before]
        snapshot = {k: plan_cache.get(k) for k in new_keys}
        # a later bucket can share plans with an earlier one (same decode
        # batch): extend instead of replace so probes cover the union
        self._bucket_plans.setdefault(bucket, {}).update(snapshot)
        if self.tuning is not None and hasattr(self.tuning, "keys"):
            new_tune = tuple(k for k in self.tuning.keys()
                             if k not in tune_before)
            prev = self._bucket_tune_keys.get(bucket, ())
            self._bucket_tune_keys[bucket] = prev + tuple(
                k for k in new_tune if k not in prev)
        return len(new_keys)

    # -- serving -------------------------------------------------------------

    def generate(self, prompt_list: Sequence[Sequence[int]],
                 key: Optional[jax.Array] = None) -> ServeResult:
        """Serve one request batch: route to the nearest warm bucket, pad,
        decode, strip padding, return per-request sequences + latencies."""
        if not prompt_list:
            return ServeResult([], [], None, 0.0, np.zeros(0), 0.0,
                               {"probed": 0, "missing": 0})
        n = len(prompt_list)
        with obs.span("serve.generate", requests=n) as call:
            t_start = time.perf_counter()
            with obs.span("serve.prepare") as prep:
                maxlen = max(len(p) for p in prompt_list)
                bucket = route(n, maxlen, self.buckets)
                if bucket is not None and not self._uses_offsets \
                        and bucket.seq != maxlen:
                    # seq-padding shifts tokens through a recurrent state;
                    # only batch-pad for models without position-offset
                    # support
                    bucket = Bucket(bucket.batch, maxlen) \
                        if bucket.batch >= n else None
                probe = self._probe_bucket(bucket)
                call.set_metadata(bucket=bucket.label if bucket else "cold")
                prep.set_metadata(repinned=probe["missing"])

                if bucket is None:
                    batch, lens = batch_requests(prompt_list, self.pad_id)
                    b_rows = n
                else:
                    dummies = [[self.dummy_token]] * (bucket.batch - n)
                    batch, lens = batch_requests(
                        list(prompt_list) + dummies, self.pad_id,
                        pad_to=bucket.seq)
                    b_rows = bucket.batch
                self.cfg.validate_prompt_len(batch.shape[1])

                key = key if key is not None else jax.random.PRNGKey(0)
                tokens = jnp.asarray(batch, jnp.int32)
                sp = tokens.shape[1]
                offsets = (jnp.asarray(sp - lens, jnp.int32)
                           if self._uses_offsets else None)
                progs = self._programs_for(Bucket(b_rows, sp))
                cache = progs.init_cache()

            out = [tokens]
            step_lat: List[float] = []
            with planned_scope(self.mesh, self.strategy, self.tuning):
                with obs.span("serve.prefill", batch=b_rows, seq=sp,
                              tokens=sum(len(p) for p in prompt_list)):
                    logits, cache = self._call(progs.prefill, cache,
                                               tokens, offsets)
                prefill_logits = logits
                if self.cfg.max_new_tokens > 0:
                    with obs.span("serve.first_token"):
                        cur = _sample(logits, self.cfg, key)
                        jax.block_until_ready(cur)
                    ttft = time.perf_counter() - t_start
                    out.append(cur[:, None])
                    with obs.span("serve.decode"):
                        for t in range(sp, sp + self.cfg.max_new_tokens - 1):
                            with obs.span("serve.key_split"):
                                key, sub = jax.random.split(key)
                            t0 = time.perf_counter()
                            with obs.span("serve.decode_step", batch=b_rows,
                                          pos=t):
                                logits, cache = self._call(
                                    progs.step, cache, cur[:, None],
                                    jnp.int32(t), offsets)
                                cur = _sample(logits, self.cfg, sub)
                                jax.block_until_ready(cur)
                            step_lat.append(time.perf_counter() - t0)
                            out.append(cur[:, None])
                else:
                    jax.block_until_ready(logits)
                    ttft = time.perf_counter() - t_start
            with obs.span("serve.collect"):
                full = np.asarray(jnp.concatenate(out, axis=1))
                wall = time.perf_counter() - t_start
                sequences, new_tokens = [], []
                for i in range(n):
                    row = full[i]
                    seq = row[sp - int(lens[i]):].tolist()  # strip left pad
                    sequences.append(seq)
                    new_tokens.append(seq[int(lens[i]):])
                prefill_logits = prefill_logits[:n]
        return ServeResult(sequences, new_tokens,
                           bucket.label if bucket else None,
                           ttft, np.asarray(step_lat), wall, probe,
                           prefill_logits)

    # -- plan-cache accounting -----------------------------------------------

    def _probe_bucket(self, bucket: Optional[Bucket]) -> Dict[str, int]:
        """Re-``get`` the bucket's warm plan keys: all hits after warmup
        (that IS the 100%-hit-rate pin); an evicted entry is re-pinned from
        the warmup snapshot and counted."""
        if bucket is None or bucket not in self._bucket_plans:
            return {"probed": 0, "missing": 0}
        snapshot = self._bucket_plans[bucket]
        missing = [k for k in snapshot if plan_cache.get(k) is None]
        for k in missing:
            if snapshot[k] is not None:
                plan_cache.put(k, snapshot[k])
        out = {"probed": len(snapshot), "missing": len(missing)}
        if self.tuning is not None and hasattr(self.tuning, "lookup_key"):
            tune_keys = self._bucket_tune_keys.get(bucket, ())
            tune_missing = [k for k in tune_keys
                            if self.tuning.lookup_key(k) is None]
            out["tune_probed"] = len(tune_keys)
            out["tune_missing"] = len(tune_missing)
        return out

    def cache_report(self) -> Dict:
        """Plan-cache accounting split at the warmup boundary: the serve
        window's hit rate is 1.0 when every post-warmup lookup (request
        probes + any re-traces) hit -- the acceptance pin for bucketed
        serving."""
        info = plan_cache.info()
        rep: Dict = {"info": info}
        if self._warm_cache_info is not None:
            hits = info["hits"] - self._warm_cache_info["hits"]
            misses = info["misses"] - self._warm_cache_info["misses"]
            total = hits + misses
            rep["serve_window"] = {
                "hits": hits, "misses": misses,
                "hit_rate": (hits / total) if total else None,
            }
        if self.tuning is not None and hasattr(self.tuning, "stats"):
            stats = dict(self.tuning.stats)
            tun: Dict = {
                "entries": len(self.tuning.keys())
                if hasattr(self.tuning, "keys") else None,
                "stats": stats,
            }
            if self._warm_tune_stats is not None:
                hits = stats["hits"] - self._warm_tune_stats["hits"]
                misses = stats["misses"] - self._warm_tune_stats["misses"]
                total = hits + misses
                tun["serve_window"] = {
                    "hits": hits, "misses": misses,
                    "hit_rate": (hits / total) if total else None,
                }
            rep["tuning"] = tun
        return rep

    # -- internals -----------------------------------------------------------

    def _programs_for(self, shape: Bucket) -> BucketPrograms:
        """The compiled programs of a (batch, seq) shape, compiled on first
        use: at warmup for a bucket, in ``generate`` for a cold shape."""
        if shape not in self._programs:
            self._programs[shape] = compile_bucket(
                self.model, self.params, shape.batch, shape.seq,
                self.cfg.max_seq, offsets=self._uses_offsets, mesh=self.mesh,
                strategy=self.strategy, tuning=self.tuning)
        return self._programs[shape]

    def _call(self, fn, cache, *args):
        """``fn(params, cache, *args)``, leaving out ``offsets`` (the last
        argument) when it is None.  The cache is donated: rebind it to the
        one returned."""
        if args[-1] is None:
            args = args[:-1]
        return fn(self.params, cache, *args)

def warmup(model, params, cfg: ServeConfig, *, mesh=None,
           buckets: Sequence = DEFAULT_BUCKETS,
           strategy: Optional[str] = None, tuning=None) -> Server:
    """Build a ``Server`` and AOT-warm its bucket grid in one call:
    ``server = warmup(model, params, cfg, mesh=mesh, buckets=[(8, 32)])``.
    Returns the warmed server (its ``warmup_report`` attribute holds the
    per-bucket accounting)."""
    server = Server(model, params, cfg, mesh=mesh, strategy=strategy,
                    tuning=tuning, buckets=buckets)
    server.warmup_report = server.warmup()
    return server
