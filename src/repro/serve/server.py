"""Plan-routed serving server: persistent compiled steps, bucket routing,
AOT plan-cache warmup, latency accounting.

The seed-era ``runtime.serve.generate`` builds a fresh ``jax.jit`` wrapper
per call, so every request pays trace + compile + planning.  ``Server``
holds ONE jitted prefill and ONE jitted decode step for the lifetime of
the process and AOT-warms them over a declared (batch, seq) bucket grid:

  * ``warmup()`` runs a dummy prefill + decode step per bucket inside the
    ``planned_matmuls(mesh)`` scope.  Tracing routes every layer matmul
    through ``repro.plan.build_plan``, so the plan cache fills with each
    bucket's ``SchedulePlan``s and XLA compiles the bucket's program pair.
    The plans inserted per bucket are snapshotted (key -> plan).
  * ``generate()`` routes the request batch to the nearest warm bucket
    (left-padding prompts to ``bucket.seq`` with per-row position offsets,
    padding the batch with dummy rows to ``bucket.batch``), re-``get``s the
    bucket's plan keys from the cache -- all hits after warmup; an evicted
    plan is re-pinned from the snapshot -- and decodes with the warm
    compiled functions.  Per-token wall latencies and TTFT are measured
    around the blocking device calls.

Observability: ``serve.prefill`` / ``serve.decode_step`` spans,
``serve.ttft_us`` / ``serve.decode_token_us`` histograms, and
``serve.requests`` / ``serve.tokens`` / ``serve.cold_bucket`` /
``serve.plan_repin`` counters (all guarded on ``obs.enabled()``).
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
from repro.runtime.serve import (ServeConfig, _default_prefill, _default_step,
                                 _sample, batch_requests, planned_scope)

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
        # ONE persistent compiled function pair for the server's lifetime;
        # the plan scope lives INSIDE the jitted closure so this server's
        # trace-cache entries are its own (see runtime.serve._default_*)
        self._prefill = _default_prefill(model, mesh, strategy, tuning)
        self._step = _default_step(model, mesh, strategy, tuning)
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
        Returns per-bucket accounting ``{label: {plans, warm_s}}``; after
        this, requests routed to a warm bucket pay zero planning/compile
        cost and ``cache_report()`` pins the serve-window hit rate."""
        buckets = self.buckets if buckets is None else tuple(
            as_bucket(b) for b in buckets)
        report: Dict[str, Dict] = {}
        for bucket in buckets:
            t0 = time.perf_counter()
            n_plans = self._warm_bucket(bucket)
            report[bucket.label] = {
                "plans": n_plans,
                "warm_s": time.perf_counter() - t0,
            }
        if obs.enabled():
            obs.counter("serve.warmup.buckets").inc(len(buckets))
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
        toks = jnp.full((bucket.batch, bucket.seq), self.dummy_token,
                        jnp.int32)
        cache = self.model.init_cache(bucket.batch, self.cfg.max_seq)
        offsets = (jnp.zeros((bucket.batch,), jnp.int32)
                   if self._uses_offsets else None)
        key = jax.random.PRNGKey(0)
        with planned_scope(self.mesh, self.strategy, self.tuning):
            with obs.span("serve.warmup", bucket=bucket.label):
                logits, cache = self._call_prefill(cache, toks, offsets)
                # two steps, not one: step 2's inputs carry the shardings
                # step 1's outputs committed them to, a different jit
                # signature than the fresh init_cache warmup step -- one
                # step would leave serving to compile that steady state
                # mid-decode
                for i in range(min(2, self.cfg.max_new_tokens)):
                    cur = _sample(logits, self.cfg, key)
                    logits, cache = self._call_step(
                        cache, cur[:, None], jnp.int32(bucket.seq + i),
                        offsets)
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
        t_start = time.perf_counter()
        n = len(prompt_list)
        maxlen = max(len(p) for p in prompt_list)
        bucket = route(n, maxlen, self.buckets)
        if bucket is not None and not self._uses_offsets \
                and bucket.seq != maxlen:
            # seq-padding shifts tokens through a recurrent state; only
            # batch-pad for models without position-offset support
            bucket = Bucket(bucket.batch, maxlen) \
                if bucket.batch >= n else None
        probe = self._probe_bucket(bucket)

        if bucket is None:
            if obs.enabled():
                obs.counter("serve.cold_bucket").inc()
            batch, lens = batch_requests(prompt_list, self.pad_id)
            b_rows = n
        else:
            dummies = [[self.dummy_token]] * (bucket.batch - n)
            batch, lens = batch_requests(
                list(prompt_list) + dummies, self.pad_id, pad_to=bucket.seq)
            b_rows = bucket.batch
        self.cfg.validate_prompt_len(batch.shape[1])

        key = key if key is not None else jax.random.PRNGKey(0)
        tokens = jnp.asarray(batch, jnp.int32)
        sp = tokens.shape[1]
        offsets = (jnp.asarray(sp - lens, jnp.int32)
                   if self._uses_offsets else None)
        cache = self.model.init_cache(b_rows, self.cfg.max_seq)

        out = [tokens]
        step_lat: List[float] = []
        with planned_scope(self.mesh, self.strategy, self.tuning):
            with obs.span("serve.prefill", batch=b_rows, seq=sp):
                logits, cache = self._call_prefill(cache, tokens, offsets)
            prefill_logits = logits
            if self.cfg.max_new_tokens > 0:
                cur = _sample(logits, self.cfg, key)
                jax.block_until_ready(cur)
                ttft = time.perf_counter() - t_start
                out.append(cur[:, None])
                for t in range(sp, sp + self.cfg.max_new_tokens - 1):
                    key, sub = jax.random.split(key)
                    t0 = time.perf_counter()
                    with obs.span("serve.decode_step", batch=b_rows, pos=t):
                        logits, cache = self._call_step(
                            cache, cur[:, None], jnp.int32(t), offsets)
                        cur = _sample(logits, self.cfg, sub)
                        jax.block_until_ready(cur)
                    step_lat.append(time.perf_counter() - t0)
                    out.append(cur[:, None])
            else:
                jax.block_until_ready(logits)
                ttft = time.perf_counter() - t_start
        full = np.asarray(jnp.concatenate(out, axis=1))
        wall = time.perf_counter() - t_start

        sequences, new_tokens = [], []
        for i in range(n):
            row = full[i]
            seq = row[sp - int(lens[i]):].tolist()   # strip left padding
            sequences.append(seq)
            new_tokens.append(seq[int(lens[i]):])
        if obs.enabled():
            obs.counter("serve.requests").inc(
                n, bucket=bucket.label if bucket else "cold")
            obs.counter("serve.tokens").inc(sum(len(t) for t in new_tokens))
            obs.histogram("serve.ttft_us").observe(ttft * 1e6)
            h = obs.histogram("serve.decode_token_us")
            for dt in step_lat:
                h.observe(dt * 1e6)
        return ServeResult(sequences, new_tokens,
                           bucket.label if bucket else None,
                           ttft, np.asarray(step_lat), wall, probe,
                           prefill_logits[:n])

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
        if missing and obs.enabled():
            obs.counter("serve.plan_repin").inc(len(missing))
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

    def _call_prefill(self, cache, tokens, offsets):
        if offsets is not None:
            return self._prefill(self.params, cache, tokens, offsets)
        return self._prefill(self.params, cache, tokens)

    def _call_step(self, cache, cur, pos, offsets):
        if offsets is not None:
            return self._step(self.params, cache, cur, pos, offsets)
        return self._step(self.params, cache, cur, pos)

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
