"""repro.obs -- observability: spans, metrics, profiles.

The measured leg of the repo's measured-vs-analytic loop.  ``repro.verify``
(PR 3) proves the lowered programs move exactly the analytic number of
words; this package measures what the machine actually does with them:

  runtime    -- hierarchical span tracing (``span("plan.build")``),
                context-propagated tags, a process-global recorder, spans
                written into the ``jax.profiler`` trace while a profiler
                session records, and a no-op fast path otherwise (the
                default)
  metrics    -- typed counters/histograms (plan-cache hits, per-strategy
                collective counts/bytes, kernel wall-time)
  export     -- Chrome/Perfetto ``trace_event`` JSON + the flat metrics
                JSON ``benchmarks/run.py --report`` consumes;
                ``collective_multiset`` is bitwise-comparable to the
                ``repro.verify`` interceptor's records
  profile    -- versioned :class:`MachineProfile` (fitted α–β per link
                class + measured peak FLOPs); ``build_plan(profile=...)``
                ranks strategies with calibrated seconds while the word
                counts stay analytic
  calibrate  -- ``probe_links(mesh)``: the microbenchmark pass that fits
                a profile (re-exported as ``repro.launch.perf_probe``'s
                library entry point)

Nothing here imports jax at module scope (the first span imports
``jax.profiler``); with the recorder off and no profiler session, a span
costs one module-global check and one profiler-session check.
"""
from . import calibrate, export, metrics, profile, runtime
from .calibrate import probe_links
from .export import (SCHEMA_VERSION, collective_multiset, collective_totals,
                     metrics_snapshot, to_trace_events, write_metrics,
                     write_trace)
from .metrics import (Counter, Histogram, counter, histogram, reset_metrics,
                      snapshot)
from .profile import (PROFILE_SCHEMA, LinkParams, MachineProfile,
                      default_profile, fit_alpha_beta, load_profile,
                      save_profile)
from .runtime import (NOOP_SPAN, CollectiveEvent, Recorder, SpanRecord,
                      current_tags, disable, enable, enabled, get_recorder,
                      instant, observe, record_collective, reset, span)

__all__ = [
    "calibrate", "export", "metrics", "profile", "runtime",
    # runtime
    "enable", "disable", "enabled", "observe", "span", "instant",
    "record_collective", "current_tags", "get_recorder", "reset",
    "Recorder", "SpanRecord", "CollectiveEvent", "NOOP_SPAN",
    # metrics
    "Counter", "Histogram", "counter", "histogram", "reset_metrics",
    "snapshot",
    # export
    "SCHEMA_VERSION", "to_trace_events", "write_trace", "metrics_snapshot",
    "write_metrics", "collective_multiset", "collective_totals",
    # profile + calibration
    "PROFILE_SCHEMA", "LinkParams", "MachineProfile", "default_profile",
    "fit_alpha_beta", "load_profile", "save_profile", "probe_links",
]
