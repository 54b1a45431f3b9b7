"""Span recorder: hierarchical, context-propagated, zero-dependency.

The process-global :class:`Recorder` collects three event kinds:

  spans        -- ``with span("plan.build", m=..., n=...):`` blocks; nesting
                  is tracked per thread (a thread-local stack), and every
                  span inherits the *tags* of its ancestors so a collective
                  recorded three layers under ``plan.execute`` still knows
                  which strategy it belongs to.
  collectives  -- one :class:`CollectiveEvent` per data-movement call routed
                  through the ``repro.dist._collectives`` seam, keyed exactly
                  like ``repro.verify.trace.CollectiveRecord`` (kind, group,
                  shard words, canonical perm) so the obs multiset is
                  bitwise-comparable to the conformance interceptor's.
  instants     -- point annotations (cache hits, ranking decisions).

Spans also reach the profiler's trace: while a ``jax.profiler`` session
records, every span opens a ``jax.profiler.TraceAnnotation`` under its own
name, with its args as the event's stats, whether or not the recorder is
on.  A host span then sits on the same clock as the device's operations,
so a trace can say what the host program was doing while a chip idled.

Disabled mode (the default) with no profiler session recording is a no-op
fast path: ``span()`` returns a shared singleton context manager that
allocates nothing, and every instrumentation site guards on ``enabled()``
(one module-global read) before touching the recorder.  ``observe()`` is
the scoped enable used by tests, drift checks, and ``benchmarks/run.py``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

Perm = Tuple[Tuple[int, int], ...]

_ENABLED = False
# jax.profiler.TraceAnnotation, imported by the first span so that
# importing this module imports no jax
_ANNOTATION = None


def enabled() -> bool:
    """True when the observability layer is recording (module-global flag;
    the one check every instrumentation site pays when tracing is off)."""
    return _ENABLED


def enable() -> None:
    """Turn span/collective recording on (process-global)."""
    global _ENABLED
    _ENABLED = True


def disable() -> None:
    """Turn recording off; already-captured events stay in the recorder."""
    global _ENABLED
    _ENABLED = False


def _now_us() -> float:
    return time.perf_counter_ns() / 1e3


def canonical_perm(perm) -> Perm:
    """Sorted non-identity (src, dst) pairs -- the same comparable form
    ``repro.verify.trace.canonical_perm`` uses (duplicated here so the
    dist seam never imports the verify package)."""
    return tuple(sorted(
        (int(s), int(d)) for s, d in perm if int(s) != int(d)))


@dataclasses.dataclass
class SpanRecord:
    """One finished span: a Perfetto complete ("X") event."""

    name: str
    ts_us: float
    dur_us: float
    tid: int
    depth: int
    args: Dict[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass(frozen=True)
class CollectiveEvent:
    """One data-movement collective seen at the dist seam.

    ``key`` matches ``repro.verify.trace.CollectiveRecord.key`` exactly, so
    ``Counter(ev.key for ev in recorder.collectives)`` is directly
    comparable to the conformance interceptor's multiset.
    """

    kind: str                     # "ppermute" | "all_gather" | "psum"
    group: int
    shard_words: int
    perm: Optional[Perm] = None   # canonical, ppermute only
    strategy: str = ""            # ambient span tag at record time
    comm: str = "exposed"         # "hidden" when issued as a prefetch
    ts_us: float = 0.0
    tid: int = 0

    @property
    def key(self) -> Tuple:
        return (self.kind, self.group, self.shard_words, self.perm)


class Recorder:
    """Thread-safe process-global sink for spans/collectives/instants."""

    def __init__(self):
        self._lock = threading.Lock()
        self.spans: List[SpanRecord] = []
        self.collectives: List[CollectiveEvent] = []
        self.instants: List[Tuple[str, float, int, Dict[str, Any]]] = []

    def add_span(self, rec: SpanRecord) -> None:
        with self._lock:
            self.spans.append(rec)

    def add_collective(self, ev: CollectiveEvent) -> None:
        with self._lock:
            self.collectives.append(ev)

    def add_instant(self, name: str, **args) -> None:
        with self._lock:
            self.instants.append(
                (name, _now_us(), threading.get_ident(), args))

    def clear(self) -> None:
        with self._lock:
            self.spans.clear()
            self.collectives.clear()
            self.instants.clear()

    def span_counts(self) -> Dict[str, int]:
        with self._lock:
            out: Dict[str, int] = {}
            for s in self.spans:
                out[s.name] = out.get(s.name, 0) + 1
            return out


_RECORDER = Recorder()
_TLS = threading.local()


def get_recorder() -> Recorder:
    """The process-global recorder (one per process, like the metrics
    registry -- exporters read it, ``reset()`` clears it)."""
    return _RECORDER


def reset() -> None:
    """Clear all recorded spans/collectives/instants (counters live in
    ``repro.obs.metrics`` and have their own reset)."""
    _RECORDER.clear()


def _stack() -> list:
    st = getattr(_TLS, "stack", None)
    if st is None:
        st = _TLS.stack = []
    return st


def current_tags() -> Dict[str, Any]:
    """Merged args of the active span stack on this thread (innermost
    wins) -- how the collective seam learns the executing strategy."""
    tags: Dict[str, Any] = {}
    for _, _, args in _stack():
        tags.update(args)
    return tags


class _Span:
    """Active span handle; re-entrant per ``with`` (one handle per enter).
    ``annotation`` is the profiler annotation opened beside the record
    while a profiler session records, else None."""

    __slots__ = ("name", "args", "annotation")

    def __init__(self, name: str, args: Dict[str, Any], annotation=None):
        self.name = name
        self.args = args
        self.annotation = annotation

    def __enter__(self):
        _stack().append((self.name, _now_us(), self.args))
        if self.annotation is not None:
            self.annotation.__enter__()
        return self

    def __exit__(self, *exc):
        if self.annotation is not None:
            self.annotation.__exit__(*exc)
        name, t0, args = _stack().pop()
        _RECORDER.add_span(SpanRecord(
            name=name, ts_us=t0, dur_us=_now_us() - t0,
            tid=threading.get_ident(), depth=len(_stack()), args=args))
        return False

    def set_metadata(self, **args) -> None:
        """Add args known only inside the span (the same call as on a
        ``jax.profiler.TraceAnnotation``)."""
        self.args.update(args)
        if self.annotation is not None:
            self.annotation.set_metadata(**args)


class _NoopSpan:
    """Shared disabled-mode singleton: enter/exit allocate nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **args) -> None:
        pass


NOOP_SPAN = _NoopSpan()


def _annotation():
    global _ANNOTATION
    from jax.profiler import TraceAnnotation

    _ANNOTATION = TraceAnnotation
    return TraceAnnotation


def span(name: str, **args):
    """Context manager recording one hierarchical span.

        with obs.span("plan.build", strategy="cannon") as s:
            ...
            s.set_metadata(plans=3)      # args known only inside

    Args become the span's Perfetto ``args`` and are inherited as ambient
    tags by everything recorded inside (see ``current_tags``).  While a
    profiler session records, the span is also a
    ``jax.profiler.TraceAnnotation`` named ``name`` with ``args`` as its
    stats (give them host ints, strings or bools).  With recording
    disabled and no profiler session this returns a shared no-op
    singleton.
    """
    annotation = _ANNOTATION or _annotation()
    if annotation.is_enabled():
        if not _ENABLED:
            return annotation(name, **args)
        return _Span(name, args, annotation(name, **args))
    if not _ENABLED:
        return NOOP_SPAN
    return _Span(name, args)


def record_collective(kind: str, group: int, shard_words: int,
                      perm=None) -> None:
    """Record one collective at the dist seam (no-op when disabled).
    ``perm`` is canonicalized; the executing strategy and the
    exposed/hidden classification (``comm="hidden"`` inside the
    double-buffered bodies' prefetch spans) are read off the ambient span
    tags."""
    if not _ENABLED:
        return
    tags = current_tags()
    _RECORDER.add_collective(CollectiveEvent(
        kind=kind, group=int(group), shard_words=int(shard_words),
        perm=canonical_perm(perm) if perm is not None else None,
        strategy=str(tags.get("strategy", "")),
        comm=str(tags.get("comm", "exposed")),
        ts_us=_now_us(), tid=threading.get_ident()))


def instant(name: str, **args) -> None:
    """Record a point annotation (no-op when disabled)."""
    if not _ENABLED:
        return
    _RECORDER.add_instant(name, **args)


@contextlib.contextmanager
def observe(fresh: bool = True):
    """Scoped recording: enable, (optionally) reset the recorder, yield it,
    then restore the previous enabled state.  The idiom for tests, the
    drift check, and ``benchmarks/run.py``:

        with obs.observe() as rec:
            execute_plan(plan, a, b)
        counts = collective_multiset(rec)
    """
    global _ENABLED
    prev = _ENABLED
    if fresh:
        _RECORDER.clear()
    _ENABLED = True
    try:
        yield _RECORDER
    finally:
        _ENABLED = prev
