"""The program's own host spans in a traced window.

``Server.generate`` marks each phase with a host span that the profiler
records on the window's thread (``serve.generate``, ``serve.prepare``,
``serve.prefill``, ``serve.first_token``, ``serve.decode`` with its
``serve.key_split`` and ``serve.decode_step`` spans, ``serve.collect``).
``trace.load`` keeps host events by name and time; ``load`` below reads
the same ``.xplane.pb`` again for the args a span carries, which the
profiler writes as the event's stats.  A program without such spans
gives readers nothing to read, and they return ``None``.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

from bench.lib import trace as tr

Span = Tuple[str, float, float, Dict]          # (name, start_s, end_s, args)


def load(path: str) -> List[Span]:
    """The host events of the window's thread that lie wholly inside the
    window, with their args, in start order."""
    from jax.profiler import ProfileData

    events = [(line.name, ev.name, ev.start_ns * 1e-9,
               (ev.start_ns + ev.duration_ns) * 1e-9, ev.stats)
              for plane in ProfileData.from_file(path).planes
              if plane.name == tr._HOST_PLANE
              for line in plane.lines for ev in line.events]
    marks = [(t, s, e) for t, n, s, e, _ in events if n == tr.WINDOW]
    if len(marks) != 1:
        raise ValueError(f"{len(marks)} {tr.WINDOW!r} annotations in {path}")
    thread, lo, hi = marks[0]
    return sorted(((n, s, e, dict(stats)) for t, n, s, e, stats in events
                   if t == thread and n != tr.WINDOW and lo <= s
                   and e <= hi), key=lambda sp: sp[1])


def within(trace: tr.Trace, name: str) -> List[Tuple[float, float]]:
    """(start, end) of the host events named ``name`` that lie wholly
    inside the window."""
    lo, hi = trace.window
    return [(s, e) for _, n, s, e in trace.host
            if n == name and lo <= s and e <= hi]


def inside(inner, outer) -> list:
    """The intervals of ``inner`` that lie wholly inside one of
    ``outer``."""
    return [(s, e) for s, e in inner
            if any(a <= s and e <= b for a, b in outer)]


def idle_in(trace: tr.Trace, chip: int, intervals) -> float:
    """Seconds of ``intervals`` (a union) in which no operation runs on
    ``chip``."""
    return tr.subtract(tr.merge(intervals, trace.window),
                       tr.busy(trace, chip))
