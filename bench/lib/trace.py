"""From a profiler trace to the numbers the per-layer metrics read.

A traced run wraps its traced window in a host annotation named
``WINDOW`` and writes the trace with ``jax.profiler``.  ``load`` reads the
newest ``.xplane.pb`` under the trace directory through
``jax.profiler.ProfileData`` and keeps, as plain tuples:

* the traced window, from the host annotation;
* per chip, the device operations (the ``XLA Ops`` line of each
  ``/device:TPU:<n>`` plane), the asynchronous copies and transfers that
  run beside them (``Async XLA Ops``) and the programs run
  (``XLA Modules``).  An operation's event is named by its HLO text,
  operands and all; it is kept as ``<instruction> (<opcode>)``;
* the host events of the thread that recorded the window (the bench's
  annotations and JAX's own dispatch events on it), used to say what the
  host program did while a chip idled.

Every reduction below takes those tuples, so it can be checked on a small
recorded trace without a chip.  Times are in seconds on the trace's clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

import numpy as np

WINDOW = "bench.window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OPS_LINE = "XLA Ops"
_ASYNC_LINE = "Async XLA Ops"
_MODULES_LINE = "XLA Modules"
_HOST_PLANE = "/host:CPU"
# HLO ops that move data between chips; their async halves ("-start",
# "-done") carry these stems in their opcodes
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|collective-permute|reduce-scatter|all-to-all"
    r"|ppermute|send|recv", re.IGNORECASE)

Interval = Tuple[str, float, float]          # (name, start_s, end_s)


@dataclasses.dataclass
class Trace:
    window: Tuple[float, float]
    ops: Dict[int, List[Interval]]            # chip -> device operations
    modules: Dict[int, List[Interval]]        # chip -> programs run
    async_ops: Dict[int, List[Interval]]      # chip -> async copies
    host: List[Tuple[str, str, float, float]]  # (thread, name, start, end)
                                               # on the window's thread

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def chips(self) -> List[int]:
        return sorted(self.ops)


def record(trace_dir: str):
    """Context manager tracing the device and the host's annotations and
    dispatch events into ``trace_dir``.  Python function calls are not
    traced: that would slow the host the window measures."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return jax.profiler.trace(trace_dir, profiler_options=opts)


def latest_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` into a ``Trace`` (see module docstring)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: Dict[int, List[Interval]] = {}
    modules: Dict[int, List[Interval]] = {}
    async_ops: Dict[int, List[Interval]] = {}
    host: List[Tuple[str, str, float, float]] = []
    for plane in data.planes:
        dev = _DEVICE_PLANE.match(plane.name)
        if dev is not None:
            chip = int(dev.group(1))
            for line in plane.lines:
                if line.name == _OPS_LINE:
                    ops[chip] = _events(line, hlo_name)
                elif line.name == _ASYNC_LINE:
                    async_ops[chip] = _events(line, hlo_name)
                elif line.name == _MODULES_LINE:
                    modules[chip] = _events(line)
        elif plane.name == _HOST_PLANE:
            for line in plane.lines:
                host.extend((line.name, n, s, e) for n, s, e in _events(line))
    marks = [(t, s, e) for t, n, s, e in host if n == WINDOW]
    if len(marks) != 1:
        raise ValueError(f"{len(marks)} {WINDOW!r} annotations in {path}")
    thread, *window = marks[0]
    host = [h for h in host if h[0] == thread]
    for chip in ops:
        modules.setdefault(chip, [])
        async_ops.setdefault(chip, [])
    return Trace(tuple(window), ops, modules, async_ops, host)


def _events(line, name=lambda n: n) -> List[Interval]:
    return [(name(ev.name), ev.start_ns * 1e-9,
             (ev.start_ns + ev.duration_ns) * 1e-9) for ev in line.events]


_HLO = re.compile(r"^%?(\S+) = .*? ([a-z][a-z0-9_.-]*)\(")


def hlo_name(text: str) -> str:
    """``%fusion.3 = bf16[8,128]{1,0} fusion(%a, %b), ...`` ->
    ``fusion.3 (fusion)``: the instruction and its opcode, without the
    operands (whose names would mislead a match on the opcode)."""
    m = _HLO.match(text)
    return f"{m.group(1)} ({m.group(2)})" if m else text


# -- interval arithmetic -------------------------------------------------------

def merge(spans: Sequence[Tuple[float, float]],
          window: Tuple[float, float]) -> np.ndarray:
    """Union of ``spans`` clipped to ``window``, as sorted disjoint rows."""
    if not len(spans):
        return np.zeros((0, 2))
    a = np.clip(np.asarray(spans, float), window[0], window[1])
    a = a[a[:, 1] > a[:, 0]]
    if not len(a):
        return np.zeros((0, 2))
    a = a[np.argsort(a[:, 0])]
    ends = np.maximum.accumulate(a[:, 1])
    starts_new = np.r_[True, a[1:, 0] > ends[:-1]]
    group = np.cumsum(starts_new) - 1
    out = np.zeros((group[-1] + 1, 2))
    out[:, 0] = a[starts_new, 0]
    np.maximum.at(out[:, 1], group, a[:, 1])
    return out


def length(u: np.ndarray) -> float:
    return float(np.sum(u[:, 1] - u[:, 0])) if len(u) else 0.0


def subtract(u: np.ndarray, v: np.ndarray) -> float:
    """Length of union ``u`` not covered by union ``v``."""
    if not len(u):
        return 0.0
    if not len(v):
        return length(u)
    covered = 0.0
    for s, e in u:
        lo = np.searchsorted(v[:, 1], s, side="right")
        hi = np.searchsorted(v[:, 0], e, side="left")
        if hi > lo:
            seg = np.clip(v[lo:hi], s, e)
            covered += float(np.sum(seg[:, 1] - seg[:, 0]))
    return length(u) - covered


def gaps(u: np.ndarray, window: Tuple[float, float]) -> np.ndarray:
    """The parts of ``window`` that union ``u`` leaves uncovered."""
    edges = np.r_[window[0], u.ravel(), window[1]].reshape(-1, 2)
    return edges[edges[:, 1] > edges[:, 0]]


# -- reductions ------------------------------------------------------------------

def busy(trace: Trace, chip: int) -> np.ndarray:
    return merge([(s, e) for _, s, e in trace.ops[chip]], trace.window)


def busy_s(trace: Trace) -> float:
    """Seconds in which some operation ran, averaged over the chips."""
    return float(np.mean([length(busy(trace, c)) for c in trace.chips]))


def idle_share(trace: Trace) -> float:
    """1 - busy / window per chip, averaged over the chips."""
    return float(np.mean([1.0 - length(busy(trace, c)) / trace.window_s
                          for c in trace.chips]))


def collective_exposed_share(trace: Trace) -> float:
    """Share of the window in which a collective runs on a chip (its
    synchronous halves or its transfer) and no other operation does,
    averaged over the chips."""
    shares = []
    for c in trace.chips:
        coll = merge([(s, e) for n, s, e in trace.ops[c] + trace.async_ops[c]
                      if COLLECTIVE.search(n)], trace.window)
        comp = merge([(s, e) for n, s, e in trace.ops[c]
                      if not COLLECTIVE.search(n)], trace.window)
        shares.append(subtract(coll, comp) / trace.window_s)
    return float(np.mean(shares))


def has_collectives(trace: Trace) -> bool:
    return any(COLLECTIVE.search(n) for c in trace.chips
               for n, _, _ in trace.ops[c] + trace.async_ops[c])


def op_seconds(trace: Trace, top: int = 10) -> List[Tuple[str, float]]:
    """Device seconds by operation name inside the window, averaged over
    the chips, largest first."""
    total: Dict[str, float] = defaultdict(float)
    lo, hi = trace.window
    for c in trace.chips:
        for n, s, e in trace.ops[c]:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                total[n] += d / len(trace.chips)
    return sorted(total.items(), key=lambda kv: -kv[1])[:top]


def module_durations(trace: Trace, pattern: str) -> List[float]:
    """Durations of the programs whose name matches ``pattern`` and that
    ran wholly inside the window, on every chip."""
    rx = re.compile(pattern)
    lo, hi = trace.window
    return [e - s for c in trace.chips for n, s, e in trace.modules[c]
            if rx.search(n) and s >= lo and e <= hi]


def host_labels(trace: Trace, times: Sequence[float]) -> List[str]:
    """What the host was doing at each of ``times``: the innermost host
    event that covers it (shortest first), other than the window
    annotation."""
    events = [(n, s, e) for _, n, s, e in trace.host if n != WINDOW]
    if not events:
        return ["no host event"] * len(times)
    names = [n for n, _, _ in events]
    start = np.asarray([s for _, s, _ in events])
    end = np.asarray([e for _, _, e in events])
    span = end - start
    out = []
    for t in times:
        cover = np.flatnonzero((start <= t) & (end >= t))
        out.append(names[cover[np.argmin(span[cover])]] if len(cover)
                   else "no host event")
    return out


def idle_gaps(trace: Trace, top: int = 10) -> List[Tuple[str, float]]:
    """Idle seconds of the first chip by what the host was doing at each
    gap's middle, largest first."""
    idle = gaps(busy(trace, trace.chips[0]), trace.window)
    total: Dict[str, float] = defaultdict(float)
    labels = host_labels(trace, (idle[:, 0] + idle[:, 1]) / 2)
    for label, (s, e) in zip(labels, idle):
        total[label] += float(e - s)
    return sorted(total.items(), key=lambda kv: -kv[1])[:top]
