"""Shared test configuration.

Puts ``src/`` on sys.path (so ``python -m pytest`` works without the
PYTHONPATH export) and, when the real ``hypothesis`` package is not
installed, registers the in-repo deterministic fallback so the property
tests still collect and run (see src/repro/_hypothesis_stub.py; the real
package is the declared dev-dependency and wins when present).
"""
import os
import sys

import pytest

_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

try:
    import hypothesis  # noqa: F401
except ImportError:
    from repro._hypothesis_stub import install

    install()


@pytest.fixture(autouse=True)
def _reset_plan_cache():
    """Isolate the process-global plan cache between tests: entries AND
    hit/miss counters start fresh, so cache-stats assertions (test_plan)
    cannot couple to whichever test planned first."""
    from repro.plan import cache_clear

    cache_clear()
    yield


@pytest.fixture
def xplane_host_events():
    """Reader of the host events a ``jax.profiler.trace(trace_dir)`` wrote:
    ``read(trace_dir)`` -> ``[(name, start_ns, end_ns, args)]`` in start
    order, ``args`` being the event's stats (a span's keyword args)."""
    import glob

    def read(trace_dir):
        from jax.profiler import ProfileData

        (path,) = glob.glob(os.path.join(
            str(trace_dir), "plugins", "profile", "*", "*.xplane.pb"))
        events = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                   dict(ev.stats))
                  for plane in ProfileData.from_file(path).planes
                  if plane.name == "/host:CPU"
                  for line in plane.lines for ev in line.events]
        return sorted(events, key=lambda e: e[1])

    return read
