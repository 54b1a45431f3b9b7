"""Share of the prefill's token slots that hold padding: 100 x (1 - the
real prompt tokens over batch x seq), summed over the ``serve.prefill``
spans of the window, from the args each span carries (``tokens``,
``batch``, ``seq``)."""
from bench.lib import harness, spans
from bench.lib import trace as tr


def read(ctx):
    prefills = [args for name, _, _, args
                in spans.load(tr.latest_xplane(harness.TRACE_DIR))
                if name == "serve.prefill"]
    if not prefills:
        return None
    slots = sum(a["batch"] * a["seq"] for a in prefills)
    return 100.0 * (1.0 - sum(a["tokens"] for a in prefills) / slots)
