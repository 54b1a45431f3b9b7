"""The serving readers, which read the program's own spans: exact values
on a hand-made trace, ``None`` on a trace without them (a program that
writes no spans), and span args read back from a trace recorded on the
CPU."""
import pytest

from bench.lib import harness, spans
from bench.lib import trace as tr

SERVE = ["decode_gap_ms.serve", "batch_overhead_ms.serve",
         "prefill_pad_share.serve"]


def _trace(with_spans=True):
    """Window 0-10 s.  One batch 1-5 s: decode 2.5-4.5 s with four steps;
    a second batch runs past the window's end and is not counted.  Chip 0
    runs the prefill 1.2-2.3 s, each step's last 0.2-0.3 s and a copy
    4.6-4.7 s; chip 1 is busy throughout."""
    ops = {0: [("fusion.1 (fusion)", s, e) for s, e in
               [(1.2, 2.3), (2.7, 3.0), (3.2, 3.5), (3.7, 4.0), (4.2, 4.4),
                (4.6, 4.7), (8.5, 9.5)]],
           1: [("fusion.1 (fusion)", 0.0, 10.0)]}
    host = [("python", tr.WINDOW, 0.0, 10.0),
            ("python", "bench.generate", 0.9, 5.1),
            ("python", "bench.generate", 7.9, 11.0)]
    if with_spans:
        host += [("python", n, s, e) for n, s, e in [
            ("serve.generate", 1.0, 5.0), ("serve.prepare", 1.0, 1.5),
            ("serve.prefill", 1.5, 2.0), ("serve.first_token", 2.0, 2.5),
            ("serve.decode", 2.5, 4.5), ("serve.decode_step", 2.6, 3.0),
            ("serve.decode_step", 3.1, 3.5), ("serve.decode_step", 3.6, 4.0),
            ("serve.decode_step", 4.1, 4.4), ("serve.collect", 4.5, 5.0),
            ("serve.generate", 8.0, 11.0), ("serve.decode", 9.0, 10.5),
            ("serve.decode_step", 9.1, 9.6)]]
    return tr.Trace((0.0, 10.0), ops, {0: [], 1: []}, {0: [], 1: []}, host)


def _read(name, trace):
    return harness.metric_reader(name).read(
        harness.Reading(trace, {}, None, len(trace.chips)))


def _prefill_args(monkeypatch, found):
    monkeypatch.setattr(tr, "latest_xplane", lambda trace_dir: trace_dir)
    monkeypatch.setattr(spans, "load", lambda path: found)


def test_decode_gap_on_hand_made_trace():
    # chip 0: 2.0 s of decode span, 1.1 s of it busy -> 0.9 s over 4 steps;
    # chip 1: none
    assert _read("decode_gap_ms.serve", _trace()) == pytest.approx(
        1e3 * (0.9 + 0.0) / 2 / 4)


def test_batch_overhead_on_hand_made_trace():
    # chip 0: 4.0 s of batch, 2.3 s busy -> 1.7 s idle, 0.9 s of it in
    # the decode span; chip 1: none
    assert _read("batch_overhead_ms.serve", _trace()) == pytest.approx(
        1e3 * (0.8 + 0.0) / 2 / 1)


def test_accounted_idle_fits_the_window():
    t = _trace()
    steps = len(spans.inside(spans.within(t, "serve.decode_step"),
                             spans.within(t, "serve.decode")))
    accounted = 1e-3 * (_read("decode_gap_ms.serve", t) * steps
                        + _read("batch_overhead_ms.serve", t))
    assert accounted <= t.window_s - tr.busy_s(t)


def test_prefill_pad_share_from_span_args(monkeypatch):
    _prefill_args(monkeypatch, [
        ("serve.prefill", 1.5, 2.0, {"batch": 8, "seq": 512,
                                     "tokens": 1000}),
        ("serve.decode", 2.5, 4.5, {}),
        ("serve.prefill", 6.0, 6.5, {"batch": 8, "seq": 512,
                                     "tokens": 2072})])
    assert _read("prefill_pad_share.serve", _trace()) == pytest.approx(
        100.0 * (1 - 3072 / 8192))


@pytest.mark.parametrize("name", SERVE)
def test_none_without_program_spans(name, monkeypatch):
    _prefill_args(monkeypatch, [("bench.generate", 0.9, 5.1, {})])
    assert _read(name, _trace(with_spans=False)) is None


@pytest.mark.parametrize("with_spans", [True, False])
def test_args_read_back_from_a_recorded_trace(with_spans, tmp_path,
                                              monkeypatch):
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    def phase(name, **args):
        return TraceAnnotation(name, **args) if with_spans else \
            TraceAnnotation("bench.other")

    with tr.record(str(tmp_path)):
        with TraceAnnotation(tr.WINDOW):
            with phase("serve.prefill", batch=8, seq=512, tokens=1000):
                jnp.ones(4).block_until_ready()
        with phase("serve.prefill", batch=1, seq=1, tokens=1):
            pass                                  # outside the window
    found = spans.load(tr.latest_xplane(str(tmp_path)))
    prefills = [(n, a) for n, _, _, a in found if n == "serve.prefill"]
    assert prefills == ([("serve.prefill",
                          {"batch": 8, "seq": 512, "tokens": 1000})]
                        if with_spans else [])
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))
    share = _read("prefill_pad_share.serve", _trace())
    assert share == (pytest.approx(100.0 * (1 - 1000 / 4096)) if with_spans
                     else None)
