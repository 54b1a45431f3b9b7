"""The trace reduction, on hand-made intervals and on a small trace
recorded on one v5e chip (the first 30 ms of a traced window of
``danube.engine.prefill4k``)."""
import os

import numpy as np
import pytest

from bench.lib import trace as tr

DATA = os.path.join(os.path.dirname(__file__), "data")


def test_merge_clips_and_joins():
    u = tr.merge([(5, 7), (0, 2), (1, 3), (6, 9)], (1, 8))
    np.testing.assert_allclose(u, [[1, 3], [5, 8]])
    assert tr.length(u) == 5


def test_subtract_and_gaps():
    u = tr.merge([(0, 4), (6, 10)], (0, 10))
    v = tr.merge([(1, 2), (3, 7)], (0, 10))
    assert tr.subtract(u, v) == pytest.approx(1 + 1 + 3)
    np.testing.assert_allclose(tr.gaps(v, (0, 10)), [[0, 1], [2, 3], [7, 10]])


def _trace():
    ops = {0: [("fusion.1 (fusion)", 0.0, 0.4),
               ("collective-permute-done.1 (collective-permute-done)",
                0.4, 0.6),
               ("fusion.2 (fusion)", 0.7, 1.0)],
           1: [("fusion.1 (fusion)", 0.0, 1.0)]}
    async_ops = {0: [("collective-permute-start.1 (collective-permute-start)",
                      0.2, 0.5)], 1: []}
    host = [("python", tr.WINDOW, 0.0, 1.0),
            ("python", "bench.wait", 0.55, 0.75),
            ("python", "PjitFunction(step)", 0.62, 0.68)]
    return tr.Trace((0.0, 1.0), ops, {0: [], 1: []}, async_ops, host)


def test_reductions_on_hand_made_trace():
    t = _trace()
    assert tr.busy_s(t) == pytest.approx((0.9 + 1.0) / 2)
    assert tr.idle_share(t) == pytest.approx((0.1 + 0.0) / 2)
    assert tr.has_collectives(t)
    # chip 0: the permute runs 0.2-0.6, fusions cover 0.0-0.4 -> 0.2 bare
    assert tr.collective_exposed_share(t) == pytest.approx((0.2 + 0.0) / 2)
    assert tr.idle_gaps(t) == [("PjitFunction(step)", pytest.approx(0.1))]
    assert tr.op_seconds(t)[0] == ("fusion.1 (fusion)", pytest.approx(0.7))


def test_hlo_names_drop_operands():
    assert tr.hlo_name("%fusion.3 = bf16[8,128]{1,0} fusion(%all-gather.1), "
                       "kind=kLoop") == "fusion.3 (fusion)"
    assert not tr.COLLECTIVE.search(tr.hlo_name(
        "%fusion.3 = bf16[8]{0} fusion(%all-gather-done.1)"))


def test_recorded_one_chip_trace():
    t = tr.load(os.path.join(DATA, "engine_1chip.xplane.pb"))
    assert t.chips == [0]
    assert t.window_s == pytest.approx(0.03)
    assert 0 < tr.busy_s(t) <= t.window_s
    assert 0 <= tr.idle_share(t) < 0.01
    name, seconds = tr.op_seconds(t)[0]
    assert name.endswith("(custom-call)") and 0 < seconds < t.window_s
    assert not tr.has_collectives(t)
    assert sum(s for _, s in tr.idle_gaps(t)) == pytest.approx(
        t.window_s - tr.busy_s(t))


def test_recorded_four_chip_trace():
    """The first 10 ms of a traced window of ``granite20b.engine.2x2`` on
    a 2x2 v5e host: four chips, and collective-permutes beside the
    kernels."""
    t = tr.load(os.path.join(DATA, "granite_2x2.xplane.pb"))
    assert t.chips == [0, 1, 2, 3]
    assert t.window_s == pytest.approx(0.01)
    assert tr.has_collectives(t)
    exposed = tr.collective_exposed_share(t)
    assert 0 < exposed < 1
    assert all(not tr.COLLECTIVE.search(n) for n, _ in tr.op_seconds(t)[:3])
