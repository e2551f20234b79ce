"""The reduction from a profiler trace to busy time, top ops and gaps."""
import os

import pytest

from chipbench import trace as T

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "jag_launches.xplane.pb")
# host-clock length of the traced window when the fixture was recorded
FIXTURE_WINDOW_S = 0.06245575699999506


def test_reduce_merges_overlaps_and_averages_chips():
    events = {0: [("a", 0, 100), ("b", 50, 100), ("a", 400, 100)],
              1: [("a", 0, 300)]}
    s = T.reduce(events, window_s=1e-6)
    assert s["busy_s"] == pytest.approx((250e-9 + 300e-9) / 2)
    assert s["window_s"] == 1e-6
    assert s["device_ops"][0] == ["a", pytest.approx(500e-9 / 2)]
    assert s["idle_gaps"] == [["after b", pytest.approx(250e-9)]]


def test_reduce_refuses_a_trace_without_device_ops():
    with pytest.raises(RuntimeError):
        T.reduce({}, window_s=1.0)


def test_reduce_a_trace_recorded_on_the_chip():
    """Three JAG launches of 1,024 rows on one TPU v5e, 10 ms apart."""
    events = T.device_events(FIXTURE, chips=1)
    assert set(events) == {0}
    s = T.reduce(events, window_s=FIXTURE_WINDOW_S)
    assert 0 < s["busy_s"] < s["window_s"]
    assert s["device_ops"] and all(t > 0 for _, t in s["device_ops"])
    assert s["idle_gaps"][0][1] > 0.005  # the 10 ms sleeps between launches
