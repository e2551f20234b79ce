"""Idle gaps named by the program's spans (chipbench/host_spans.py)."""
import os
import types

import pytest

from chipbench import host_spans as H
from chipbench import trace as T

# chip 0: four ops, three gaps of 890, 900 and 450 ns
EVENTS = {0: [("op_a", 0, 100), ("dynamic_slice.1", 100, 10),
              ("op_b", 1000, 100), ("op_c", 2000, 50), ("op_d", 2500, 10)]}
LINES = [
    [("worker.wait", 0, 2040)],  # a worker's thread: it only waits
    [("engine.write", 100, 950), ("bundler.write", 150, 750)],  # the writer
]


def test_a_work_span_beats_a_wait_span_and_a_bare_gap_keeps_its_op():
    s = H.reduce_with_spans(EVENTS, 1e-6, LINES)
    assert s["idle_gaps"] == [
        ["after op_b: worker.wait 100%", pytest.approx(900e-9)],
        # the writer's self time inside its bundle write is the gap's name
        ["after dynamic_slice.1: bundler.write 84%", pytest.approx(890e-9)],
        ["after op_c", pytest.approx(450e-9)],
    ]
    assert s["idle_gap_s"] == pytest.approx(2240e-9)
    assert s["idle_attributed_s"] == pytest.approx(1790e-9)


def test_without_spans_the_reduction_is_trace_reduce():
    s = H.reduce_with_spans(EVENTS, 1e-6, [])
    base = T.reduce(EVENTS, 1e-6)
    assert {k: s[k] for k in base} == base
    assert s["idle_attributed_s"] == 0.0


def test_self_segments_cut_a_parent_around_its_children():
    segs = H.self_segments([("engine.write", 0, 100),
                            ("bundler.write", 10, 20),
                            ("bundler.write", 40, 20), ("runtime.mark", 80, 5)])
    assert segs == [("engine.write", 0, 10), ("bundler.write", 10, 30),
                    ("engine.write", 30, 40), ("bundler.write", 40, 60),
                    ("engine.write", 60, 80), ("runtime.mark", 80, 85),
                    ("engine.write", 85, 100)]


def test_host_spans_strips_metadata_and_keeps_only_named_spans(monkeypatch):
    ev = types.SimpleNamespace
    plane = ev(name="/host:CPU", lines=[
        ev(events=[ev(name="bundler.write#task=30#", start_ns=5,
                      duration_ns=7),
                   ev(name="$bundler.py:66 write_bundle", start_ns=5,
                      duration_ns=7)]),
        ev(events=[ev(name="unrelated", start_ns=1, duration_ns=1)])])
    device = ev(name="/device:TPU:0", lines=[
        ev(events=[ev(name="bundler.write", start_ns=0, duration_ns=1)])])
    import jax.profiler
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        staticmethod(lambda path: ev(planes=[plane, device])))
    assert H.host_spans("any.xplane.pb") == [[("bundler.write", 5, 7)]]


STUDY_FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "study_spans.xplane.pb")
# host-clock length of the traced window when the fixture was recorded
STUDY_FIXTURE_WINDOW_S = 1.0524586370000009


def test_gaps_of_a_study_recorded_on_the_chip_are_named_by_spans():
    """A JAG study at 12 x 64 x 64 through a WorkerPool of 4 workers
    (bundle 10) on one TPU v5e, traced for 1 s with the Python tracer off:
    four fused launches of 40 rows and the three writes between them."""
    events = T.device_events(STUDY_FIXTURE, chips=1)
    lines = H.host_spans(STUDY_FIXTURE)
    names = {n for line in lines for n, _, _ in line}
    assert names == set(H.STUDY_SPANS)
    s = H.reduce_with_spans(events, STUDY_FIXTURE_WINDOW_S, lines)
    assert s["idle_attributed_s"] > 0.99 * s["idle_gap_s"]
    # the three writer cycles: ~0.3 s each, named by the bundle writes
    for label, seconds in s["idle_gaps"][:3]:
        assert label.startswith("after dynamic_slice.1: bundler.write ")
        assert 0.25 < seconds < 0.4
    assert all(":" in label for label, _ in s["idle_gaps"])
