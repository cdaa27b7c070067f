"""The trace reduction on a trace built by hand."""
import pytest

from benchmarks import trace

MS = 1e6  # ns


def hand_trace():
    ops = [
        ("while", 10 * MS, 60 * MS),          # contains the next three
        ("fusion.1", 10 * MS, 20 * MS),
        ("fusion.2", 30 * MS, 10 * MS),
        ("fusion.1", 50 * MS, 20 * MS),
        ("copy", 80 * MS, 10 * MS),
        ("copy", 150 * MS, 10 * MS),          # outside the window
    ]
    modules = [("jit__fit_jitted(123)", 10 * MS, 60 * MS),
               ("jit_score(9)", 80 * MS, 10 * MS)]
    host = [("bench.window", 0.0, 100 * MS),
            ("bench.fit", 0.0, 100 * MS),
            ("bench.build_fast_aux", 1 * MS, 8 * MS),
            ("bench.validate", 71 * MS, 8 * MS),
            ("other", 0.0, 100 * MS)]
    return {"/device:TPU:0": {trace.OPS_LINE: ops, trace.MODULES_LINE: modules},
            trace.HOST_PLANE: {"main": host},
            "/device:TPU:0 SparseCore": {trace.OPS_LINE: [("x", 0.0, 100 * MS)]}}


def test_device_planes_are_the_chips_only():
    assert trace.device_planes(hand_trace()) == ["/device:TPU:0"]


def test_busy_is_the_union_not_the_sum():
    events = [("a", 0.0, 10.0), ("b", 5.0, 10.0), ("c", 30.0, 5.0)]
    assert trace.union_intervals(events) == [(0.0, 15.0), (30.0, 35.0)]
    assert trace.busy_ns(events) == 20.0
    assert trace.gaps(trace.union_intervals(events), 0.0, 40.0) == [
        (15.0, 30.0), (35.0, 40.0)]


def test_clip_cuts_to_the_window():
    assert trace.clip([("a", 0.0, 10.0), ("b", 20.0, 10.0)], 5.0, 22.0) == [
        ("a", 5.0, 5.0), ("b", 20.0, 2.0)]


def test_self_times_leave_the_nested_out():
    s = trace.self_times(hand_trace()["/device:TPU:0"][trace.OPS_LINE][:5])
    assert s["while"] == pytest.approx(0.010)       # 60 - 20 - 10 - 20 ms
    assert s["fusion.1"] == pytest.approx(0.040)
    assert s["copy"] == pytest.approx(0.010)
    assert sum(s.values()) == pytest.approx(0.070)  # the line's busy time


def test_reduce_hand_trace():
    r = trace.reduce(hand_trace())
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx(0.070)
    assert r["module_s"] == pytest.approx(
        {"jit__fit_jitted": 0.060, "jit_score": 0.010})
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(0.040)]
    assert {n for n, _ in r["device_ops"]} == {
        "fusion.1", "fusion.2", "while", "copy"}
    idle = dict(r["idle_gaps"])
    # 0-10 ms: the table build's span covers the gap's middle; 70-80 ms:
    # validation; 90-100 ms: only the fit's span.
    assert idle["bench.build_fast_aux"] == pytest.approx(0.010)
    assert idle["bench.validate"] == pytest.approx(0.010)
    assert idle["bench.fit"] == pytest.approx(0.010)
    assert sum(idle.values()) + r["busy_s"] == pytest.approx(r["window_s"])


def test_short_gaps_are_lumped():
    t = hand_trace()
    t["/device:TPU:0"][trace.OPS_LINE] = [
        ("a", 0.0, 50 * MS - 1000.0), ("b", 50 * MS, 50 * MS)]
    idle = dict(trace.reduce(t)["idle_gaps"])
    assert list(idle) == ["(gaps under 20 us between operations)"]


def test_no_device_plane_is_an_error():
    t = hand_trace()
    del t["/device:TPU:0"]
    with pytest.raises(ValueError):
        trace.reduce(t)
