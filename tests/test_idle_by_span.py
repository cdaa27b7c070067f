"""``scripts/idle_by_span.py``'s arithmetic on a profile built by hand:
the device's idle gaps split over the innermost program span open, and the
guard's logged transfers put down to the span around them."""
import importlib.util
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MS = 1e6  # ns


@pytest.fixture(scope="module")
def script():
    spec = importlib.util.spec_from_file_location(
        "idle_by_span", os.path.join(ROOT, "scripts", "idle_by_span.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


NAMES = {"estimator.fit", "descent.step", "optim.fixed_solve", "device.wait",
         "descent.validate", "validate.evaluate"}


def hand_profile():
    """One fit of 100 ms in a window of 110: a step (10 to 60) whose solve
    dispatches until 20 and whose wait lasts from 25 to 60, validation (60
    to 90) with one evaluator (70 to 90) that waits from 80. The device
    runs 22 to 58 but for 10 us, and 82 to 88."""
    ops = [("fusion.1", 22 * MS, 18 * MS), ("fusion.2", 40.01 * MS, 17.99 * MS),
           ("fusion.3", 82 * MS, 6 * MS)]
    host = [("bench.window", 0.0, 110 * MS),
            ("estimator.fit", 0.0, 100 * MS),
            ("descent.step", 10 * MS, 50 * MS),
            ("optim.fixed_solve", 10 * MS, 10 * MS),
            ("device.wait:step", 25 * MS, 35 * MS),
            ("descent.validate", 60 * MS, 30 * MS),
            ("validate.evaluate:AUC", 70 * MS, 20 * MS),
            ("device.wait:evaluator", 80 * MS, 10 * MS),
            ("np.asarray(jax.Array)", 80 * MS, 10 * MS)]   # not the program's
    return {"/device:TPU:0": {"XLA Ops": ops},
            "/host:CPU": {"main": host}}


def test_innermost_segments_partition_the_spans(script):
    segments = script.innermost_segments(hand_profile()["/host:CPU"]["main"][1:8])
    assert [(a / MS, b / MS, n) for a, b, n in segments] == [
        (0, 10, "estimator.fit"), (10, 20, "optim.fixed_solve"),
        (20, 25, "descent.step"), (25, 60, "device.wait:step"),
        (60, 70, "descent.validate"), (70, 80, "validate.evaluate:AUC"),
        (80, 90, "device.wait:evaluator"), (90, 100, "estimator.fit")]


def test_idle_gaps_fall_to_the_innermost_span_open(script):
    out = script.attribute(hand_profile(), NAMES)
    assert out["window_s"] == pytest.approx(0.110)
    assert out["busy_s"] == pytest.approx(0.04199)
    by = out["by_span"]
    idle = {k: v["idle_s"] for k, v in by.items() if v["idle_s"]}
    assert idle == pytest.approx({
        "estimator.fit": 0.010 + 0.010,        # before the step, after validation
        "optim.fixed_solve": 0.010, "descent.step": 0.002,
        "device.wait:step": 0.00001 + 0.002,
        "descent.validate": 0.010, "validate.evaluate:AUC": 0.010,
        "device.wait:evaluator": 0.002 + 0.002,
        script.OUTSIDE: 0.010})
    assert sum(idle.values()) == pytest.approx(0.110 - out["busy_s"])
    # the 10 us between two operations of a running program
    assert {k: v["short_s"] for k, v in by.items() if v["short_s"]} == (
        pytest.approx({"device.wait:step": 0.00001}))
    assert by["device.wait:step"]["host_s"] == pytest.approx(0.035)
    assert sum(v["host_s"] for v in by.values()) == pytest.approx(0.110)


def test_a_profile_without_a_device_plane_is_refused(script):
    with pytest.raises(ValueError, match="device"):
        script.attribute({"/host:CPU": hand_profile()["/host:CPU"]}, NAMES)


def test_logged_transfers_fall_to_the_span_around_them(script):
    guard = "W0000 transfer_guard: device-to-host transfer: aval=f32[1]"
    lines = ["@@ > estimator.fit", "@@ > descent.step",
             "@@ > device.wait:step", guard, "@@ < device.wait:step",
             guard, "@@ < descent.step", "something else on stderr",
             "@@ > device.wait:evaluator", guard, guard,
             "@@ < device.wait:evaluator", "@@ < estimator.fit", guard]
    inside, outside = script.logged_reads(lines)
    assert inside == {"device.wait:step": 1, "device.wait:evaluator": 2}
    assert outside == {"descent.step": 1, script.OUTSIDE: 1}
