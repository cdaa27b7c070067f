"""Seconds a fit spends in its factored random-effect coordinate steps
(tracker ``seconds``, which end in a device-to-host read), per fit."""
from benchmarks.layer_metrics import _tracker


def read(state: dict):
    s = _tracker.steps(state, "factored")
    return sum(x["seconds"] for x in s) / len(state["trackers"]) if s else None
