"""Seconds a fit spends in its random-effect coordinate steps, per fit."""
from benchmarks.layer_metrics import _tracker


def read(state: dict):
    s = _tracker.steps(state, "random")
    return sum(x["seconds"] for x in s) / len(state["trackers"]) if s else None
