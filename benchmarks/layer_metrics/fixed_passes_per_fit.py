"""Data passes (one matvec or one rmatvec over all entries) the fixed-effect
solves of a fit make: the solver's own on-device counter, per fit."""
from benchmarks.layer_metrics import _tracker


def read(state: dict):
    s = _tracker.steps(state, "fixed")
    return sum(x["data_passes"] for x in s) / len(state["trackers"]) if s else None
