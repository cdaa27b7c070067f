"""Per-entity solves finished per second of random-effect step time: the
tracker's convergence-reason counts over its seconds."""
from benchmarks.layer_metrics import _tracker


def read(state: dict):
    s = _tracker.steps(state, "random")
    seconds = sum(x["seconds"] for x in s)
    if not s or seconds <= 0:
        return None
    return sum(sum(x["reasons"].values()) for x in s) / seconds
