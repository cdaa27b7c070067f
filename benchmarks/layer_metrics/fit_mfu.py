"""The whole window's share of the chip's peak: the least time the chip
could take for everything the window's fits had to do (fixed-effect and
random-effect passes; the larger of FLOPs over peak FLOP/s and bytes over
peak bytes/s, and the bytes bind) over the window's seconds."""
from benchmarks import work
from benchmarks.layer_metrics import _tracker


def read(state: dict):
    total = work.add(_tracker.fixed_work(state), _tracker.random_work(state))
    if total["bytes"] <= 0:
        return None
    least, _ = work.least_seconds(total, state["peak"])
    return 100.0 * least / state["window_s"]
