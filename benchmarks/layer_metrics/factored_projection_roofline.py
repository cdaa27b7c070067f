"""The projection steps' share of their roofline: the least time the chip
could take for the passes they made (``_factored.projection_work``: the
larger of bytes over peak bytes/s and operations over peak FLOP/s) over
the device seconds of the projection step's program in the traced
window. ``None`` where the window made no projection pass or the trace
holds no such program; never 0."""
from benchmarks import work
from benchmarks.layer_metrics import _factored


def read(state: dict):
    seconds = sum(state["trace"]["module_s"].get(m, 0.0)
                  for m in _factored.PROJECTION_MODULES)
    passes = _factored.projection_passes(state)
    if seconds <= 0 or passes <= 0:
        return None
    least, _ = work.least_seconds(
        _factored.projection_work(state, passes), state["peak"])
    return 100.0 * least / seconds
