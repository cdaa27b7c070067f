"""Seconds of a fit's fixed-effect steps outside their ``device.wait``:
the host dispatching the solve and the scorer. An upper bound of the idle
device under those steps; ``fixed_step_s`` less this is the wait."""
from benchmarks.layer_metrics import _waits


def read(state: dict):
    return _waits.per_fit(state, lambda tree: _waits.host_seconds(
        tree, _waits.steps(tree, state, "fixed")))
