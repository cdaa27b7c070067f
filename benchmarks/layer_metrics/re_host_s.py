"""Seconds of a fit's per-entity steps outside their ``device.wait``: the
host preparing each bucket's inputs, dispatching its program, scoring. An
upper bound of the idle device under those steps; ``re_step_s`` less this
is the wait, during which the chip runs the gathers, the bucket programs
and the scatter (``re_device_s`` counts the bucket programs alone)."""
from benchmarks.layer_metrics import _waits


def read(state: dict):
    return _waits.per_fit(state, lambda tree: _waits.host_seconds(
        tree, _waits.steps(tree, state, "random")))
