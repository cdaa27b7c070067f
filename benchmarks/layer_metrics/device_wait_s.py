"""Seconds of a fit in which its thread was blocked in a device-to-host
read (the ``device.wait`` spans): the host waiting on the chip. A lower
bound of the chip's busy seconds a fit, but for the transfers' own time."""
from benchmarks.layer_metrics import _spans, _waits


def read(state: dict):
    return _waits.per_fit(
        state, lambda tree: _spans.seconds(tree, (_waits.WAIT,)))
