"""Seconds a fit spends in the projection steps of its factored coordinate
(``factored.projection``: ``P`` refitted by L-BFGS over the rows, ending
in the read of its counts), per fit."""
from benchmarks.layer_metrics import _factored


def read(state: dict):
    return _factored.seconds_per_fit(state, _factored.PROJECTION)
