"""Seconds a fit spends building and placing the fast-path tables of its
sparse features (the program's ``data.accel_tables`` spans), per fit."""
from benchmarks.layer_metrics import _spans

SPANS = ("data.accel_tables",)


def read(state: dict):
    return _spans.per_fit(state, lambda tree: _spans.seconds(tree, SPANS))
