"""Seconds a fit spends in validation's scorers (the ``validate.score``
spans: the coordinate just trained after a step, every coordinate on the
returned model), the pulls of a per-entity model's stacks among them. Part
of ``validate_s``; the rest is the evaluators and the bookkeeping."""
from benchmarks.layer_metrics import _spans, _waits

SPANS = ("validate.score",)


def read(state: dict):
    return _waits.per_fit(state, lambda tree: _spans.seconds(tree, SPANS))
