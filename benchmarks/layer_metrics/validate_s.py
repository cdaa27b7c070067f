"""Seconds a fit spends validating: after every coordinate step the scorer
of the coordinate just trained, the evaluators and the best-model
bookkeeping (``descent.validate``), and the evaluation of the model it
returns (``estimator.evaluate``), per fit."""
from benchmarks.layer_metrics import _spans

SPANS = ("descent.validate", "estimator.evaluate")


def read(state: dict):
    return _spans.per_fit(state, lambda tree: _spans.seconds(tree, SPANS))
