"""Seconds of a fit's validation (``descent.validate`` and
``estimator.evaluate``: ``validate_s``) outside the ``device.wait`` below
them: the host around the scorers' and the evaluators' reads. An upper
bound of the idle device under validation."""
from benchmarks.layer_metrics import _spans, _waits

SPANS = ("descent.validate", "estimator.evaluate")


def read(state: dict):
    return _waits.per_fit(state, lambda tree: _waits.host_seconds(
        tree, [s for s in tree if s[_spans.NAME] in SPANS]))
