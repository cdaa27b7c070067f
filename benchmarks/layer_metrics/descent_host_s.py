"""Seconds of a fit that are coordinate descent's own host work: the self
time (a span less what its children cover) of the estimator's and the
descent's framing spans, which is what is left of a fit once preparation,
tables, coordinate steps and validation are taken out. Per fit."""
from benchmarks.layer_metrics import _spans

SPANS = ("estimator.fit", "estimator.build_coordinates", "descent.run",
         "descent.sweep")


def read(state: dict):
    def host(tree: list) -> float:
        own = _spans.self_seconds(tree)
        return sum(own.get(name, 0.0) for name in SPANS)

    return _spans.per_fit(state, host)
