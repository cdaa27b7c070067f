"""Seconds a fit spends inside the ``optim.re_bucket`` spans, which end at
dispatch: choosing a bucket's solver and handing its program to the
runtime. Part of ``re_host_s``."""
from benchmarks.layer_metrics import _spans, _waits

SPANS = ("optim.re_bucket",)


def read(state: dict):
    return _waits.per_fit(state, lambda tree: _spans.seconds(tree, SPANS))
