"""Seconds a fit spends preparing the buckets' inputs, one
``optim.re_inputs`` span a bucket a step: the starting point, the mask's
gather, ``local_batches``' gather of the offsets, all eager dispatches.
Part of ``re_host_s``."""
from benchmarks.layer_metrics import _spans, _waits

SPANS = ("optim.re_inputs",)


def read(state: dict):
    return _waits.per_fit(state, lambda tree: _spans.seconds(tree, SPANS))
