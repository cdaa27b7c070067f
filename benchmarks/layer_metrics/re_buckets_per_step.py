"""Buckets a random-effect step solves: the ``optim.re_bucket`` spans of
the window's fits over the steps that hold them. Each bucket is a program
of its own shape, a dispatch and a scatter of scores."""
from benchmarks.layer_metrics import _re_buckets, _spans


def read(state: dict):
    kept = _spans.trees(state)
    if not kept:
        return None
    steps = sum(_re_buckets.steps(t) for t in kept)
    if not steps:
        return None
    return sum(len(_re_buckets.buckets(t)) for t in kept) / steps
