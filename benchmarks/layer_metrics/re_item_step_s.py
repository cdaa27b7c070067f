"""Seconds a fit spends in the steps of the per-item coordinate: the
``descent.step`` spans (each ends in the device-to-host read, and is the
tracker's ``seconds``) above the bucket spans whose ``re_type`` is the
item's. With the per-user steps' seconds it adds up to ``re_step_s``."""
from benchmarks.layer_metrics import _re_item, _spans


def read(state: dict):
    kept = _spans.trees(state)
    if not kept:
        return None
    seconds = []
    for tree in kept:
        steps = {s[_spans.PARENT_ID] for s in _re_item.buckets(tree, state)}
        seconds += [s[_spans.END] - s[_spans.START] for s in tree
                    if s[_spans.SPAN_ID] in steps]
    return sum(seconds) / len(kept) if seconds else None
