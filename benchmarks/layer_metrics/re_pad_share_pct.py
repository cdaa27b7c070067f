"""The share of the random-effect buckets' row slots that is padding:
100 x (1 - rows / row slots) over the buckets of the window's fits. A
padded slot is solved like a row and counts for nothing."""
from benchmarks.layer_metrics import _re_buckets, _spans


def read(state: dict):
    kept = _spans.trees(state)
    if not kept:
        return None
    held = [b for t in kept for b in _re_buckets.buckets(t)]
    slots = sum(b["row_slots"] for b in held)
    if not slots:
        return None
    return 100.0 * (1.0 - sum(b["rows"] for b in held) / slots)
