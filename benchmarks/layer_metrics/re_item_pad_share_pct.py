"""The share of the per-item coordinate's row slots that is padding:
100 x (1 - rows / row slots) over that coordinate's buckets in the
window's fits (``re_pad_share_pct`` pools both per-entity coordinates)."""
from benchmarks.layer_metrics import _re_item, _spans


def read(state: dict):
    kept = _spans.trees(state)
    if not kept:
        return None
    held = [s[_spans.ARGS] for t in kept for s in _re_item.buckets(t, state)]
    slots = sum(b["row_slots"] for b in held)
    if not slots:
        return None
    return 100.0 * (1.0 - sum(b["rows"] for b in held) / slots)
