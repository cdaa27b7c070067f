"""Shared arithmetic of the readers that split a layer's seconds into the
host's and the wait for the device.

Since PR 38 every blocking device-to-host read of a fit's thread is a leaf
span ``device.wait`` (argument ``site``) of the fit's kept tree
(``docs/observability.md`` "A fit's span tree"): the step's commit read,
each evaluator's ``float``, the pulls of a per-entity model scored on other
rows, the tracker's read of a step's results. Seconds inside are the host
blocked on the chip, a lower bound of the chip's busy seconds (and the
transfer's own); a layer's seconds outside them are the host's, an upper
bound of the chip's idle ones (what was dispatched before may still run).
A program from before carries no ``device.wait``: every reader here then
returns ``None``, the seconds of a span the parent already had too, so that
a line holds all seven or none.
"""
from benchmarks.layer_metrics import _spans, _tracker

WAIT = "device.wait"
STEP = "descent.step"


def per_fit(state: dict, of_tree):
    """The mean of ``of_tree(tree)`` over the window's fits, or ``None``
    where their trees hold no ``device.wait``."""
    if not any(_spans.count(t, (WAIT,)) for t in _spans.trees(state) or ()):
        return None
    return _spans.per_fit(state, of_tree)


def host_seconds(tree: list, spans: list) -> float:
    """Seconds of ``spans`` (none inside another) less the ``device.wait``
    below them, however deep."""
    parent = {s[_spans.SPAN_ID]: s[_spans.PARENT_ID] for s in tree}
    tops = {s[_spans.SPAN_ID] for s in spans}
    waited = 0.0
    for s in tree:
        if s[_spans.NAME] != WAIT:
            continue
        at = s[_spans.PARENT_ID]
        while at is not None and at not in tops:
            at = parent.get(at)
        if at is not None:
            waited += s[_spans.END] - s[_spans.START]
    return sum(s[_spans.END] - s[_spans.START] for s in spans) - waited


def steps(tree: list, state: dict, kind: str) -> list:
    """One fit's ``descent.step`` spans of the configuration's coordinates
    of ``kind`` (``fixed`` or ``random``), by the span's ``coordinate``."""
    ids = _tracker.coordinate_ids(state, kind)
    return [s for s in tree if s[_spans.NAME] == STEP
            and s[_spans.ARGS].get("coordinate") in ids]
