"""Shared arithmetic of the readers that read the fits' own span trees.

Since PR 26 the program measures itself: every ``GameEstimator.fit`` is a
root span ``estimator.fit`` of ``photon_tpu.obs.trace``, the spans entered
under it on its thread name their parent, and the finished tree of each of
the last 256 fits is kept in memory with no collector installed
(``photon_tpu.obs.trace.recent_trees("estimator.fit")``; the span catalog is
in ``docs/observability.md``, the table of spans beside metrics in
``PERF.md`` §3). A tree is a list of tuples ``(name, span_id, parent_id,
start_s, end_s, args)``, the root last. While the profiler records, the
same spans are ``TraceAnnotation``s on the ``/host:CPU`` plane of the device
trace; these readers do not need the trace, they read the tuples.

``trees(state)`` takes the trees of the window's fits and no others: the
last ``len(state["trackers"])`` roots, or as many as the program kept, so
the warm-up fit (which builds datasets and loads programs) is never among
them. A program from before PR 26 keeps no tree: every reader then returns
``None`` and the harness leaves its metric out of the line. A reader keeps
the span names it looks for in its own file.
"""
from __future__ import annotations

ROOT = "estimator.fit"
NAME, SPAN_ID, PARENT_ID, START, END, ARGS = range(6)


def trees(state: dict):
    """The span trees of the window's fits, oldest first, or ``None``."""
    try:
        from photon_tpu.obs.trace import recent_trees
    except ImportError:
        return None
    return recent_trees(ROOT, len(state["trackers"])) or None


def seconds(tree: list, names: tuple) -> float:
    """Seconds inside the spans called one of ``names``."""
    return sum(s[END] - s[START] for s in tree if s[NAME] in names)


def count(tree: list, names: tuple) -> int:
    return sum(1 for s in tree if s[NAME] in names)


def self_seconds(tree: list) -> dict:
    """Seconds per span name, each span counted without what its children
    cover (``choosing-metrics`` §4), so that the names add up to the root's
    duration."""
    children: dict = {}
    for s in tree:
        children.setdefault(s[PARENT_ID], []).append(s)
    out: dict = {}
    for s in tree:
        covered, at = 0.0, s[START]
        for c in sorted(children.get(s[SPAN_ID], ()), key=lambda c: c[START]):
            a, b = max(c[START], at), min(c[END], s[END])
            if b > a:
                covered += b - a
                at = b
        out[s[NAME]] = out.get(s[NAME], 0.0) + (s[END] - s[START]) - covered
    return out


def per_fit(state: dict, of_tree):
    """The mean of ``of_tree(tree)`` over the window's fits, or ``None``
    where the program kept no tree."""
    kept = trees(state)
    if not kept:
        return None
    return sum(of_tree(t) for t in kept) / len(kept)
