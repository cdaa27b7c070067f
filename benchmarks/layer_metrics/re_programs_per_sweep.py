"""Bucket programs a sweep runs: the distinct ``(re_type, solver,
padded_rows, local_dim, entities)`` among the ``optim.re_bucket`` spans
under one ``descent.sweep``, over every per-entity coordinate of the sweep;
the mean over the sweeps of the window's fits. Each is a compiled program
of its own, loaded or compiled by a process's first fit and dispatched
once a step. A program from before PR 36 names no ``re_type`` on its
bucket spans, so which coordinate a bucket was of cannot be read:
``None``."""
from benchmarks.layer_metrics import _re_buckets, _spans

KEYS = ("re_type", "solver", "padded_rows", "local_dim", "entities")


def read(state: dict):
    kept = _spans.trees(state)
    if not kept:
        return None
    by_sweep: dict = {}
    for n, tree in enumerate(kept):
        parent = {s[_spans.SPAN_ID]: s[_spans.PARENT_ID] for s in tree}
        for s in tree:
            args = s[_spans.ARGS]
            if (s[_spans.NAME] == _re_buckets.BUCKET
                    and all(k in args for k in KEYS)):
                sweep = (n, parent.get(s[_spans.PARENT_ID]))
                by_sweep.setdefault(sweep, set()).add(
                    tuple(args[k] for k in KEYS))
    if not by_sweep:
        return None
    return sum(len(v) for v in by_sweep.values()) / len(by_sweep)
