"""Shared arithmetic of the readers that tell a fit's two per-entity
coordinates apart.

Since PR 36 an ``optim.re_bucket`` span carries ``re_type``, the entity
column of the dataset its bucket belongs to (``docs/observability.md``), so
a fit's kept tree says which coordinate a bucket, and the ``descent.step``
above it, is of. The configuration names the item's column
(``item_entity``). A program from before carries no ``re_type``, a
configuration with one kind of entity names no item: the readers then
return ``None``.
"""
from benchmarks.layer_metrics import _re_buckets, _spans


def buckets(tree: list, state: dict) -> list:
    """One fit's bucket spans of the item's coordinate."""
    item = state["config"].get("item_entity")
    if item is None:
        return []
    return [s for s in tree if s[_spans.NAME] == _re_buckets.BUCKET
            and s[_spans.ARGS].get("re_type") == item]
