"""Seconds the warm-up fit spends building its random-effect datasets: the
``data.re_dataset`` spans (one a dataset: each training key, and each key
again for the validation rows) of the tree the kind kept of that fit
(``state["warmup_tree"]``). Reading the shard back, grouping ten million
rows by a key on the host, packing and placing the buckets: the largest
part of a warm ``setup_s``. A program from before PR 36 has no such span,
a kind that keeps no warm-up tree no tree: ``None``."""
from benchmarks.layer_metrics import _spans

SPAN = "data.re_dataset"


def read(state: dict):
    tree = state.get("warmup_tree")
    if not tree or not _spans.count(tree, (SPAN,)):
        return None
    return _spans.seconds(tree, (SPAN,))
