"""Shared arithmetic of the readers of the per-bucket spans.

Since PR 33 a random-effect step's ``optim.re_bucket`` spans carry, beside
``bucket``, ``entities``, ``local_dim`` and ``solver``, the real ``rows``
in the bucket, its ``row_slots`` (entities x padded rows), ``padded_rows``
and ``chunk`` (``docs/observability.md``). A program from before carries
no ``row_slots``, and keeps no tree at all before PR 26: the readers then
return ``None``.
"""
from benchmarks.layer_metrics import _spans

BUCKET = "optim.re_bucket"
ARGUMENT = "row_slots"


def buckets(tree: list) -> list:
    """The arguments of one fit's bucket spans that say what they held."""
    return [s[_spans.ARGS] for s in tree
            if s[_spans.NAME] == BUCKET and ARGUMENT in s[_spans.ARGS]]


def steps(tree: list) -> int:
    """How many of the fit's spans are the parent of such a bucket span:
    its random-effect steps."""
    return len({s[_spans.PARENT_ID] for s in tree
                if s[_spans.NAME] == BUCKET and ARGUMENT in s[_spans.ARGS]})
