"""Shared arithmetic of the readers of a factored random effect's steps.

A factored step (``game/factored_random_effect.py``) is a ``descent.step``
whose coordinate is of kind ``factored``; under it the program keeps spans
``factored.init``, ``factored.latent`` (its ``factored.project`` child lays
the projected rows into the buckets) and ``factored.projection``, each
ending in the read of its solves' ``iterations`` and ``passes``
(``docs/observability.md``). A program without them keeps no such span:
every reader then returns ``None``.

The projection step's work is counted from the algorithm: one pass (a
product of the rows with ``P`` and the latent vectors, or its transpose)
has to read each row's entries (int32 column and float32 value), its
entity's latent vector (``p`` float32) and read or write one float32 of
the row, and does two operations an entry and latent dimension and two a
latent dimension for the row's dot; the table ``P`` itself is left out,
as it may stay in fast memory. So it is a floor of what any formulation
moves.
"""
from benchmarks.layer_metrics import _spans

LATENT, PROJECTION = "factored.latent", "factored.projection"

# The projection step's program on the trace's XLA Modules line.
PROJECTION_MODULES = ("jit__projection_step",)


def seconds_per_fit(state: dict, name: str):
    """Seconds a fit spends in the spans ``name``, or ``None`` where the
    window's fits kept none."""
    kept = _spans.trees(state)
    if not kept or not any(_spans.count(t, (name,)) for t in kept):
        return None
    return sum(_spans.seconds(t, (name,)) for t in kept) / len(kept)


def projection_passes(state: dict) -> int:
    """The window's projection steps' passes, from their spans."""
    return sum(s[_spans.ARGS].get("passes") or 0
               for t in _spans.trees(state) or ()
               for s in t if s[_spans.NAME] == PROJECTION)


def projection_work(state: dict, passes: int) -> dict:
    sh = state["shapes"]
    rows, k, p = sh["rows"], sh["factored_nnz"], sh["latent_dim"]
    per_row_bytes = k * 8 + p * 4 + 4
    per_row_flops = 2 * k * p + 2 * p
    return {"bytes": passes * rows * per_row_bytes,
            "flops": passes * rows * per_row_flops}
