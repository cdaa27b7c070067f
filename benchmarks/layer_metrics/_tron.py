"""Shared arithmetic of the readers of TRON's own counters.

A coordinate step solved by TRON carries, as arguments of its
``descent.step`` span (set after the step's device-to-host read), what the
solver counted on the device: ``hvp`` (Hessian-vector products), ``cg_steps``
and ``rejected`` (trial steps the trust region refused);
``docs/observability.md`` has them. A step of another optimizer carries
none, and a program from before these counters carries none anywhere: the
readers then return ``None``.
"""
from benchmarks.layer_metrics import _spans

STEP = "descent.step"


def per_fit(state: dict, argument: str):
    """``argument`` summed over the steps of the window's fits that carry
    it, per fit; ``None`` where no step does."""
    kept = _spans.trees(state)
    if not kept:
        return None
    found = [s[_spans.ARGS][argument] for tree in kept for s in tree
             if s[_spans.NAME] == STEP and argument in s[_spans.ARGS]]
    return sum(found) / len(kept) if found else None
