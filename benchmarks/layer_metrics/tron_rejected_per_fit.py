"""Trial steps the trust region refused (``rho <= 1e-4``: a whole CG solve
and a trial evaluation that moved nothing), per fit: the ``rejected``
argument of the fit's ``descent.step`` spans."""
from benchmarks.layer_metrics import _tron


def read(state: dict):
    return _tron.per_fit(state, "rejected")
