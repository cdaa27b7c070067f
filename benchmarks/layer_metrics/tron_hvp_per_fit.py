"""Hessian-vector products a fit's TRON solves made (each one ``matvec``
and one ``rmatvec`` over all entries): the solver's own on-device counter,
from the ``hvp`` argument of the fit's ``descent.step`` spans, per fit."""
from benchmarks.layer_metrics import _tron


def read(state: dict):
    return _tron.per_fit(state, "hvp")
