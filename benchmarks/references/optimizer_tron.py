"""The plain reference of the optimizer ``TRON``: trust-region Newton with
a truncated conjugate-gradient inner solve, NumPy float64.

Lin, Weng & Keerthi, *Trust Region Newton Method for Large-Scale Logistic
Regression* (JMLR 9, 2008), as LIBLINEAR implements it and Photon-ML ports
it. It imports nothing of ``photon_tpu``. ``STATED`` is
``(problem, n_steps, w0) -> {"values", "grad_norms", "w", "sure"}`` as
``benchmarks/README.md`` asks of an optimizer: ``n_steps`` outer iterations
from ``w0`` with no stopping rule, the objective and the gradient's norm at
the start and after each iteration, the coefficients after the last.
Beside those four it returns what the tests read: ``cg_steps`` (CG steps of
each outer iteration), ``ended`` (what ended each CG solve: ``residual``,
``boundary`` or ``cap``), ``rejected`` (whether each trial step was
refused), and each iteration's ``rho`` and ``pred``.

One outer iteration, from ``w`` with objective ``f``, gradient ``g`` and
radius ``delta`` (the first radius is the first gradient's norm):

1. Steihaug CG on ``H s = -g`` inside ``|s| <= delta``, from ``s = 0``, with
   Hessian-vector products ``H v = X^T (d2 * X v) + l2 * mask * v``; ``d2``
   is the loss's curvature at the margins of ``w`` (times the row weights)
   and ``mask`` leaves the intercept out. It ends when the residual's norm
   is at most ``0.1 |g|``, after ``max_cg`` steps, or at the boundary.
2. ``pred = -(g.s + s.Hs / 2)``, ``rho = (f - f(w + s)) / pred``.
3. The radius: ``rho < 0.25``: ``0.25 * min(|s|, delta)``; ``rho < 0.75``:
   ``0.5 * delta``; else ``4 |s|`` held within ``[delta, 4 delta]``.
4. The step is taken if ``rho > 1e-4``; a refused step leaves ``w``, ``f``
   and ``g`` as they were, so the iteration repeats the objective.

Departures from the paper, each because the stated optimizer (Photon-ML's
port) makes it:

* The paper gives the new radius an interval (its equation 9: ``[sigma1
  min(|s|, delta), sigma2 delta]``, ``[sigma1 delta, sigma3 delta]``,
  ``[delta, sigma3 delta]``) and LIBLINEAR picks the point in it by
  interpolating the objective along ``s``. Stated here is one fixed point
  of each interval (step 3), with no interpolation.
* LIBLINEAR lowers the first radius to the first step's length (``delta =
  min(delta, |s|)`` in iteration 1). Not stated here.
* The paper's CG has no cap on its steps; Photon-ML's has, 20 by default.
* The boundary is met when ``|s + alpha d| >= delta`` (the paper: ``>``),
  and a direction with ``d.Hd <= 1e-30`` goes to the boundary too (the
  paper's objectives are strictly convex, so it has no such case).
* The paper stops on ``|g| <= eps |g0|``; the harness asks for ``n_steps``
  iterations and no stopping rule.

``sure`` counts the leading outer iterations in which float32 rounding
could have decided none of the iteration's tests otherwise (``PERF.md``
§2): the accept test (``rho`` against 1e-4), the radius branch (``rho``
against 0.25 and 0.75), and in the CG solve the residual test and the
boundary test of every step. A float32 program's objective carries an
error of up to ``f_noise`` of itself, so its ``rho`` lies within
``2 * f_noise * |f| / pred`` of this one; its CG residual and step norms
lie within ``cg_noise`` of these, relatively. Past the first iteration
with a test inside those margins a float32 program and this path may have
parted, and what follows says nothing about either.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from benchmarks import reference

ETA0, ETA1, ETA2 = 1e-4, 0.25, 0.75
SIGMA1, SIGMA2, SIGMA3 = 0.25, 0.5, 4.0
MAX_CG = 20                 # Photon-ML's and the program's default
CG_TOLERANCE = 0.1          # of the gradient's norm
# The margins of ``sure``, from readings on the chip at the cell's own size
# (PERF.md §2). The float32 objective sits a steady 2e-6 above this one, so
# a decrease carries far less: the largest error read in one was 2.3e-6 of
# the objective, and ``rho``'s slack is 2 * F_NOISE * |f| / pred, 2.6 times
# that. The gradient after a step, which is the CG residual the program
# ended on, read within 3.5e-3 of this one's.
F_NOISE = 3e-6
CG_NOISE = 1e-2


def logistic_curvature(z: np.ndarray) -> np.ndarray:
    """Second derivative of ``log(1 + exp(z)) - y z`` in ``z``."""
    s = reference.sigmoid(z)
    return s * (1.0 - s)


def _curvature(p):
    """The loss's curvature in the margin: the logistic one stated here for
    ``reference.SparseLogistic``; a task that ``references/task_*.py``
    brings is asked for its own, ``curvature(z)`` (beside what it has of
    ``SparseLogistic``: ``rmatvec``, ``lam``, ``weights``)."""
    own = getattr(p, "curvature", None)
    if own is not None:
        return own
    if isinstance(p, reference.SparseLogistic):
        return logistic_curvature
    raise ValueError(
        f"optimizer_tron: {type(p).__name__} states no curvature(z), the "
        "second derivative of its loss in the margin")


def steihaug(hvp, g: np.ndarray, delta: float, tol: float, max_cg: int,
             cg_noise: float):
    """``(s, Hs, steps, ended, sure)``: truncated CG on ``H s = -g`` inside
    ``|s| <= delta``. ``sure`` is whether every test it made held by more
    than ``cg_noise``, relatively."""
    s, hs = np.zeros_like(g), np.zeros_like(g)
    r = -g
    d = r.copy()
    rr = float(r @ r)
    steps, sure = 0, True
    while True:
        if steps >= max_cg:
            return s, hs, steps, "cap", sure
        rnorm = np.sqrt(rr)
        sure = sure and abs(rnorm - tol) > cg_noise * tol
        if rnorm <= tol:
            return s, hs, steps, "residual", sure
        hd = hvp(d)
        dhd = float(d @ hd)
        flat = dhd <= 1e-30
        alpha = rr / dhd if not flat else rr
        ahead = float(np.linalg.norm(s + alpha * d))
        sure = sure and abs(ahead - delta) > cg_noise * delta
        out = flat or ahead >= delta
        if out:
            dd, sd, ss = float(d @ d), float(s @ d), float(s @ s)
            disc = np.sqrt(max(sd * sd + dd * (delta * delta - ss), 0.0))
            alpha = (-sd + disc) / max(dd, 1e-30)
        s = s + alpha * d
        hs = hs + alpha * hd
        r = r - alpha * hd
        rr_new = float(r @ r)
        d = r + (rr_new / max(rr, 1e-30)) * d
        rr = rr_new
        steps += 1
        if out:
            return s, hs, steps, "boundary", sure


def tron(p, n_steps: int, w0: Optional[np.ndarray] = None,
         max_cg: int = MAX_CG, f_noise: float = F_NOISE,
         cg_noise: float = CG_NOISE) -> dict:
    curvature = _curvature(p)
    w = np.zeros(p.dim) if w0 is None else np.asarray(w0, np.float64).copy()
    z = p.margins(w)
    f = p.value_from_margins(z, w)
    g = p.grad_from_margins(z, w)
    delta = float(np.linalg.norm(g))
    values, gnorms = [f], [delta]
    cg_steps, ended, rejected, rhos, preds = [], [], [], [], []
    sure = 0
    for it in range(n_steps):
        d2 = p.weights * curvature(z)

        def hvp(v):
            return p.rmatvec(d2 * p.matvec(v)) + p.lam * v

        s, hs, steps, how, cg_sure = steihaug(
            hvp, g, delta, CG_TOLERANCE * gnorms[-1], max_cg, cg_noise)
        pred = -(float(g @ s) + 0.5 * float(s @ hs))
        w_try = w + s
        z_try = p.margins(w_try)
        f_try = p.value_from_margins(z_try, w_try)
        rho = (f - f_try) / (pred if abs(pred) > 1e-30 else 1.0)
        if not np.isfinite(f_try):
            rho = -np.inf                   # takes the shrinking branch
        snorm = float(np.linalg.norm(s))
        if rho < ETA1:
            new_delta = max(SIGMA1 * min(snorm, delta), 1e-12)
        elif rho < ETA2:
            new_delta = SIGMA2 * delta
        else:
            new_delta = min(max(SIGMA3 * snorm, delta), SIGMA3 * delta)
        if sure == it:                      # every iteration so far was sure
            slack = 2.0 * f_noise * abs(f) / max(abs(pred), 1e-30)
            if cg_sure and all(abs(rho - t) > slack
                               for t in (ETA0, ETA1, ETA2)):
                sure += 1
        accept = rho > ETA0
        if accept:
            w, z, f = w_try, z_try, f_try
            g = p.grad_from_margins(z, w)
        delta = new_delta
        values.append(f)
        gnorms.append(float(np.linalg.norm(g)))
        cg_steps.append(steps)
        ended.append(how)
        rejected.append(not accept)
        rhos.append(float(rho))
        preds.append(pred)
    return {"values": values, "grad_norms": gnorms, "w": w, "sure": sure,
            "cg_steps": cg_steps, "ended": ended, "rejected": rejected,
            "rho": rhos, "pred": preds}


STATED = tron
