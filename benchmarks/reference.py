"""The plain reference: GLM / GAME arithmetic in NumPy float64.

Independent of the program: it imports nothing of ``photon_tpu`` and takes
nothing the program made but the numbers to be judged. It states the
configuration's mathematics once, straightforwardly:

* the objective of one coordinate, ``sum_i w_i * logloss(x_i.b + o_i, y_i)
  + lambda/2 * |b_masked|^2`` (no 1/N; the intercept is not regularized),
  its gradient, and the gradient's norm at a point (a *residual*: how far
  a returned solution is from the optimum of the stated problem);
* the stated optimizer (L-BFGS, memory 10, Armijo backtracking from t=1 by
  halves, c1=1e-4) run for a stated number of iterations from stated
  coefficients, with no stopping rule: the configurations cap the
  iterations so that every seed runs to the cap (PERF.md §2);
* per-user objectives on the user shard, the same formula per entity;
* scores and the validation evaluators.

What a configuration names is looked up by that name: ``objective`` by its
``task``, ``optimizer`` by a coordinate's ``optimizer``, ``evaluator_gap`` by
the names under ``evaluators``. A name that is not stated here is looked
for in a file of its own, ``benchmarks/references/<what>_<name>.py`` with
one object ``STATED`` (README.md says what each has to be), so that a later
configuration brings its mathematics without editing this file. A name
stated nowhere is an error.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Optional

import numpy as np


def log1pexp(z: np.ndarray) -> np.ndarray:
    return np.logaddexp(0.0, z)


def sigmoid(z: np.ndarray) -> np.ndarray:
    return 0.5 * (1.0 + np.tanh(0.5 * z))


@dataclasses.dataclass
class SparseLogistic:
    """One fixed-effect problem on padded-row sparse data ``idx``/``val``
    ``[n, k]``: value, gradient and margins."""

    idx: np.ndarray
    val: np.ndarray
    y: np.ndarray
    offsets: np.ndarray
    dim: int
    l2: float
    intercept: Optional[int]
    weights: Optional[np.ndarray] = None

    def __post_init__(self):
        self.val = np.asarray(self.val, np.float64)
        self.offsets = np.asarray(self.offsets, np.float64)
        self.lam = np.full(self.dim, float(self.l2))
        if self.intercept is not None:
            self.lam[self.intercept] = 0.0
        if self.weights is None:
            self.weights = np.ones(len(self.y))

    def matvec(self, w: np.ndarray) -> np.ndarray:
        return (self.val * w[self.idx]).sum(1)

    def rmatvec(self, r: np.ndarray) -> np.ndarray:
        contrib = (self.val * r[:, None]).ravel()
        return np.bincount(self.idx.ravel(), weights=contrib,
                           minlength=self.dim)

    def value_from_margins(self, z: np.ndarray, w: np.ndarray) -> float:
        data = np.sum(self.weights * (log1pexp(z) - self.y * z))
        return float(data + 0.5 * np.sum(self.lam * w * w))

    def grad_from_margins(self, z: np.ndarray, w: np.ndarray) -> np.ndarray:
        dz = self.weights * (sigmoid(z) - self.y)
        return self.rmatvec(dz) + self.lam * w

    def margins(self, w: np.ndarray) -> np.ndarray:
        return self.matvec(w) + self.offsets

    def gradient(self, w: np.ndarray) -> np.ndarray:
        return self.grad_from_margins(self.margins(w), w)


def lbfgs(p: SparseLogistic, n_steps: int, w0: Optional[np.ndarray] = None,
          memory: int = 10, c1: float = 1e-4, shrink: float = 0.5,
          max_probes: int = 25, noise: float = 5e-5) -> dict:
    """``n_steps`` L-BFGS iterations from ``w0`` (zero unless given): the
    objective value and gradient norm at the start and after each
    iteration, and the coefficients after the last. No stopping rule but a
    line search that finds no decrease.

    ``sure`` counts the leading iterations whose line searches rounding
    could not have decided otherwise. A float32 objective carries an error
    of up to 2e-5 of itself on the chip (PERF.md §2), so a probe that
    passes or fails the Armijo test by less than ``noise`` times the
    objective may go the other way there; where that would have changed
    the objective by more than the same margin, a float32 program and
    this path part at once, and what follows says nothing about either."""
    w = np.zeros(p.dim) if w0 is None else np.asarray(w0, np.float64).copy()
    z = p.margins(w)
    f = p.value_from_margins(z, w)
    g = p.grad_from_margins(z, w)
    values, gnorms = [f], [float(np.linalg.norm(g))]
    s_hist, y_hist = [], []
    sure = 0
    for _ in range(n_steps):
        q = g.copy()
        alphas = []
        for s, yv in zip(reversed(s_hist), reversed(y_hist)):
            a = np.dot(s, q) / np.dot(s, yv)
            alphas.append(a)
            q -= a * yv
        if s_hist:
            q *= np.dot(s_hist[-1], y_hist[-1]) / np.dot(y_hist[-1], y_hist[-1])
        for (s, yv), a in zip(zip(s_hist, y_hist), reversed(alphas)):
            b = np.dot(yv, q) / np.dot(s, yv)
            q += (a - b) * s
        d = -q
        if np.dot(d, g) >= 0:
            d = -g
        zp = p.matvec(d)
        dg = float(np.dot(d, g))
        # Armijo from t = 1 by halves; where no probe of ``max_probes``
        # satisfies it, the last one probed is taken if it decreases f.
        slack = noise * abs(f)
        t, ft, before = 1.0, f, None
        for probe in range(max_probes):
            before = (t, ft) if probe else None
            t = shrink ** probe
            ft = p.value_from_margins(z + t * zp, w + t * d)
            if np.isfinite(ft) and ft <= f + c1 * t * dg:
                break
        else:
            if not (np.isfinite(ft) and ft < f):
                break
        if sure == len(values) - 1:              # every search so far was sure
            undecided = False
            if f + c1 * t * dg - ft < slack:     # passed by little: what if not?
                half = p.value_from_margins(z + t * shrink * zp,
                                            w + t * shrink * d)
                undecided = abs(ft - half) > slack
            if before and np.isfinite(before[1]) and (
                    before[1] - (f + c1 * before[0] * dg) < slack):
                undecided = undecided or abs(before[1] - ft) > slack
            if not undecided:
                sure += 1
        w_new = w + t * d
        z = z + t * zp
        g_new = p.grad_from_margins(z, w_new)
        s, yv = w_new - w, g_new - g
        sy = np.dot(s, yv)
        if sy > 1e-10 * np.linalg.norm(s) * np.linalg.norm(yv):
            s_hist.append(s)
            y_hist.append(yv)
            s_hist, y_hist = s_hist[-memory:], y_hist[-memory:]
        w, f, g = w_new, ft, g_new
        values.append(f)
        gnorms.append(float(np.linalg.norm(g)))
    return {"values": values, "grad_norms": gnorms, "w": w, "sure": sure}


OBJECTIVES = {"LOGISTIC_REGRESSION": SparseLogistic}
OPTIMIZERS = {"LBFGS": lbfgs}


def _named(table: dict, name: str, what: str):
    if name in table:
        return table[name]
    module = f"benchmarks.references.{what}_{name.lower()}"
    try:
        return importlib.import_module(module).STATED
    except ModuleNotFoundError:
        raise ValueError(
            f"no plain reference states the {what} {name!r}: "
            f"benchmarks/reference.py states {sorted(table)}, and there is "
            f"no {module.replace('.', '/')}.py") from None


def objective(task: str):
    """The fixed-effect objective class of a configuration's ``task``."""
    return _named(OBJECTIVES, task, "task")


def optimizer(name: str):
    """The optimizer of a coordinate's ``optimizer``."""
    return _named(OPTIMIZERS, name, "optimizer")


@dataclasses.dataclass
class PerUserLogistic:
    """Every user's problem on the user shard, as dense ``[U, R, P]``
    designs: R is the most rows a user has, and a user with fewer has
    slots that are not ``live`` and count for nothing."""

    x: np.ndarray          # [U, R, P]
    y: np.ndarray          # [U, R]
    rows: np.ndarray       # [U, R] global row of each slot
    live: np.ndarray       # [U, R] whether the slot holds a row
    l2: float
    intercept: Optional[int]

    @staticmethod
    def build(users, ui, uv, y, n_users: int, dim: int, l2: float,
              intercept: Optional[int]) -> "PerUserLogistic":
        order = np.argsort(users, kind="stable")
        counts = np.bincount(users, minlength=n_users)
        start = np.r_[0, np.cumsum(counts)[:-1]]
        slot = np.arange(len(users)) - np.repeat(start, counts)
        rows = np.zeros((n_users, int(counts.max())), np.int64)
        live = np.zeros(rows.shape, bool)
        rows[users[order], slot] = order
        live[users[order], slot] = True
        x = np.zeros(rows.shape + (dim,))
        np.put_along_axis(x, ui[rows], np.asarray(uv, np.float64)[rows], axis=2)
        x *= live[..., None]
        return PerUserLogistic(x=x, y=np.asarray(y, np.float64)[rows] * live,
                               rows=rows, live=live, l2=l2,
                               intercept=intercept)

    def gradient(self, w: np.ndarray, offsets: np.ndarray) -> np.ndarray:
        """``[U, P]`` gradients of the per-user objectives at ``w [U, P]``
        given the global per-row ``offsets``."""
        z = np.einsum("urp,up->ur", self.x, w) + offsets[self.rows]
        g = np.einsum("urp,ur->up", self.x, (sigmoid(z) - self.y) * self.live)
        lam = np.full(self.x.shape[2], float(self.l2))
        if self.intercept is not None:
            lam[self.intercept] = 0.0
        return g + lam * w

    def residual(self, w: np.ndarray, offsets: np.ndarray) -> float:
        """|gradient at w| over |gradient at 0|, all users together: 0 at
        every user's optimum, 1 for coefficients that never moved."""
        g0 = np.linalg.norm(self.gradient(np.zeros_like(w), offsets))
        return float(np.linalg.norm(self.gradient(w, offsets)) / g0)

    def scores(self, w: np.ndarray) -> np.ndarray:
        """Per-row training scores ``[n]`` of the per-user models."""
        out = np.zeros(int(self.live.sum()))
        out[self.rows[self.live]] = np.einsum(
            "urp,up->ur", self.x, w)[self.live]
        return out


def sparse_scores(idx, val, w) -> np.ndarray:
    return (np.asarray(val, np.float64) * np.asarray(w, np.float64)[idx]).sum(1)


def user_scores(users, ui, uv, w_users) -> np.ndarray:
    """Scores of per-user coefficients ``[U, P]`` on rows of any split; a
    user the model never saw (negative id) scores 0."""
    known = users >= 0
    u = np.where(known, users, 0)
    return known * (np.asarray(uv, np.float64) * w_users[u[:, None], ui]).sum(1)


def mean_logistic_loss(scores, y) -> float:
    return float(np.mean(log1pexp(scores) - y * scores))


def auc(scores, y) -> float:
    """ROC AUC with average ranks for ties."""
    order = np.argsort(scores, kind="stable")
    s = scores[order]
    start = np.flatnonzero(np.r_[True, s[1:] != s[:-1]])
    end = np.r_[start[1:], len(s)]
    ranks = np.repeat(0.5 * (start + end - 1) + 1.0, end - start)
    pos = y[order] > 0.5
    n_pos, n_neg = pos.sum(), (~pos).sum()
    return float((ranks[pos].sum() - n_pos * (n_pos + 1) / 2.0)
                 / (n_pos * n_neg))


# The validation evaluators a configuration may name, each with how its gap
# to the program's reading is measured: AUC lies in [0, 1] and is compared
# absolutely, a loss relatively.
EVALUATORS = {"AUC": (auc, "absolute"),
              "LOGISTIC_LOSS": (mean_logistic_loss, "relative")}


def evaluator_gap(name: str, reported: float, scores, y) -> float:
    """The gap between the program's ``reported`` value of evaluator
    ``name`` and the reference's on ``scores`` and labels ``y``."""
    fn, how = _named(EVALUATORS, name, "evaluator")
    want = fn(scores, y)
    gap = abs(float(reported) - want)
    return gap / abs(want) if how == "relative" else gap
