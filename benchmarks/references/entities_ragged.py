"""The plain reference of per-user problems whose users hold unequal
numbers of rows: NumPy float64 over **segments of rows sorted by user**.

``reference.PerUserLogistic`` holds every user's rows as one dense
``[users, rows, features]`` block padded to the longest user: at 138,493
users of whom one holds 9,254 rows that block cannot exist. This file
states the same mathematics on the rows as they are, sorted by user and
never padded: a user's objective is

    sum over the user's rows of logloss(x_i.b_u + o_i, y_i)
        + l2/2 * |b_u masked|^2          (the intercept is not regularized)

its gradient ``X_u^T (sigmoid(z) - y) + l2 * b_u masked``, and its
*residual* the norm of that gradient at the returned coefficients over its
norm at zero (0 at the optimum, 1 for coefficients that never moved), as
``PerUserLogistic`` states them. Sums over a user's rows are
``np.bincount`` over the sorted rows' user (and column), computed a block
of rows at a time so that nothing larger than a block's entries is ever
held beside the data.

It imports nothing of the program (of the benchmark, the logistic
function and its loss as ``reference.py`` states them) and takes from the
program only the coefficients to be judged.

**Size classes.** A pooled residual is a norm over all users together:
thirty users of 8,000 rows are 0.02% of the users, and a class of them
that never moved would hide in it. ``size_classes`` puts a user in the
class of the power of two at or above its row count (20 rows: 32; 9,254:
16,384), by the data alone, and ``residual_by_class`` gives the residual
of each class apart.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from benchmarks.reference import log1pexp, sigmoid

BLOCK_ROWS = 1 << 21


def size_classes(counts: np.ndarray) -> np.ndarray:
    """The size class of each user: the power of two at or above its rows
    (a user without rows is in class 1)."""
    counts = np.maximum(np.asarray(counts, np.int64), 1)
    return 1 << np.ceil(np.log2(counts)).astype(np.int64)


@dataclasses.dataclass
class RaggedUserLogistic:
    """Every user's problem on the user shard: the rows sorted by user
    (``order``: position in the sorted sequence -> row of the split), each
    user's rows one segment of them."""

    order: np.ndarray      # [n] rows of the split, sorted by user (stable)
    user: np.ndarray       # [n] the user of each sorted row
    idx: np.ndarray        # [n, k] columns of each sorted row
    val: np.ndarray        # [n, k] float64 values of each sorted row
    y: np.ndarray          # [n] labels of each sorted row
    counts: np.ndarray     # [U] rows a user
    dim: int
    l2: float
    intercept: Optional[int]

    @staticmethod
    def build(users, ui, uv, y, n_users: int, dim: int, l2: float,
              intercept: Optional[int]) -> "RaggedUserLogistic":
        users = np.asarray(users, np.int64)
        order = np.argsort(users, kind="stable")
        return RaggedUserLogistic(
            order=order, user=users[order], idx=np.asarray(ui)[order],
            val=np.asarray(uv, np.float64)[order],
            y=np.asarray(y, np.float64)[order],
            counts=np.bincount(users, minlength=n_users), dim=dim,
            l2=float(l2), intercept=intercept)

    @property
    def n_users(self) -> int:
        return len(self.counts)

    def _lam(self) -> np.ndarray:
        lam = np.full(self.dim, self.l2)
        if self.intercept is not None:
            lam[self.intercept] = 0.0
        return lam

    def _blocks(self):
        n = len(self.order)
        for lo in range(0, n, BLOCK_ROWS):
            yield slice(lo, min(lo + BLOCK_ROWS, n))

    def _margins(self, w: np.ndarray, offsets: np.ndarray, b: slice):
        x_dot_w = (self.val[b] * w[self.user[b, None], self.idx[b]]).sum(1)
        return x_dot_w + np.asarray(offsets, np.float64)[self.order[b]]

    def value(self, w: np.ndarray, offsets: np.ndarray) -> np.ndarray:
        """``[U]`` per-user objectives at ``w [U, P]`` given the per-row
        ``offsets`` of the split."""
        data = np.zeros(self.n_users)
        for b in self._blocks():
            z = self._margins(w, offsets, b)
            data += np.bincount(self.user[b],
                                weights=log1pexp(z) - self.y[b] * z,
                                minlength=self.n_users)
        return data + 0.5 * (self._lam() * w * w).sum(1)

    def gradient(self, w: np.ndarray, offsets: np.ndarray) -> np.ndarray:
        """``[U, P]`` gradients of the per-user objectives at ``w``."""
        g = np.zeros(self.n_users * self.dim)
        for b in self._blocks():
            r = sigmoid(self._margins(w, offsets, b)) - self.y[b]
            cell = self.user[b, None] * self.dim + self.idx[b]
            g += np.bincount(cell.ravel(),
                             weights=(self.val[b] * r[:, None]).ravel(),
                             minlength=len(g))
        return g.reshape(self.n_users, self.dim) + self._lam() * w

    def residual(self, w: np.ndarray, offsets: np.ndarray) -> float:
        """|gradient at w| over |gradient at 0|, all users together: what
        ``PerUserLogistic.residual`` states."""
        g0 = np.linalg.norm(self.gradient(np.zeros_like(w), offsets))
        return float(np.linalg.norm(self.gradient(w, offsets)) / g0)

    def residual_by_class(self, w: np.ndarray, offsets: np.ndarray) -> dict:
        """The same ratio over the users of each size class apart:
        ``{class: residual}``, for the classes that hold a user."""
        g = self.gradient(w, offsets)
        g0 = self.gradient(np.zeros_like(w), offsets)
        classes = size_classes(self.counts)
        return {int(c): float(np.linalg.norm(g[classes == c])
                              / np.linalg.norm(g0[classes == c]))
                for c in np.unique(classes)}

    def scores(self, w: np.ndarray) -> np.ndarray:
        """Per-row training scores ``[n]`` of the per-user models, in the
        split's own row order."""
        out = np.empty(len(self.order))
        zero = np.zeros(len(self.order))
        for b in self._blocks():
            out[self.order[b]] = self._margins(w, zero, b)
        return out
