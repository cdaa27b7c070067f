"""The plain reference of a factored random effect: NumPy float64 over the
rows as they are, in blocks of rows on threads (a block's sums are products
of SciPy sparse matrices of its entries and of its rows' entities).

A factored random effect constrains each entity's coefficients to
``w_u = P b_u``: a projection ``P [D, p]`` all entities share and a latent
vector ``b_u [p]`` for each. A row ``n`` holds entries ``x_n`` over the
shard's ``D`` columns and belongs to entity ``u(n)`` (none, ``-1``: the row
scores 0); its *projected row* is ``XP[n] = x_n P`` and its score
``XP[n] . b_{u(n)}``. With the logistic loss ``l(z, y)`` and training
weights ``w_n``, this file states:

* **the latent problems** at a given ``P``: for every entity ``u``,
  ``sum over u's rows of w_n l(XP[n] . b_u + o_n, y_n) + l2/2 |b_u|^2``,
  their gradients ``sum_n w_n (sigmoid(z_n) - y_n) XP[n] + l2 b_u``, and
  the *residual* of a class of entities: the norm of their gradients at
  the returned ``b`` over that at ``b = 0`` (0 at the optimum, 1 for
  vectors that never moved), each size class of entity apart (the power of
  two at or above its rows, as ``entities_ragged.size_classes``);
* **the projection problem** at given ``b``:
  ``sum_n w_n l(XP[n] . b_{u(n)} + o_n, y_n) + l2/2 |P|^2`` over
  ``vec(P)`` (row-major), a GLM whose row ``n`` is ``x_n (x) b_{u(n)}``:
  ``ProjectionProblem`` has ``reference.SparseLogistic``'s interface, so
  ``reference.lbfgs`` runs the stated optimizer on it;
* **the init**: the top-``p`` right singular subspace of the entities x
  columns matrix of first-order residuals, ``R[u, c] = sum over u's rows
  of r_n x_n[c]`` with ``r_n = w_n (y_n - sigmoid(o_n))`` at the step's
  offsets, by ``rounds`` rounds of subspace iteration
  ``Q <- orth(R^T R Q)`` from ``orth(Q0)``, where ``orth`` is the Q of a
  QR whose R has a positive diagonal (the unique one) and ``Q0`` the
  ``[D, p]`` standard normal draws of ``numpy.random.default_rng(seed)``,
  rounded to the data's precision; ``b`` starts at 0;
* **the alternation**: from the init, ``alternations`` times a latent
  step (every ``b_u`` solved at the current ``P``) then a projection step
  (``P`` refitted by ``reference.lbfgs`` for the stated iterations at the
  current ``b``), and a last latent step;
* **the latent-space score** ``XP[n] . b_{u(n)}`` (``scores``).

It imports nothing of the program, and takes from it only the numbers to
be judged. Every product over the rows goes through one sparse matrix, the
rows' entries grouped by entity: ``C_s [entities, dim]`` holds
``s_n x_n`` in the row of ``u(n)``, so that ``C_s P`` is every entity's
``sum_n s_n XP[n]`` and ``C_s^T b`` the projection's ``sum_n s_n x_n (x)
b_{u(n)}``, and no ``[rows, p]`` array is held beside the data but a
block's. The products that hold one whole (``times``, ``transpose_times``,
``by_entity``, ``of_entity``) serve ``LatentProblems.solve`` and the tests,
at a test's size.
"""
from __future__ import annotations

import dataclasses
import functools
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import scipy.sparse as sp

from benchmarks.reference import log1pexp, lbfgs, sigmoid
from benchmarks.references.entities_ragged import size_classes

# Rounds of subspace iteration of the init: the program's stated init
# (four products by the residual matrix and four by its transpose).
INIT_ROUNDS = 4

# Sums over the rows run a block at a time on threads (NumPy's gathers,
# reductions and ufuncs run without Python's lock). A block of
# ``pair_dots`` keeps its two gathered ``[entries, p]`` operands in a
# core's cache.
BLOCK_ROWS = 1 << 16
BLOCK_ENTRIES = 1 << 14
THREADS = min(8, os.cpu_count() or 1)


def in_blocks(fn, n: int, block: int = BLOCK_ROWS) -> list:
    """``fn(slice)`` of every block of ``range(n)``, the blocks on threads,
    the results in block order."""
    with ThreadPoolExecutor(THREADS) as pool:
        return list(pool.map(fn, (slice(lo, min(lo + block, n))
                                  for lo in range(0, n, block))))


@dataclasses.dataclass
class FactoredRows:
    """One split's rows of the factored shard: ``idx``/``val`` ``[n, k]``
    (a column of ``dim`` or more is absent), ``ent`` ``[n]`` (the row's
    entity, ``-1`` for none), labels and training weights."""

    idx: np.ndarray
    val: np.ndarray
    ent: np.ndarray
    y: np.ndarray
    dim: int
    n_entities: int
    l2: float
    weights: np.ndarray = None

    def __post_init__(self):
        self.idx = np.asarray(self.idx, np.int64)
        self.val = np.where(self.idx < self.dim,
                            np.asarray(self.val, np.float64), 0.0)
        self.idx = np.minimum(self.idx, self.dim)
        self.ent = np.asarray(self.ent, np.int64)
        self.y = np.asarray(self.y, np.float64)
        self.weights = (np.ones(len(self.y)) if self.weights is None
                        else np.asarray(self.weights, np.float64))
        self.weights = np.where(self.ent >= 0, self.weights, 0.0)

    @property
    def n_rows(self) -> int:
        return len(self.y)

    @functools.cached_property
    def _entries(self) -> tuple:
        """The entries of the rows of an entity, grouped by entity (rows in
        order within one): each entry's row, column, value and entity, and
        the entities' ``indptr`` into them."""
        k = self.idx.shape[1]
        order = np.argsort(np.where(self.ent >= 0, self.ent, self.n_entities),
                           kind="stable")
        order = order[self.ent[order] >= 0]
        row = np.repeat(order, k)
        col, val = self.idx[order].ravel(), self.val[order].ravel()
        keep = col < self.dim
        row, col, val = row[keep], col[keep], val[keep]
        ent = self.ent[row]
        counts = np.bincount(ent, minlength=self.n_entities)
        return row, col, val, ent, np.concatenate([[0], np.cumsum(counts)])

    def _c(self, scale) -> sp.csr_matrix:
        """``C_s [entities, dim]`` (module doc) for ``scale`` ``[n]``."""
        row, col, val, _, indptr = self._entries
        return sp.csr_matrix((val * np.asarray(scale, np.float64)[row], col,
                              indptr), shape=(self.n_entities, self.dim))

    def entity_times(self, scale, table: np.ndarray) -> np.ndarray:
        """``[entities, p]``: ``sum over u's rows of scale[n] x_n table``."""
        return self._c(scale) @ np.asarray(table, np.float64)

    def entity_transpose_times(self, scale, b: np.ndarray) -> np.ndarray:
        """``[dim, p]``: ``sum_n scale[n] x_n (x) b_{u(n)}``."""
        return self._c(scale).T @ np.asarray(b, np.float64)

    def pair_dots(self, table: np.ndarray, b: np.ndarray) -> np.ndarray:
        """``[n]``: every row's ``x_n table . b_{u(n)}`` (0 for a row of no
        entity), entry by entry in blocks on threads, summed by row."""
        row, col, val, ent, _ = self._entries
        table = np.asarray(table, np.float64)
        b = np.asarray(b, np.float64)
        dots = np.empty(len(row))

        def one(at):
            dots[at] = val[at] * np.einsum("np,np->n", table[col[at]],
                                           b[ent[at]])
        in_blocks(one, len(row), BLOCK_ENTRIES)
        return np.bincount(row, weights=dots, minlength=self.n_rows)

    def times(self, table: np.ndarray) -> np.ndarray:
        """``[n, p]``: every row's ``x_n table``."""
        ext = np.vstack([np.asarray(table, np.float64),
                         np.zeros((1, table.shape[1]))])
        return np.einsum("nk,nkp->np", self.val, ext[self.idx])

    def transpose_times(self, r: np.ndarray) -> np.ndarray:
        """``[dim, p]``: ``sum_n x_n (x) r[n]``."""
        n, k = self.idx.shape
        x = sp.csr_matrix((self.val.ravel(), self.idx.ravel(),
                           np.arange(0, n * k + 1, k)),
                          shape=(n, self.dim + 1))
        return (x.T @ np.asarray(r, np.float64))[:-1]

    def by_entity(self, r: np.ndarray) -> np.ndarray:
        """``[entities, p]``: the rows' ``r[n]`` summed by entity."""
        live = self.ent >= 0
        out = np.zeros((self.n_entities,) + r.shape[1:])
        np.add.at(out, self.ent[live], r[live])
        return out

    def of_entity(self, b: np.ndarray) -> np.ndarray:
        """``[n, p]``: each row's entity's row of ``b``, 0 for none."""
        ext = np.vstack([np.asarray(b, np.float64), np.zeros((1, b.shape[1]))])
        return ext[np.where(self.ent >= 0, self.ent, self.n_entities)]

    def loss(self, z: np.ndarray) -> float:
        """``sum_n w_n l(z_n, y_n)``, summed in block order."""
        return float(sum(in_blocks(lambda b: np.sum(
            self.weights[b] * (log1pexp(z[b]) - self.y[b] * z[b])),
            self.n_rows)))

    def d1(self, z: np.ndarray) -> np.ndarray:
        """``[n]``: ``w_n (sigmoid(z_n) - y_n)``."""
        out = np.empty(self.n_rows)

        def one(b):
            out[b] = self.weights[b] * (sigmoid(z[b]) - self.y[b])
        in_blocks(one, self.n_rows)
        return out

    def rows_per_entity(self) -> np.ndarray:
        return np.bincount(self.ent[self.ent >= 0],
                           minlength=self.n_entities)


def scores(rows: FactoredRows, P: np.ndarray, b: np.ndarray) -> np.ndarray:
    """The latent-space score of every row (0 for a row of no entity)."""
    return rows.pair_dots(P, b)


def orth(y: np.ndarray) -> np.ndarray:
    """The Q of ``y``'s QR with a positive diagonal in R."""
    q, r = np.linalg.qr(y)
    signs = np.sign(np.diag(r))
    return q * np.where(signs == 0, 1.0, signs)


def init_start(dim: int, p: int, seed: int, dtype=np.float32) -> np.ndarray:
    return np.random.default_rng(seed).standard_normal((dim, p)).astype(
        dtype).astype(np.float64)


def subspace_init(rows: FactoredRows, offsets: np.ndarray, p: int,
                  seed: int, rounds: int, dtype=np.float32) -> np.ndarray:
    """``P0 [dim, p]``: the init (module doc); ``R`` is ``C_r``."""
    r = rows.weights * (rows.y - sigmoid(np.asarray(offsets, np.float64)))
    q = orth(init_start(rows.dim, p, seed, dtype))
    for _ in range(rounds):
        q = orth(rows.entity_transpose_times(r, rows.entity_times(r, q)))
    return q


@dataclasses.dataclass
class LatentProblems:
    """Every entity's latent problem at one ``P`` and offsets."""

    rows: FactoredRows
    P: np.ndarray            # [dim, p]
    offsets: np.ndarray

    @staticmethod
    def at(rows: FactoredRows, P: np.ndarray, offsets) -> "LatentProblems":
        return LatentProblems(rows, np.asarray(P, np.float64),
                              np.asarray(offsets, np.float64))

    def margins(self, b: np.ndarray) -> np.ndarray:
        return self.rows.pair_dots(self.P, b) + self.offsets

    def value(self, b: np.ndarray) -> float:
        z, r = self.margins(b), self.rows
        return r.loss(z) + 0.5 * r.l2 * float(np.sum(np.asarray(b) ** 2))

    def gradient(self, b: np.ndarray) -> np.ndarray:
        """``[entities, p]``."""
        r = self.rows
        return (r.entity_times(r.d1(self.margins(b)), self.P)
                + r.l2 * np.asarray(b))

    def residual_by_class(self, b: np.ndarray) -> dict:
        """Each size class's residual at ``b`` (module doc)."""
        r = self.rows
        g = np.linalg.norm(self.gradient(b), axis=1) ** 2
        # at b = 0 every margin is its offset
        g0 = np.linalg.norm(r.entity_times(r.d1(self.offsets), self.P),
                            axis=1) ** 2
        classes = size_classes(self.rows.rows_per_entity())
        return {int(c): float(np.sqrt(g[classes == c].sum()
                                      / max(g0[classes == c].sum(), 1e-300)))
                for c in np.unique(classes)}

    def solve(self, b0: np.ndarray, newton_steps: int = 12) -> np.ndarray:
        """Every entity's optimum by damped Newton from ``b0`` (the rows'
        ``XP`` held whole: for a test's size)."""
        r, b = self.rows, np.asarray(b0, np.float64).copy()
        xp = r.times(self.P)
        p = xp.shape[1]
        for _ in range(newton_steps):
            z = self.margins(b)
            s = sigmoid(z)
            g = self.gradient(b)
            d2 = r.weights * s * (1.0 - s)
            outer = (xp[:, :, None] * xp[:, None, :]
                     * d2[:, None, None]).reshape(len(z), p * p)
            h = r.by_entity(outer).reshape(-1, p, p) + r.l2 * np.eye(p)
            step = np.linalg.solve(h, -g[..., None])[..., 0]
            t, f = 1.0, self.value(b)
            while t > 1e-10 and self.value(b + t * step) > f:
                t *= 0.5
            b = b + t * step
        return b


@dataclasses.dataclass
class ProjectionProblem:
    """The projection problem at given ``b`` (module doc), with
    ``reference.SparseLogistic``'s interface over ``vec(P)``."""

    rows: FactoredRows
    b: np.ndarray            # [entities, p]
    offsets: np.ndarray

    def __post_init__(self):
        self.offsets = np.asarray(self.offsets, np.float64)
        self.b = np.asarray(self.b, np.float64)
        self.p = self.b.shape[1]
        self.dim = self.rows.dim * self.p

    def matvec(self, w: np.ndarray) -> np.ndarray:
        return self.rows.pair_dots(w.reshape(-1, self.p), self.b)

    def margins(self, w: np.ndarray) -> np.ndarray:
        return self.matvec(w) + self.offsets

    def value_from_margins(self, z: np.ndarray, w: np.ndarray) -> float:
        r = self.rows
        return r.loss(z) + 0.5 * r.l2 * float(np.sum(w * w))

    def grad_from_margins(self, z: np.ndarray, w: np.ndarray) -> np.ndarray:
        r = self.rows
        return (r.entity_transpose_times(r.d1(z), self.b).ravel()
                + r.l2 * w)

    def gradient(self, w: np.ndarray) -> np.ndarray:
        return self.grad_from_margins(self.margins(w), w)


def alternation(rows: FactoredRows, offsets: np.ndarray, p: int, seed: int,
                rounds: int, alternations: int, iterations: int,
                dtype=np.float32) -> dict:
    """The whole factored step from zero (module doc): ``P``, ``b`` and
    what each sub-step gave (``init``; ``latent``, a list of ``b``;
    ``projection``, a list of ``reference.lbfgs``' records)."""
    P = subspace_init(rows, offsets, p, seed, rounds, dtype)
    out = {"init": P, "latent": [], "projection": []}
    b = np.zeros((rows.n_entities, p))
    for _ in range(alternations):
        b = LatentProblems.at(rows, P, offsets).solve(b)
        out["latent"].append(b)
        run = lbfgs(ProjectionProblem(rows, b, offsets), iterations,
                    P.ravel())
        out["projection"].append(run)
        P = run["w"].reshape(P.shape)
    b = LatentProblems.at(rows, P, offsets).solve(b)
    out["latent"].append(b)
    out.update(P=P, b=b)
    return out
