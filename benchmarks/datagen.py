"""Synthetic GLM / GAME data from a seed, as NumPy arrays.

One general generator: every size is a parameter of the configuration's
``data`` object, so a new configuration is a JSON file and no code. The
arithmetic is ``chip_smoke.py``'s (PR 22), without the Avro detour: a true
fixed-effect coefficient vector (a popular head with large coefficients, a
long tail with small ones), real per-user effects on the user shard, labels
drawn from the logistic model. Nothing here imports the program.

Every seed gives arrays of the same shapes *and the same occupancy*: each
feature id occurs equally often (to within one) whatever the seed, in an
order the seed shuffles, and every user's rows between them hold every
user feature. The program sizes its tables by occupancy (the fast sparse
path's column table by entries per 128-column range, the random-effect
buckets by the features a user's rows hold), so iid draws gave every seed
its own program shapes and its own compilation (PERF.md §6, PR 25); with
equal occupancy one compiled program serves every seed.

Layout of one split (all float64 / int64 on the host; the harness narrows
to the configuration's dtype when it hands them to the program):

* ``gi``/``gv`` ``[n, named_nnz + 1]``: fixed-effect shard, the last slot
  the intercept (column ``named_features``, value 1);
* ``ui``/``uv`` ``[n, user_nnz + 1]``: the user shard with its intercept
  (column ``user_features``), when the configuration has users;
* ``users`` ``[n]``: the row's user, ``-1 - k`` for the k-th unseen user;
* ``y`` ``[n]``: 0/1 labels.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np


@dataclasses.dataclass(frozen=True)
class Split:
    gi: np.ndarray
    gv: np.ndarray
    y: np.ndarray
    users: Optional[np.ndarray] = None
    ui: Optional[np.ndarray] = None
    uv: Optional[np.ndarray] = None

    @property
    def n_rows(self) -> int:
        return len(self.y)


@dataclasses.dataclass(frozen=True)
class Dataset:
    train: Split
    validation: Split
    global_dim: int              # named features + intercept
    user_dim: int                # user features + intercept; 0 = no users
    n_users: int


def _balanced(rng, n: int, k: int, d: int) -> np.ndarray:
    """``[n, k]`` ids below ``d``, each id equally often (to within one),
    in an order drawn from ``rng``."""
    ids = np.arange(n * k) % d
    rng.shuffle(ids)
    return ids.reshape(n, k)


def _occurrence(users: np.ndarray) -> np.ndarray:
    """For each row, how many earlier rows hold the same user."""
    order = np.argsort(users, kind="stable")
    sorted_users = users[order]
    first = np.flatnonzero(np.r_[True, sorted_users[1:] != sorted_users[:-1]])
    start = np.repeat(first, np.diff(np.r_[first, len(users)]))
    occ = np.empty(len(users), np.int64)
    occ[order] = np.arange(len(users)) - start
    return occ


def _rows(rng, d: dict, users: Optional[np.ndarray], n: int, wg, wu, bu) -> Split:
    kg, dg = d["named_nnz"], d["named_features"]
    kh = kg // 2
    gi = np.concatenate([
        _balanced(rng, n, kh, d["head_features"]),
        _balanced(rng, n, kg - kh, dg),
    ], axis=1)
    gv = rng.normal(size=(n, kg)) / np.sqrt(kg)
    z = (gv * wg[gi]).sum(1)
    gi = np.concatenate([gi, np.full((n, 1), dg)], axis=1)
    gv = np.concatenate([gv, np.ones((n, 1))], axis=1)
    ui = uv = None
    if users is not None:
        ku, du = d["user_nnz"], d["user_features"]
        # A user's r-th row holds features r*ku .. r*ku + ku - 1 (mod du),
        # turned by a per-user draw: ceil(du / ku) rows hold them all.
        turn = rng.integers(0, du, size=users.max() + 1 - min(users.min(), 0))
        ui = (_occurrence(users)[:, None] * ku + np.arange(ku)
              + turn[users - min(users.min(), 0)][:, None]) % du
        uv = rng.normal(size=(n, ku))
        known = users >= 0
        u = np.where(known, users, 0)
        z = z + known * (bu[u] + (uv * wu[u[:, None], ui]).sum(1))
        ui = np.concatenate([ui, np.full((n, 1), du)], axis=1)
        uv = np.concatenate([uv, np.ones((n, 1))], axis=1)
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-z))).astype(np.float64)
    return Split(gi=gi, gv=gv, y=y, users=users, ui=ui, uv=uv)


def generate(data: dict, seed: int) -> Dataset:
    """The training and validation splits of one configuration's ``data``
    object for ``seed`` (any non-negative whole number)."""
    d = data
    n_users = int(d.get("users", 0))
    truth = np.random.default_rng([seed, 0])
    wg = truth.normal(size=d["named_features"]) * 0.3
    wg[: d["head_features"]] = truth.normal(size=d["head_features"]) * 1.5
    wu = bu = None
    if n_users:
        wu = truth.normal(size=(n_users, d["user_features"]))
        bu = truth.normal(size=n_users) * 2.0

    v = d["validation"]
    if n_users:
        train_users = np.repeat(np.arange(n_users), d["rows_per_user"])
        unseen = -1 - np.repeat(np.arange(v["unseen_users"]), v["unseen_rows"])
        val_users = np.concatenate(
            [np.repeat(np.arange(n_users), v["rows_per_user"]), unseen])
        n_train, n_val = len(train_users), len(val_users)
    else:
        train_users = val_users = None
        n_train, n_val = int(d["rows"]), int(v["rows"])

    rng = np.random.default_rng([seed, 1])
    if train_users is not None:
        rng.shuffle(train_users)
    train = _rows(rng, d, train_users, n_train, wg, wu, bu)
    val = _rows(np.random.default_rng([seed, 2]), d, val_users, n_val,
                wg, wu, bu)
    return Dataset(
        train=train, validation=val,
        global_dim=d["named_features"] + 1,
        user_dim=(d["user_features"] + 1) if n_users else 0,
        n_users=n_users,
    )


def user_keys(users: np.ndarray) -> np.ndarray:
    """Entity ids per row as the program's id-tag column wants them:
    ``user<u>``, and ``stranger<k>`` for the k-th unseen user."""
    return np.array(
        [f"user{u}" if u >= 0 else f"stranger{-1 - u}" for u in users.tolist()],
        dtype=object)


def user_of_key(key) -> int:
    """The user a ``user<u>`` key names (the inverse of ``user_keys``)."""
    return int(str(key)[len("user"):])
