"""Factored random effects: per-entity models in a learned latent space.

Parity: reference ⟦photon-api/.../algorithm/FactoredRandomEffectCoordinate⟧ +
⟦.../projector/ProjectionMatrix⟧ (SURVEY.md §2.2 Projectors, L5 layer map —
fork-vintage component). Each entity's coefficients are constrained to
``w_e = P · β_e`` with a SHARED projection ``P [D, p]`` and per-entity
latent vectors ``β_e [p]``; training alternates

  1. latent step — fit every entity's ``β_e`` against its rows' projected
     features ``XP[n] = x_n · P`` (small dense per-entity problems), and
  2. projection step — refit ``P`` against all rows with every ``β_e``
     fixed: the margins ``XP[n] · β_{u(n)}`` are linear in ``P``, so this
     is one GLM over ``D·p`` parameters.

TPU-first: everything runs in the latent space, over the rows laid flat in
the global row order (``flat_rows``), and never indexes an entity's local
subspace (as wide as the items it rated: 16,384 padded columns at
MovieLens-20M's heaviest user, where the bucketed gather compiles for
minutes). The flat products are a gather from a ``[D+1, p]`` table by the
rows' columns (``XP``, and ``β`` by the rows' entities) and its transpose, a
scatter-add into ``[D+1, p]`` (the projection gradient): no operand is
batched per entity. The latent step hands each bucket to the per-entity
solver of the plain random effect (``train_random_effects``, its routing,
the Newton lanes) as a dense bucket of ``p`` local columns; the projection
step runs the shared L-BFGS on maintained margins. Where the shard holds
one entry a row (an item id), the projection step runs over the rows
sorted by item instead (``sorted_rows``, built once a dataset): its
products are the one-hot products of ``ops/item_sorted.py`` on the MXU,
and the rows' latent vectors are laid out once a step. ``P`` starts as the top-
``p`` right singular subspace of the entities × columns matrix of first-
order residuals, found by subspace iteration through the same two flat
products (``subspace_init``), and ``β`` from the first latent step. The
effective per-entity coefficients ``P_local · β_e`` (a
:class:`RandomEffectModel`) are materialized only when asked for: export
and serving.
"""
from __future__ import annotations

import dataclasses
import functools
import weakref
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from photon_tpu.data.random_effect import EntityBucket, RandomEffectDataset
from photon_tpu.functions.objective import ScoreSpaceObjective
from photon_tpu.functions.problem import GLMOptimizationProblem
from photon_tpu.game.random_effect import (
    _RE_PROJECTION_PLANS,
    RandomEffectModel,
    train_random_effects,
)
from photon_tpu.obs import device_wait, trace_span
from photon_tpu.obs.metrics import REGISTRY
from photon_tpu.ops import item_sorted
from photon_tpu.ops.losses import loss_for_task
from photon_tpu.optim import LBFGS, OptimizerResult
from photon_tpu.types import TaskType

Array = jax.Array

# Rounds of subspace iteration of the initial projection: each is one
# product by the residual matrix and one by its transpose, four flat passes.
INIT_ROUNDS = 4

_PROJECTION_PASSES = REGISTRY.counter(
    "factored_projection_passes_total",
    "Data passes (one flat product of the rows with P, or its transpose) "
    "made by the projection steps of factored random effects",
)
_PROJECTION_PLAN_BUILDS = REGISTRY.counter(
    "factored_projection_plan_builds_total",
    "Rows sorted by item for the projection steps of factored random "
    "effects (``sorted_rows``): one a dataset, kept while it lives",
)


# Rows a flat product takes at a time. On the TPU a gather writes its
# [rows, p] result with p padded to the 128 lanes (5.1 GB at 10 M rows by
# 32), so every product runs over chunks of rows and holds one chunk's.
CHUNK_ROWS = 1 << 19


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class FlatRows:
    """A per-entity dataset's rows in the global row order, padded to whole
    chunks of ``CHUNK_ROWS`` where there are more: each row's entries in global columns (``idx``
    ghost = the dataset's ``global_dim``), and ``slot``, its entity's
    position in the dataset's buckets laid end to end (bucket start + lane;
    ``n_slots`` for a row of no entity and a padding row). ``weights`` are
    the training weights (0 on a passive row, a row of no entity and a
    padding row); ``n_rows`` the rows before the padding."""

    idx: Array        # [R, K] int32
    val: Array        # [R, K]
    slot: Array       # [R] int32
    weights: Array    # [R]
    labels: Array     # [R]
    n_rows: int = dataclasses.field(metadata=dict(static=True))
    n_slots: int = dataclasses.field(metadata=dict(static=True))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SortedRows:
    """A one-entry dataset's flat rows sorted by their item, stably, the
    ghost and padding rows last, padded to whole grid steps of the kernels
    (``ops/item_sorted.py``): ``perm[i]`` is the flat row at sorted
    position ``i`` (past the flat rows' offsets for a padding row); ``col``
    and ``val`` the sorted items and values (``[C, CHUNK]``; a ghost or
    padding row: item ``D``, value 0); ``slot``, ``weights`` and ``labels``
    sorted alike; ``windows`` the chunks' windows (``item_windows``).
    ``window_passes`` is the windows one product reads."""

    perm: Array       # [R'] int32
    col: Array        # [C, CHUNK] int32
    val: Array        # [C, CHUNK]
    slot: Array       # [R'] int32
    weights: Array    # [R']
    labels: Array     # [R']
    windows: Array    # [C] int32
    window_passes: int = dataclasses.field(metadata=dict(static=True))


# What the factored path keeps for an object while it lives, by its id:
# for a dataset ``rows`` (``flat_rows``) and ``latent_plan``; for the
# dataset's flat rows ``sorted`` (``sorted_rows``).
_KEPT: dict = {}


def _kept(owner) -> dict:
    key = id(owner)
    if key not in _KEPT:
        _KEPT[key] = {}
        weakref.finalize(owner, _KEPT.pop, key, None)
    return _KEPT[key]


def flat_rows(dataset: RandomEffectDataset) -> FlatRows:
    """The dataset's rows laid flat, built on the host from its buckets
    once and kept while the dataset lives."""
    kept = _kept(dataset)
    if "rows" not in kept:
        kept["rows"] = _build_flat_rows(dataset)
    return kept["rows"]


def _build_flat_rows(dataset: RandomEffectDataset) -> FlatRows:
    n, d = dataset.n_rows, dataset.global_dim
    padded = (max(n, 1) if n <= CHUNK_ROWS
              else -(-n // CHUNK_ROWS) * CHUNK_ROWS)
    k = dataset.buckets[0].idx.shape[2] if dataset.buckets else 1
    dtype = dataset.buckets[0].val.dtype if dataset.buckets else np.float32
    idx = np.full((padded, k), d, np.int32)
    val = np.zeros((padded, k), dtype)
    slot = np.full(padded, sum(b.n_entities for b in dataset.buckets),
                   np.int32)
    weights = np.zeros(padded, dtype)
    labels = np.zeros(padded, dtype)
    start = 0
    for b in dataset.buckets:
        with device_wait("factored_rows"):
            b_idx, b_val, proj, rows, tw, lab = (np.asarray(a) for a in (
                b.idx, b.val, b.proj, b.row_ids, b.train_weights, b.labels))
        e, s, _ = b_idx.shape
        # The local ghost column (== P) maps to the global ghost.
        ext = np.concatenate([proj, np.full((e, 1), d, proj.dtype)], axis=1)
        cols = np.take_along_axis(ext, b_idx.reshape(e, s * k), axis=1)
        live = rows < n
        at = rows[live]
        idx[at] = cols.reshape(e, s, k)[live]
        val[at] = b_val[live]
        weights[at] = tw[live]
        labels[at] = lab[live]
        slot[at] = np.broadcast_to(
            np.arange(start, start + e, dtype=np.int32)[:, None], (e, s))[live]
        start += e
    return FlatRows(idx=jnp.asarray(idx), val=jnp.asarray(val),
                    slot=jnp.asarray(slot), weights=jnp.asarray(weights),
                    labels=jnp.asarray(labels), n_rows=n, n_slots=start)


def sorted_rows(rows: FlatRows, P: Array):
    """The rows sorted by item for a projection step at ``P``
    (:class:`SortedRows`), built on the host once and kept while the rows
    (and so their dataset) live; ``None`` where the step keeps the flat
    products: more than one entry a row, data or ``P`` not float32, or a
    ``P`` the kernels cannot hold (``item_sorted.holds``)."""
    dim, p = P.shape
    if not (rows.idx.shape[1] == 1 and rows.val.dtype == jnp.float32
            and P.dtype == jnp.float32 and item_sorted.holds(dim, p)):
        return None
    kept = _kept(rows)
    if "sorted" not in kept:
        kept["sorted"] = _build_sorted_rows(rows, dim)
        _PROJECTION_PLAN_BUILDS.inc()
    return kept["sorted"]


def _build_sorted_rows(rows: FlatRows, dim: int) -> SortedRows:
    with device_wait("factored_plan"):
        col, val, slot, weights, labels = (np.asarray(a).reshape(-1) for a in (
            rows.idx, rows.val, rows.slot, rows.weights, rows.labels))
    n = len(col)
    # 16-bit keys take NumPy's radix sort; the ghost item ``dim`` sorts last.
    order = np.argsort(col.astype(np.uint16) if dim < 1 << 16 else col,
                       kind="stable")
    padded = -(-n // item_sorted.STEP_ROWS) * item_sorted.STEP_ROWS

    def laid(a: np.ndarray, fill) -> np.ndarray:
        out = np.full(padded, fill, a.dtype)
        out[:n] = a[order]
        return out

    items = laid(col, dim)
    windows = item_sorted.item_windows(items, dim)
    return SortedRows(
        perm=jnp.asarray(laid(np.arange(n, dtype=np.int32), n)),
        col=jnp.asarray(items.reshape(-1, item_sorted.CHUNK)),
        val=jnp.asarray(laid(val, 0).reshape(-1, item_sorted.CHUNK)),
        slot=jnp.asarray(laid(slot, rows.n_slots)),
        weights=jnp.asarray(laid(weights, 0)),
        labels=jnp.asarray(laid(labels, 0)),
        windows=jnp.asarray(windows),
        window_passes=item_sorted.window_passes(windows))


# ------------------------------------------------------ the flat products


def _take(table: Array, at: Array) -> Array:
    """Rows ``at`` of ``table``; 0 where ``at`` is past its end (the ghost
    column, the slot of no entity). A gather from one table: no operand is
    batched per entity."""
    return jnp.take(table, at, axis=0, mode="fill", fill_value=0)


def _chunk_rows(a: Array) -> int:
    return min(a.shape[0], CHUNK_ROWS)


def _chunk(a: Array, i) -> Array:
    size = _chunk_rows(a)
    return jax.lax.dynamic_slice_in_dim(a, i * size, size)


def _n_chunks(a: Array) -> int:
    return a.shape[0] // _chunk_rows(a)


def _chunk_times(table: Array, idx: Array, val: Array) -> Array:
    """``[rows, p]``: ``sum_k val[n,k] * table[idx[n,k]]`` over one chunk,
    in elementwise products (exact in float32: no matrix unit pass)."""
    out = 0
    for k in range(idx.shape[1]):
        out = out + val[:, k, None].astype(table.dtype) * _take(
            table, idx[:, k])
    return out


def _times_dot(table: Array, beta: Array, idx: Array, val: Array,
               slot: Array) -> Array:
    """``[R]``: ``(x_n table) . beta[slot[n]]``, each row's margin (0 for a
    row of no entity)."""
    def one(i):
        xp = _chunk_times(table, _chunk(idx, i), _chunk(val, i))
        return jnp.sum(xp * _take(beta, _chunk(slot, i)), axis=1)

    return jax.lax.map(one, jnp.arange(_n_chunks(idx))).reshape(-1)


def _transpose_dot(r: Array, beta: Array, idx: Array, val: Array,
                   slot: Array, dim: int) -> Array:
    """``[dim, p]``: ``sum_n x_n (x) (r_n beta[slot[n]])``, a scatter-add of
    the rows' entries into one table (the ghost column dropped)."""
    def one(i, out):
        upd = _chunk(r, i)[:, None] * _take(beta, _chunk(slot, i))
        at, v = _chunk(idx, i), _chunk(val, i)
        for k in range(idx.shape[1]):
            out = out.at[at[:, k]].add(v[:, k, None].astype(upd.dtype) * upd,
                                       mode="drop")
        return out

    return jax.lax.fori_loop(0, _n_chunks(idx), one,
                             jnp.zeros((dim, beta.shape[1]), beta.dtype))


def _sum_times(r: Array, table: Array, idx: Array, val: Array, slot: Array,
               n_slots: int) -> Array:
    """``[n_slots, p]``: ``sum over u's rows of r_n (x_n table)``."""
    def one(i, out):
        xp = _chunk_times(table, _chunk(idx, i), _chunk(val, i))
        return out.at[_chunk(slot, i)].add(_chunk(r, i)[:, None] * xp,
                                           mode="drop")

    return jax.lax.fori_loop(0, _n_chunks(idx), one,
                             jnp.zeros((n_slots, table.shape[1]), table.dtype))


def _padded(offsets: Array, rows: int, dtype) -> Array:
    return jnp.pad(offsets.astype(dtype), (0, rows - offsets.shape[0]))


@partial(jax.jit, static_argnames="n_rows")
def latent_scores(P: Array, beta: Array, idx: Array, val: Array,
                  plan: Array, *, n_rows: int) -> Array:
    """Per-row scores ``XP[n] . beta[plan[n]]`` (``plan`` past ``beta``'s
    rows: an entity the model does not hold, scoring 0), one flat pass."""
    return _times_dot(P, beta, idx, val, plan)[:n_rows]


def _orthonormal(y: Array) -> Array:
    """The Q of ``y``'s QR with a positive diagonal in R (the unique one),
    by classical Gram-Schmidt applied twice, in elementwise products. A
    column in the span of those before it comes out 0."""
    p = y.shape[1]
    columns = jnp.arange(p)

    def column(j, q):
        v = y[:, j]
        before = (columns < j).astype(y.dtype)
        for _ in range(2):
            c = jnp.sum(q * v[:, None], axis=0) * before
            v = v - jnp.sum(q * c[None, :], axis=1)
        norm = jnp.sqrt(jnp.sum(v * v))
        v = jnp.where(norm > 0, v / jnp.where(norm > 0, norm, 1), 0)
        return q.at[:, j].set(v)

    return jax.lax.fori_loop(0, p, column, jnp.zeros_like(y))


@partial(jax.jit, static_argnames=("task", "n_slots", "rounds"))
def _subspace_iteration(q0, idx, val, slot, weights, labels, offsets, *,
                        task, n_slots, rounds):
    """The top right singular subspace of ``R [entities, D]``,
    ``R[u, c] = sum over u's rows of r_n x_n[c]`` with the first-order
    residual ``r_n = -w_n * loss'(o_n, y_n)`` at the offsets, by ``rounds``
    rounds of ``Q <- orth(Rᵀ R Q)`` from ``orth(q0)``."""
    loss = loss_for_task(task)
    offsets = _padded(offsets, idx.shape[0], q0.dtype)
    r = -weights * loss.d1(offsets, labels)
    q = _orthonormal(q0)
    for _ in range(rounds):
        m = _sum_times(r, q, idx, val, slot, n_slots)                 # R Q
        q = _orthonormal(
            _transpose_dot(r, m, idx, val, slot, q0.shape[0]))       # RᵀRQ
    return q


def init_start(dim: int, latent_dim: int, seed: int, dtype) -> np.ndarray:
    """The subspace iteration's start: ``[dim, latent_dim]`` standard
    normal draws of ``numpy.random.default_rng(seed)`` in float64, cast to
    the data's precision."""
    return np.random.default_rng(seed).standard_normal(
        (dim, latent_dim)).astype(dtype)


def subspace_init(problem: GLMOptimizationProblem, rows: FlatRows,
                  offsets: Array, dim: int, latent_dim: int, seed: int
                  ) -> Array:
    """The initial projection ``P0 [dim, latent_dim]`` (module doc)."""
    q0 = jnp.asarray(init_start(dim, latent_dim, seed, rows.val.dtype))
    return _subspace_iteration(
        q0, rows.idx, rows.val, rows.slot, rows.weights, rows.labels,
        offsets, task=problem.task, n_slots=rows.n_slots, rounds=INIT_ROUNDS)


def _latent_columns(beta: Array, slot: Array, rows: int) -> Array:
    """``[rows, R]``: column ``n`` is ``beta[slot[n]]`` (0 past ``beta``'s
    rows, and in the rows past its width), gathered ``CHUNK_ROWS`` rows at
    a time and laid lane-dense: never ``[R, p]``, which the TPU pads to 128
    lanes."""
    table = jnp.pad(beta, ((0, 0), (0, rows - beta.shape[1])))
    size = _chunk_rows(slot)

    def one(i, out):
        return jax.lax.dynamic_update_slice_in_dim(
            out, _take(table, _chunk(slot, i)).T, i * size, axis=1)

    return jax.lax.fori_loop(0, _n_chunks(slot), one,
                             jnp.zeros((rows, slot.shape[0]), beta.dtype))


def _sorted_products(plan: SortedRows, beta: Array, offsets: Array,
                     shape: tuple):
    """``(times, transpose, offsets)`` over rows sorted by item: the latent
    vectors laid once (``_latent_columns``), the offsets put in the plan's
    order, and both products the kernels of ``ops/item_sorted.py``, in
    float32."""
    bt = _latent_columns(beta.astype(jnp.float32), plan.slot,
                         item_sorted.latent_rows(shape[1]))
    return (
        lambda table: item_sorted.sorted_times(
            plan.windows, plan.col, plan.val, table, bt),
        lambda r: item_sorted.sorted_scatter(
            plan.windows, plan.col, plan.val, r, bt, shape),
        jnp.take(offsets.astype(jnp.float32), plan.perm, mode="fill",
                 fill_value=0))


def _flat_products(rows: FlatRows, beta: Array, offsets: Array,
                   shape: tuple):
    """``(times, transpose, offsets)`` over the rows laid flat."""
    return (
        lambda table: _times_dot(table, beta, rows.idx, rows.val, rows.slot),
        lambda r: _transpose_dot(r, beta, rows.idx, rows.val, rows.slot,
                                 shape[0]),
        _padded(offsets, rows.idx.shape[0], beta.dtype))


@partial(jax.jit, static_argnums=(0,))
def _projection_step(problem, P, rows, offsets, beta):
    """Refit ``P`` with every β fixed: L-BFGS over ``vec(P)`` on maintained
    margins ``z[n] = XP[n] · β_{u(n)} + o_n``; the gradient is
    ``Xᵀ(d1 ⊙ B) + λP`` with ``B[n] = β_{u(n)}``. Over ``SortedRows`` the
    margins are kept in the plan's order for the whole solve and both
    products are the item-sorted kernels; over ``FlatRows``, the flat
    gather and scatter-add."""
    loss = loss_for_task(problem.task)
    lam = problem.regularization.l2_weight(problem.reg_weight)
    dim, p = P.shape
    times, transpose, offsets = (
        _sorted_products if isinstance(rows, SortedRows) else _flat_products
    )(rows, beta, offsets, P.shape)
    weights, labels = rows.weights, rows.labels

    def score_delta(w):
        return times(w.reshape(dim, p))

    def value(z, w):
        return jnp.sum(weights * loss.loss(z, labels)) + 0.5 * lam * jnp.sum(
            w * w)

    def grad(z, w):
        return (transpose(weights * loss.d1(z, labels)).reshape(-1)
                + lam * w)

    objective = ScoreSpaceObjective(
        score=lambda w: score_delta(w) + offsets, score_delta=score_delta,
        value_from_scores=value, grad_from_scores=grad)
    result = LBFGS(problem.optimizer_config).optimize_scored(
        objective, P.reshape(-1))
    return result.x.reshape(dim, p), result


@jax.jit
def project_slots(P: Array, idx: Array, val: Array, row_ids: Array
                  ) -> Array:
    """``[E, S, p]``: the projected rows ``XP[n] = x_n P`` of one bucket's
    slots, ``row_ids`` into the flat rows ``idx``/``val`` (a padding slot's
    row is a padding row, or past the end: zeros). Flat gathers: the
    slots' entries by row, then ``P``'s rows by column."""
    at = row_ids.reshape(-1)
    out = 0
    for k in range(idx.shape[1]):
        col = jnp.take(idx[:, k], at, mode="fill", fill_value=P.shape[0])
        v = jnp.take(val[:, k], at, mode="fill", fill_value=0)
        out = out + v[:, None].astype(P.dtype) * _take(P, col)
    return out.reshape(row_ids.shape + (P.shape[1],))


def _latent_buckets(dataset: RandomEffectDataset, rows: FlatRows,
                    P: Array) -> tuple:
    """The dataset's buckets as dense buckets of ``p`` local columns: entry
    ``k`` of a slot is column ``k`` with value ``XP[row, k]``. The column
    maps (``idx[e,s,k] = k``, ``proj[e,k] = k``) hold no data and are made
    for the step alone."""
    p = P.shape[1]
    columns = jnp.arange(p, dtype=jnp.int32)
    return tuple(
        EntityBucket(idx=jnp.broadcast_to(columns, b.idx.shape[:2] + (p,)),
                     val=project_slots(P, rows.idx, rows.val, b.row_ids),
                     labels=b.labels,
                     weights=b.weights, train_weights=b.train_weights,
                     row_ids=b.row_ids,
                     proj=jnp.broadcast_to(columns, (b.n_entities, p)),
                     entity_ids=b.entity_ids)
        for b in dataset.buckets)


def _outcome(results: Sequence[OptimizerResult]) -> dict:
    """``{iterations, passes, solves}`` of a sub-step's solves: the most
    iterations of any solve, the data passes of all of them, and each
    result's most iterations (a bucket's batched loop runs as long as its
    slowest lane)."""
    with device_wait("factored_outcome"):
        each = [int(np.asarray(r.iterations).max()) for r in results]
        passes = int(sum(np.asarray(r.data_passes).sum() for r in results))
    return {"iterations": max(each), "passes": passes, "solves": each}


def latent_step(problem: GLMOptimizationProblem,
                dataset: RandomEffectDataset, rows: FlatRows, P: Array,
                offsets: Array, lats: Sequence[Array]):
    """Every entity's ``β`` at ``P``: ``(lats, results)``."""
    with trace_span("factored.latent", cat="optim",
                    re_type=dataset.re_type) as span:
        with trace_span("factored.project", cat="optim",
                        rows=dataset.n_rows):
            latent = dataclasses.replace(
                dataset, buckets=_latent_buckets(dataset, rows, P),
                global_dim=P.shape[1])
        model, results = train_random_effects(
            problem, latent, offsets, init_coefs=lats)
        span.set(**_outcome(results))
    return list(model.bucket_coefs), results


def projection_step(problem: GLMOptimizationProblem, rows: FlatRows,
                    P: Array, offsets: Array, lats: Sequence[Array]):
    """``P`` refitted at the given ``β``: ``(P, result)``. Over the rows
    sorted by item where ``sorted_rows`` gives a plan, else over the flat
    rows."""
    with trace_span("factored.projection", cat="optim",
                    parameters=int(P.size)) as span:
        plan = sorted_rows(rows, P)
        span.set(formulation="flat" if plan is None else "sorted")
        if plan is not None:
            span.set(window_passes=plan.window_passes)
        beta = jnp.concatenate(list(lats))
        P, result = _projection_step(
            problem, P, rows if plan is None else plan, offsets, beta)
        outcome = _outcome([result])
        span.set(**outcome)
    _PROJECTION_PASSES.inc(outcome["passes"])
    return P, result


@dataclasses.dataclass(frozen=True)
class FactoredRandomEffectModel:
    """``w_e = P · β_e`` over the buckets of the dataset it was trained on.

    Scores in the latent space (``score_new_dataset``): the rows' ``XP``
    and each row's entity's ``β``, matched by entity key through a plan the
    scored dataset keeps. ``effective`` (the per-entity coefficients in
    each entity's local subspace, a :class:`RandomEffectModel`) is built on
    first use, for export and serving.
    """

    re_type: str
    task: TaskType
    projection: Array                   # [D, p]
    bucket_latent: Sequence[Array]      # per bucket: [E, p]
    entity_keys: Sequence               # dense REId -> key
    entity_to_slot: dict                # dense REId -> (bucket, lane)
    bucket_proj: Sequence[Array]        # per bucket: [E, P] local -> global
    bucket_entity_ids: Sequence[Array]  # per bucket: [E]
    global_dim: int

    @property
    def latent_dim(self) -> int:
        return self.projection.shape[1]

    @property
    def n_entities(self) -> int:
        return len(self.entity_keys)

    @functools.cached_property
    def effective(self) -> RandomEffectModel:
        P = self.projection
        ext = jnp.concatenate([P, jnp.zeros_like(P[:1])])
        coefs = [jnp.sum(ext[proj] * lat[:, None, :], axis=-1)
                 for proj, lat in zip(self.bucket_proj, self.bucket_latent)]
        return RandomEffectModel(
            re_type=self.re_type, task=self.task, bucket_coefs=coefs,
            bucket_proj=self.bucket_proj,
            bucket_entity_ids=self.bucket_entity_ids,
            entity_keys=self.entity_keys, entity_to_slot=self.entity_to_slot,
            global_dim=self.global_dim)

    def latent_plan(self, dataset: RandomEffectDataset) -> Array:
        """``[N]``: each row of ``dataset``, the position of its entity's
        ``β`` in this model's stacks laid end to end (their length for an
        entity this model never saw). Built on the host once for a pair of
        structure and dataset and kept with the dataset, as
        ``RandomEffectModel.projection_plan`` keeps its plan; counted in
        ``re_projection_plans_total{outcome}`` likewise, and the outcome
        left where a projection of a plain model leaves its own
        (``dataset.kept_projection["outcome"]``, read by validation's
        scorer)."""
        kept = _kept(dataset)
        hit = kept.get("latent_plan")
        if (hit is not None and hit[0] is self.entity_keys
                and hit[1] is self.entity_to_slot):
            outcome = "reused"
        else:
            hit = kept["latent_plan"] = (
                self.entity_keys, self.entity_to_slot, self._build_plan(dataset))
            outcome = "built"
        dataset.kept_projection["outcome"] = outcome
        _RE_PROJECTION_PLANS.inc(outcome=outcome)
        return hit[2]

    def _build_plan(self, dataset: RandomEffectDataset) -> Array:
        rows = flat_rows(dataset)
        sizes = [int(b.shape[0]) for b in self.bucket_latent]
        n_slots = sum(sizes)
        if dataset.entity_keys is self.entity_keys:
            # The dataset this model was trained on: its own slots.
            return jnp.minimum(rows.slot, n_slots)
        start = np.cumsum([0] + sizes)
        key_to_dense = {k: i for i, k in enumerate(self.entity_keys)}
        # slot of dataset -> slot here (n_slots: never trained)
        to_model = np.full(rows.n_slots + 1, n_slots, np.int32)
        at = 0
        for b in dataset.buckets:
            with device_wait("project_stacks"):
                eids = np.asarray(b.entity_ids)
            for lane, e in enumerate(eids.tolist()):
                dense = (key_to_dense.get(dataset.entity_keys[e])
                         if e >= 0 else None)
                if dense is not None:
                    bo, lo = self.entity_to_slot[dense]
                    to_model[at + lane] = start[bo] + lo
            at += len(eids)
        with device_wait("project_stacks"):
            slot = np.asarray(rows.slot)
        return jnp.asarray(to_model[slot])

    def score_new_dataset(self, dataset: RandomEffectDataset) -> Array:
        """Scores of ``dataset``'s rows in the latent space: one flat pass
        through the projection and the plan's latent vectors."""
        plan = self.latent_plan(dataset)
        rows = flat_rows(dataset)
        beta = jnp.concatenate(list(self.bucket_latent))
        return latent_scores(self.projection, beta, rows.idx, rows.val, plan,
                             n_rows=rows.n_rows)

    def score_dataset(self, dataset: RandomEffectDataset) -> Array:
        return self.score_new_dataset(dataset)

    def coefficients_for(self, entity_key):
        return self.effective.coefficients_for(entity_key)


def _factor_model(
    source: "RandomEffectModel",
    dataset: RandomEffectDataset,
    latent_dim: int,
    seed: int,
) -> tuple[Array, list[Array]]:
    """Top-p SVD of ``source``'s sparse per-entity coefficients, with β rows
    matched to ``dataset``'s entities BY KEY (entities the source never saw
    start at 0): re-factoring a loaded effective model, whose coefficient
    matrix is exactly rank-p. A host path, taken by a warm start alone."""
    import scipy.sparse as sp
    from scipy.sparse.linalg import svds

    rows, cols, vals = [], [], []
    for coefs, proj, eids in zip(
        source.bucket_coefs, source.bucket_proj, source.bucket_entity_ids
    ):
        c = np.asarray(coefs, np.float64)
        p = np.asarray(proj)
        e = np.asarray(eids)
        lane_ok = e >= 0
        col_ok = p < dataset.global_dim
        ok = lane_ok[:, None] & col_ok
        rows.append(np.broadcast_to(e[:, None], p.shape)[ok])
        cols.append(p[ok])
        vals.append(c[ok])
    n_src = source.n_entities
    W = sp.csr_matrix(
        (np.concatenate(vals), (np.concatenate(rows), np.concatenate(cols))),
        shape=(n_src, source.global_dim),
    )
    k = min(latent_dim, min(W.shape) - 1)
    P0 = np.zeros((dataset.global_dim, latent_dim))
    B_src = np.zeros((n_src, latent_dim))
    if k >= 1:
        # deterministic ARPACK start vector (svds' random_state plumbing
        # varies across scipy versions)
        v0 = np.random.default_rng(seed).normal(size=min(W.shape))
        u, s, vt = svds(W, k=k, v0=v0)
        order = np.argsort(-s)
        u, s, vt = u[:, order], s[order], vt[order]
        P0[: source.global_dim, :k] = vt.T
        B_src[:, :k] = u * s
    B0 = np.zeros((dataset.n_entities + 1, latent_dim))
    key_to_src = source._key_to_dense
    for dense_new, key in enumerate(dataset.entity_keys):
        src = key_to_src.get(key)
        if src is not None:
            B0[dense_new] = B_src[src]
    lats = [
        jnp.asarray(B0[np.asarray(b.entity_ids)])   # -1 pad -> zero last row
        for b in dataset.buckets
    ]
    return jnp.asarray(P0), lats


def train_factored_random_effects(
    problem: GLMOptimizationProblem,
    dataset: RandomEffectDataset,
    offsets: Array,
    latent_dim: int = 8,
    n_alternations: int = 2,
    seed: int = 0,
    init=None,
) -> tuple[FactoredRandomEffectModel, list[OptimizerResult]]:
    """Alternating factored-RE training over all buckets.

    ``problem`` configures both steps (its optimizer config drives the
    latent solves and the projection step's L-BFGS alike). ``init`` may be
    a :class:`FactoredRandomEffectModel` (same structure → resume its
    factors) or a plain :class:`RandomEffectModel` (a loaded warm start →
    its coefficients are re-factored, ``_factor_model``); otherwise ``P``
    starts from ``subspace_init`` and ``β`` at 0. Returns the model and the
    results of every solve the step made: each latent step's per-bucket
    results and each projection step's, in order."""
    dtype = dataset.buckets[0].val.dtype if dataset.buckets else jnp.float32
    d = dataset.global_dim
    rows = flat_rows(dataset)
    same_init = (
        isinstance(init, FactoredRandomEffectModel)
        and init.projection.shape == (d, latent_dim)
        and len(init.bucket_latent) == len(dataset.buckets)
        and all(
            b.shape[0] == bk.n_entities
            for b, bk in zip(init.bucket_latent, dataset.buckets)
        )
    )
    if same_init:
        P = init.projection.astype(dtype)
        lats = [b.astype(dtype) for b in init.bucket_latent]
    elif (
        isinstance(init, RandomEffectModel) and init.global_dim == d
        and dataset.buckets
    ):
        P, lats = _factor_model(init, dataset, latent_dim, seed)
        P = P.astype(dtype)
        lats = [b.astype(dtype) for b in lats]
    else:
        # A Gaussian random P (the reference RandomProjectionMatrix) makes
        # the alternation lock onto the random subspace: the first β-step
        # fits noise the random P happens to span and the P-step then
        # reinforces it. Starting in the residuals' principal subspace
        # lands in the right basin.
        with trace_span("factored.init", cat="optim", rounds=INIT_ROUNDS):
            P = subspace_init(problem, rows, offsets, d, latent_dim, seed)
            with device_wait("factored_init"):
                np.asarray(P[:1, :1])
        lats = [jnp.zeros((b.n_entities, latent_dim), dtype)
                for b in dataset.buckets]

    results: list[OptimizerResult] = []
    for _ in range(max(1, n_alternations)):
        lats, res = latent_step(problem, dataset, rows, P, offsets, lats)
        results.extend(res)
        P, res = projection_step(problem, rows, P, offsets, lats)
        results.append(res)
    # Final latent refresh so β is optimal for the returned P.
    lats, res = latent_step(problem, dataset, rows, P, offsets, lats)
    results.extend(res)

    model = FactoredRandomEffectModel(
        re_type=dataset.re_type,
        task=problem.task,
        projection=P,
        bucket_latent=lats,
        entity_keys=dataset.entity_keys,
        entity_to_slot=dataset.entity_to_slot,
        bucket_proj=[b.proj for b in dataset.buckets],
        bucket_entity_ids=[b.entity_ids for b in dataset.buckets],
        global_dim=d,
    )
    return model, results
