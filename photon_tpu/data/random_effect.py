"""Random-effect datasets: per-entity data, bucketed and padded for vmap.

Parity: reference ⟦photon-api/.../data/RandomEffectDataset.scala⟧ +
``LocalDataset`` + ⟦.../projector/LinearSubspaceProjector⟧ and the
sample-count-balancing ⟦RandomEffectDatasetPartitioner⟧ (SURVEY.md §2.2,
§3.5, §2.6 P2/P6).

TPU-first layout: instead of an ``RDD[(REId, LocalDataset)]`` with one Breeze
solve per entity inside ``mapPartitions``, entities are grouped host-side and
packed into **buckets** of identical padded shape ``[E, S, K]`` (entities x
max-samples x max-nnz). Within a bucket every per-entity solve is one lane of
a ``vmap``; buckets shard over the mesh's entity axis. Shapes are quantized
to powers of two so the number of distinct XLA compilations stays O(log² of
the size range) — the TPU analog of the reference's skew-balancing
partitioner.

Feature projection: each entity sees only the feature columns present in its
own rows (the reference's ``LinearSubspaceProjector``). Global ELL indices are
remapped to a compact per-entity local space ``[0, P)``; ``proj[e, p]`` maps
local slot p back to the global column (or ``global_dim`` for unused pad
slots, which is the global ghost column). Scoring and model export gather
through ``proj``.

Active/passive split: rows beyond ``active_bound`` per entity keep weight for
scoring (``weights``) but carry 0 in ``train_weights`` — the reference's
passive data, scored but not trained on.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


def _next_pow2(n: int) -> int:
    return 1 << max(0, int(n - 1).bit_length())


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class EntityBucket:
    """One padded bucket of entities with identical [E, S, K, P] shapes.

    ``idx``/``val`` are per-entity ELL in *local* feature space (ghost column
    = P). ``proj`` maps local→global columns (ghost slots hold
    ``global_dim``). ``row_ids`` maps each (entity, sample) slot back to the
    global row it came from (padding slots hold the global row count N, a
    ghost row). ``weights`` masks valid rows; ``train_weights`` additionally
    zeroes passive rows. ``entity_ids`` are dense REIds (padding: -1).
    """

    idx: Array            # [E, S, K] int32, local column ids
    val: Array            # [E, S, K]
    labels: Array         # [E, S]
    weights: Array        # [E, S] — 0 marks padded rows
    train_weights: Array  # [E, S] — 0 marks padded AND passive rows
    row_ids: Array        # [E, S] int32 into the global sample order; N = pad
    proj: Array           # [E, P] int32 local→global column map; dim = pad
    entity_ids: Array     # [E] int32 dense entity ids; -1 = padded entity

    @property
    def n_entities(self) -> int:
        return self.idx.shape[0]

    @property
    def max_samples(self) -> int:
        return self.idx.shape[1]

    @property
    def local_dim(self) -> int:
        return self.proj.shape[1]

    def local_batches(self, global_offsets: Array):
        """Per-entity LabeledBatch pytree stacked on axis 0 (for vmap), using
        offsets gathered from the global per-sample offset vector."""
        from photon_tpu.data.batch import LabeledBatch, SparseFeatures

        global_offsets = global_offsets.astype(self.val.dtype)
        # Ghost row offset 0: extend then gather (row_ids padding == n).
        ext = jnp.concatenate([global_offsets, jnp.zeros((1,), global_offsets.dtype)])
        offsets = ext[self.row_ids]
        return LabeledBatch(
            features=SparseFeatures(idx=self.idx, val=self.val, dim=self.local_dim),
            labels=self.labels,
            offsets=offsets,
            weights=self.train_weights,
        )

    def scores(self, coefs: Array) -> Array:
        """Per-slot scores [E, S] from per-entity coefficients [E, P]
        (offsets NOT included — GAME composes scores additively)."""
        return _bucket_scores(self.idx, self.val, coefs)


# The widest local dimension at which an entry's column is picked by
# compare-select over all the columns (``_bucket_scores``, and the dense
# design of ``game/newton_re.py``). Both GAME cells run at 32, where the
# pick is read on the chip; from 33 columns on the CPU's compiler no longer
# fuses the scorer's pick and holds it whole, [E,S,K,P] float32 (0.44 GB at
# 4,096 x 256 x 3 x 33 for nothing at 32; PERF.md §6, PR 33). Wider, the
# gather and the scatter-add as they were: no cell runs there, and on the
# chip alone the pick still wins at 256 (PERF.md §6; §7 item 11).
SELECT_MAX_COLUMNS = 32


@jax.jit
def _bucket_scores(idx: Array, val: Array, coefs: Array) -> Array:
    """``sum_k val[e,s,k] * coefs[e, idx[e,s,k]]``, the ghost column (== P)
    counting for nothing. Up to ``SELECT_MAX_COLUMNS`` local columns the
    coefficient of an entry is picked by compare-select over the columns,
    fused into the sum, and not by a gather: a batched gather over [E,S,K]
    indices compiles on the TPU's compiler in time that grows with the
    slots (309 s and 3.37 GB of temporaries at 12,874 x 256 x 3 for a
    described v5e; 0.75 s and none by the pick: PERF.md §6, PR 33). The
    pick is exact: one coefficient and zeros."""
    if coefs.shape[1] > SELECT_MAX_COLUMNS:
        ext = jnp.concatenate([coefs, jnp.zeros_like(coefs[:, :1])], axis=1)
        return jax.vmap(lambda w, i, v: jnp.sum(w[i] * v, axis=-1))(
            ext, idx, val)
    columns = jnp.arange(coefs.shape[1], dtype=idx.dtype)
    picked = jnp.sum(
        jnp.where(idx[..., None] == columns, coefs[:, None, None, :], 0),
        axis=-1)
    return jnp.sum(picked * val, axis=-1)


@functools.partial(jax.jit, static_argnames="n_rows")
def _scatter_slots(per_bucket_scores, per_bucket_row_ids, n_rows: int):
    """One scatter of every bucket's [E, S] slots into the ``n_rows`` rows
    they came from; padding slots point at a ghost row past the last. One
    program a dataset, where a scatter a bucket was one a size class."""
    flat = jnp.concatenate([s.ravel() for s in per_bucket_scores])
    rows = jnp.concatenate([r.ravel() for r in per_bucket_row_ids])
    return jnp.zeros((n_rows + 1,), flat.dtype).at[rows].set(flat)[:n_rows]


@dataclasses.dataclass(frozen=True)
class RandomEffectDataset:
    """All buckets for one random-effect coordinate + host-side entity index.

    ``entity_to_slot`` maps entity key → (bucket_index, lane); ``n_rows`` is
    the global sample count the ``row_ids`` refer to. ``bucket_rows`` is the
    number of real rows each bucket holds (its row slots less the padding),
    as the builder counted them.
    """

    re_type: str                      # entity column name, e.g. "userId"
    buckets: Sequence[EntityBucket]
    entity_keys: Sequence             # dense REId -> original key
    entity_to_slot: dict              # dense REId -> (bucket, lane)
    n_rows: int
    global_dim: int
    bucket_rows: Sequence[int]        # per bucket: real rows

    @property
    def n_entities(self) -> int:
        return len(self.entity_keys)

    @property
    def row_slots(self) -> int:
        """Entities x padded rows, summed over the buckets."""
        return sum(b.n_entities * b.max_samples for b in self.buckets)

    @property
    def size_classes(self) -> tuple[int, ...]:
        """The distinct padded row counts of the buckets, ascending: one a
        size class of entity, however many buckets (local widths, chunks
        of entities) a class was split into."""
        return tuple(sorted({int(b.max_samples) for b in self.buckets}))

    def span_arguments(self) -> dict:
        """``{buckets, rows, row_slots}``: how many buckets a step over
        this dataset solves, the real rows in them and the padded row
        slots."""
        return {"buckets": len(self.buckets),
                "rows": int(sum(self.bucket_rows)),
                "row_slots": self.row_slots}

    @functools.cached_property
    def kept_projection(self) -> dict:
        """What projecting a model into this dataset leaves for the next
        projection (``game/random_effect.py``
        ``RandomEffectModel.projection_plan``): ``plan``, the last plan
        built into it, and ``outcome``, whether the last projection
        ``built`` that plan or ``reused`` it. It lives as long as this
        object and is not copied with it."""
        return {}

    def scatter_scores(self, per_bucket_scores: Sequence[Array]) -> Array:
        """Assemble a global [n_rows] score vector from per-bucket [E, S]
        scores (padding slots point at the ghost row and are dropped)."""
        return _scatter_slots(
            list(per_bucket_scores), [b.row_ids for b in self.buckets],
            n_rows=self.n_rows)


def down_sample_dataset(
    dataset: RandomEffectDataset, sampler, key
) -> RandomEffectDataset:
    """Down-sample training weights per entity bucket (reference: per-config
    down-sampling applies to random-effect coordinates too). Only
    ``train_weights`` change — scoring weights and the active/passive split
    are untouched, and already-zero (padded/passive) slots stay zero."""
    import jax as _jax

    new_buckets = []
    for i, b in enumerate(dataset.buckets):
        k = _jax.random.fold_in(key, i)
        tw = sampler.down_sample_weights(k, b.labels, b.train_weights)
        new_buckets.append(dataclasses.replace(b, train_weights=tw))
    return dataclasses.replace(dataset, buckets=tuple(new_buckets))


def pearson_scores(
    local: np.ndarray,
    vals: np.ndarray,
    labels_e: np.ndarray,
    n_cols: int,
) -> np.ndarray:
    """|Pearson correlation| of each local feature column with the label over
    one entity's rows, treating absent entries as 0 (sparse semantics —
    reference ⟦LocalDataset.filterFeaturesByPearsonCorrelationScore⟧).
    Assumes each row indexes a column at most once (squares accumulate
    per-entry, so duplicate (row, col) entries would skew the variance).

    Zero-variance columns score 0 (the intercept is force-kept by the
    caller, not through its score).
    """
    s = len(labels_e)
    flat = local.ravel()
    keep = flat < n_cols
    cols = flat[keep]
    v = vals.ravel()[keep]
    y_rep = np.repeat(labels_e, local.shape[1])[keep]
    sum_x = np.bincount(cols, weights=v, minlength=n_cols)
    sum_x2 = np.bincount(cols, weights=v * v, minlength=n_cols)
    sum_xy = np.bincount(cols, weights=v * y_rep, minlength=n_cols)
    sum_y = labels_e.sum()
    sum_y2 = (labels_e * labels_e).sum()
    num = s * sum_xy - sum_x * sum_y
    var_x = s * sum_x2 - sum_x * sum_x
    var_y = s * sum_y2 - sum_y * sum_y
    denom = np.sqrt(np.maximum(var_x, 0.0) * max(var_y, 0.0))
    with np.errstate(invalid="ignore", divide="ignore"):
        corr = np.where(denom > 0, np.abs(num) / np.maximum(denom, 1e-30), 0.0)
    return corr


def build_random_effect_dataset(
    re_type: str,
    entity_keys_per_row: np.ndarray,
    idx: np.ndarray,
    val: np.ndarray,
    labels: np.ndarray,
    global_dim: int,
    weights: Optional[np.ndarray] = None,
    active_bound: Optional[int] = None,
    min_entity_rows: int = 1,
    intercept_index: Optional[int] = None,
    dtype=np.float32,
    max_features_per_entity: Optional[int] = None,
    max_bucket_entities: Optional[int] = None,
    host_resident: bool = False,
) -> RandomEffectDataset:
    """Host-side builder: group rows by entity, project features, bucket+pad.

    Inputs are global ELL arrays (``idx[N, K]`` with ghost == ``global_dim``)
    plus one entity key per row. Entities with fewer than ``min_entity_rows``
    rows are dropped (reference: ``numActiveDataPointsLowerBound``).
    ``intercept_index``, when given, is force-included in every entity's
    subspace so each per-entity model can carry an intercept.

    ``max_features_per_entity`` enables Pearson-correlation feature filtering
    (reference ⟦LocalDataset.filterFeaturesByPearsonCorrelationScore⟧,
    SURVEY.md §2.2): each entity keeps only its ``m`` features most
    |correlated| with the label (ties broken by lower column id; the
    intercept always kept on top of ``m``), shrinking per-entity subspaces
    and bucket padding on wide shards.

    Fully vectorized over entities (VERDICT round-2 weak #7): per-entity
    subspaces come from ONE ``unique`` over (entity, column) pair keys, the
    local remap is ONE ``searchsorted`` against those keys, Pearson sums are
    global ``bincount``s, and bucket packing is flat fancy-index writes —
    no per-entity Python. ``_build_reference_loop`` keeps the original
    entity-at-a-time implementation as the oracle for the equivalence test.

    Scale controls (SURVEY.md §2.6 P6): ``max_bucket_entities`` splits each
    size-class bucket into slices of at most that many entities, and
    ``host_resident=True`` keeps bucket arrays as host numpy — the RE
    trainer then transfers ONE bucket at a time, so peak device residency
    is a single bucket instead of the whole grouped dataset (the knob that
    bounds HBM for config-5-scale GAME).
    """
    if max_bucket_entities is not None and max_bucket_entities < 1:
        raise ValueError(
            f"max_bucket_entities must be >= 1, got {max_bucket_entities}"
        )
    n, k = idx.shape
    idx = np.asarray(idx)
    val = np.asarray(val)
    labels = np.asarray(labels, dtype)
    weights = np.ones(n, dtype) if weights is None else np.asarray(weights, dtype)

    keys, inv = _sorted_factorize(entity_keys_per_row)
    counts_all = np.bincount(inv, minlength=len(keys))
    kept = np.flatnonzero(counts_all >= min_entity_rows)
    e_count = len(kept)
    if e_count == 0:
        return RandomEffectDataset(
            re_type=re_type, buckets=(), entity_keys=[], entity_to_slot={},
            n_rows=n, global_dim=global_dim, bucket_rows=(),
        )
    new_id = np.full(len(keys), -1, np.int64)
    new_id[kept] = np.arange(e_count)
    dense_e = new_id[inv]                       # [n] dense entity id, -1 dropped
    row_kept = dense_e >= 0
    counts = counts_all[kept]                   # [E] rows per kept entity

    # ---- per-entity column subspaces. The DISTINCT (entity, column) pair
    # count is small (≈ E × per-entity support), but a single np.unique
    # with return_inverse over all N·K entry keys materializes ~4 int64
    # arrays of N·K (keys, sort permutation, sorted copy, inverse) — ~20 GB
    # of temporaries at the 50M×13 rehearsal shape, the RE build's RSS
    # peak (VERDICT r4 weak #4). Instead: chunked uniques (each bounded by
    # the chunk), one final unique over the concatenated smalls, then a
    # chunked searchsorted for each entry's pair rank — peak extra memory
    # is one chunk's worth plus the distinct-pair table.
    stride = global_dim + 1
    ent_of_row = dense_e                         # [n], -1 = dropped row
    chunk_rows_ = max(1, min(n, 1 << 22))
    uniq_parts = []
    if intercept_index is not None:
        uniq_parts.append(
            np.arange(e_count, dtype=np.int64) * stride + intercept_index)
    nz_per_ent = np.zeros(e_count, np.int64)
    for lo in range(0, n, chunk_rows_):
        hi = min(lo + chunk_rows_, n)
        ee_c = np.repeat(ent_of_row[lo:hi], k)
        fi_c = idx[lo:hi].ravel().astype(np.int64)
        ok_c = (ee_c >= 0) & (fi_c < global_dim)
        pairs_c = ee_c[ok_c] * stride + fi_c[ok_c]
        uniq_parts.append(np.unique(pairs_c))
        if intercept_index is None:  # counts only feed the empty-entity fix
            nz_per_ent += np.bincount(ee_c[ok_c], minlength=e_count)
    if intercept_index is None:
        # entities with no real entries still need a 1-column subspace ([0])
        empty = np.flatnonzero(nz_per_ent == 0)
        if len(empty):
            uniq_parts.append(empty.astype(np.int64) * stride)
    upairs = np.unique(np.concatenate(uniq_parts))
    del uniq_parts
    ent_of_col = upairs // stride

    # entry_pos: each ok entry's rank in upairs, chunked searchsorted.
    entry_pos_parts = []
    ok_parts = []
    for lo in range(0, n, chunk_rows_):
        hi = min(lo + chunk_rows_, n)
        ee_c = np.repeat(ent_of_row[lo:hi], k)
        fi_c = idx[lo:hi].ravel().astype(np.int64)
        ok_c = (ee_c >= 0) & (fi_c < global_dim)
        pairs_c = ee_c[ok_c] * stride + fi_c[ok_c]
        entry_pos_parts.append(
            np.searchsorted(upairs, pairs_c).astype(np.int32))
        ok_parts.append(ok_c)
    entry_pos = np.concatenate(entry_pos_parts) if entry_pos_parts else \
        np.zeros(0, np.int32)
    entry_ok = np.concatenate(ok_parts) if ok_parts else np.zeros(0, bool)
    del entry_pos_parts, ok_parts
    # int32 throughout: these are the N·K-sized survivors, and at the 50M
    # rehearsal shape every int64 copy here is 5.2 GB of RSS.
    ee = np.repeat(ent_of_row.astype(np.int32), k)  # entity per ELL entry

    if max_features_per_entity is not None:
        chosen = _choose_pairs_by_pearson(
            upairs, ent_of_col, stride, entry_pos, entry_ok,
            val.ravel(), labels, dense_e, counts, e_count,
            max_features_per_entity, intercept_index,
        )
        # remap surviving pair ids to their rank in the filtered set
        new_pos = np.cumsum(chosen, dtype=np.int64) - 1
        survived = chosen[entry_pos]
        entry_pos = np.where(survived, new_pos[entry_pos], -1)
        upairs, ent_of_col = upairs[chosen], ent_of_col[chosen]
    ncols = np.bincount(ent_of_col, minlength=e_count).astype(np.int64)
    col_off = np.zeros(e_count + 1, np.int64)
    np.cumsum(ncols, out=col_off[1:])

    # ---- local remap straight from the unique inverse (no searchsorted)
    ee_safe = np.maximum(ee, 0)
    local_flat = ncols[ee_safe].astype(np.int32)     # default: local ghost
    ok_ix = np.flatnonzero(entry_ok)
    hit_ok = entry_pos >= 0
    local_flat[ok_ix[hit_ok]] = (
        entry_pos[hit_ok] - col_off[ee[ok_ix[hit_ok]]]
    ).astype(np.int32)
    local = local_flat.reshape(n, k)
    hit = np.zeros(n * k, bool)
    hit[ok_ix[hit_ok]] = True
    val_eff = np.where(hit.reshape(n, k), val, 0.0).astype(val.dtype)

    # ---- bucket by (pow2 samples, pow2 local dim); dense ids in the same
    # (bucket-sorted, then ascending-entity) order as the reference loop
    s_pad_e = _next_pow2_vec(counts)
    p_pad_e = _next_pow2_vec(ncols)
    ent_sort = np.lexsort((np.arange(e_count), p_pad_e, s_pad_e))
    dense_of = np.empty(e_count, np.int64)
    dense_of[ent_sort] = np.arange(e_count)          # entity -> dense id

    # group boundaries of (s_pad, p_pad) buckets over the sorted entities
    sp_sorted = np.stack([s_pad_e[ent_sort], p_pad_e[ent_sort]], axis=1)
    bucket_break = np.any(np.diff(sp_sorted, axis=0) != 0, axis=1)
    bucket_starts = np.concatenate([[0], np.flatnonzero(bucket_break) + 1, [e_count]])

    # rows re-sorted by dense id (stable keeps original row order per entity)
    row_dense = np.where(row_kept, dense_of[np.maximum(dense_e, 0)], e_count)
    row_order = np.argsort(row_dense, kind="stable")[: int(row_kept.sum())]
    rcounts = counts[ent_sort]                        # rows per dense id
    rstarts = np.zeros(e_count + 1, np.int64)
    np.cumsum(rcounts, out=rstarts[1:])
    within_row = np.arange(len(row_order)) - rstarts[row_dense[row_order]]

    # column entries re-sorted by dense id
    col_dense = dense_of[ent_of_col]
    col_order = np.argsort(col_dense, kind="stable")
    ccounts = ncols[ent_sort]
    cstarts = np.zeros(e_count + 1, np.int64)
    np.cumsum(ccounts, out=cstarts[1:])
    within_col = np.arange(len(col_order)) - cstarts[col_dense[col_order]]
    cols_flat = upairs % stride

    buckets = []
    bucket_rows = []
    entity_keys_out = list(keys[kept][ent_sort])
    entity_to_slot = {}
    for b, (mb, me) in enumerate(zip(bucket_starts[:-1], bucket_starts[1:])):
        ecount = int(me - mb)
        s_pad = int(sp_sorted[mb, 0])
        p_pad = int(sp_sorted[mb, 1])
        b_idx = np.full((ecount, s_pad, k), p_pad, np.int32)
        b_val = np.zeros((ecount, s_pad, k), dtype)
        b_lab = np.zeros((ecount, s_pad), dtype)
        b_w = np.zeros((ecount, s_pad), dtype)
        b_tw = np.zeros((ecount, s_pad), dtype)
        b_rows = np.full((ecount, s_pad), n, np.int32)
        b_proj = np.full((ecount, p_pad), global_dim, np.int32)

        rsl = slice(rstarts[mb], rstarts[me])
        rows_b = row_order[rsl]                       # original row ids
        lane_r = row_dense[rows_b] - mb
        wr = within_row[rsl]
        b_idx[lane_r, wr] = local[rows_b]
        b_val[lane_r, wr] = val_eff[rows_b]
        b_lab[lane_r, wr] = labels[rows_b]
        b_w[lane_r, wr] = weights[rows_b]
        tw = weights[rows_b].copy()
        if active_bound is not None:
            tw[wr >= active_bound] = 0.0              # passive rows
        b_tw[lane_r, wr] = tw
        b_rows[lane_r, wr] = rows_b

        csl = slice(cstarts[mb], cstarts[me])
        centries = col_order[csl]
        b_proj[col_dense[centries] - mb, within_col[csl]] = cols_flat[centries]

        conv = (lambda a: a) if host_resident else jnp.asarray
        cap = max_bucket_entities or ecount
        for lo in range(0, ecount, cap):
            hi = min(lo + cap, ecount)
            bi = len(buckets)
            for lane in range(lo, hi):
                entity_to_slot[int(mb + lane)] = (bi, lane - lo)
            buckets.append(EntityBucket(
                idx=conv(b_idx[lo:hi]), val=conv(b_val[lo:hi]),
                labels=conv(b_lab[lo:hi]), weights=conv(b_w[lo:hi]),
                train_weights=conv(b_tw[lo:hi]),
                row_ids=conv(b_rows[lo:hi]),
                proj=conv(b_proj[lo:hi]),
                entity_ids=conv(
                    np.arange(mb + lo, mb + hi, dtype=np.int32)
                ),
            ))
            bucket_rows.append(int(rstarts[mb + hi] - rstarts[mb + lo]))

    return RandomEffectDataset(
        re_type=re_type,
        buckets=tuple(buckets),
        entity_keys=entity_keys_out,
        entity_to_slot=entity_to_slot,
        n_rows=n,
        global_dim=global_dim,
        bucket_rows=tuple(bucket_rows),
    )


def _next_pow2_vec(x: np.ndarray) -> np.ndarray:
    x = np.maximum(np.asarray(x, np.int64), 1)
    return 1 << np.ceil(np.log2(x)).astype(np.int64)


def _sorted_factorize(keys_per_row: np.ndarray):
    """(sorted unique keys, inverse) — np.unique semantics, hash-based speed.

    np.unique comparison-sorts the raw key column; for millions of object
    strings that sort IS the old builder's profile hot spot. pandas'
    hash-based factorize + a sort of the (small) unique set is ~5x faster
    and produces the identical (sorted-unique, inverse) pair."""
    try:
        import pandas as pd
    except ImportError:  # pragma: no cover - pandas ships in the image
        return np.unique(keys_per_row, return_inverse=True)
    codes, uniq = pd.factorize(keys_per_row, sort=True)
    if (codes < 0).any():
        # pd.factorize drops NaN/None (code -1); np.unique keeps them as
        # keys — fall back so missing-key behavior matches.
        return np.unique(keys_per_row, return_inverse=True)
    return np.asarray(uniq), codes.astype(np.int64)


def _choose_pairs_by_pearson(
    upairs, ent_of_col, stride, entry_pos, entry_ok, flat_val,
    labels, dense_e, counts, e_count, max_features, intercept_index,
):
    """Vectorized Pearson top-m per entity over the (entity, col) pair keys;
    returns the keep mask over ``upairs``.

    Matches ``pearson_scores`` semantics (absent entries are zeros) with
    global bincounts instead of per-entity passes; entities at or under the
    cap keep their full subspace, ties break toward lower column ids, and
    the intercept is force-kept on top of ``m``.
    """
    pos = entry_pos
    v_raw = flat_val[entry_ok]                       # source dtype, like
    v = np.asarray(v_raw, np.float64)                # pearson_scores' v
    y_row = np.asarray(labels, np.float64)
    k = len(entry_ok) // dense_e.shape[0]
    y_ent = np.repeat(y_row, k)[entry_ok]            # label of each entry's row
    npairs = len(upairs)
    sum_x = np.bincount(pos, weights=v, minlength=npairs)
    # v*v in the SOURCE dtype (f32 upstream) so scores are bit-identical to
    # pearson_scores — exact ties must break the same way in both builders.
    sum_x2 = np.bincount(pos, weights=np.asarray(v_raw * v_raw, np.float64),
                         minlength=npairs)
    sum_xy = np.bincount(pos, weights=v * y_ent, minlength=npairs)
    row_of_kept = dense_e >= 0
    sum_y_e = np.bincount(dense_e[row_of_kept], weights=y_row[row_of_kept],
                          minlength=e_count)
    sum_y2_e = np.bincount(dense_e[row_of_kept],
                           weights=y_row[row_of_kept] ** 2, minlength=e_count)
    s_e = counts.astype(np.float64)
    s, sy, sy2 = s_e[ent_of_col], sum_y_e[ent_of_col], sum_y2_e[ent_of_col]
    num = s * sum_xy - sum_x * sy
    var_x = s * sum_x2 - sum_x * sum_x
    var_y = s * sy2 - sy * sy
    denom = np.sqrt(np.maximum(var_x, 0.0) * np.maximum(var_y, 0.0))
    with np.errstate(invalid="ignore", divide="ignore"):
        score = np.where(denom > 0, np.abs(num) / np.maximum(denom, 1e-30), 0.0)

    cols = upairs % stride
    rank_order = np.lexsort((cols, -score, ent_of_col))
    off = np.zeros(e_count + 1, np.int64)
    np.cumsum(np.bincount(ent_of_col, minlength=e_count), out=off[1:])
    rank = np.empty(npairs, np.int64)
    rank[rank_order] = np.arange(npairs) - off[ent_of_col[rank_order]]
    over_cap = (off[1:] - off[:-1]) > max_features     # per entity
    chosen = ~over_cap[ent_of_col] | (rank < max_features)
    if intercept_index is not None:
        chosen |= cols == intercept_index
    return chosen


def _build_reference_loop(
    re_type: str,
    entity_keys_per_row: np.ndarray,
    idx: np.ndarray,
    val: np.ndarray,
    labels: np.ndarray,
    global_dim: int,
    weights: Optional[np.ndarray] = None,
    active_bound: Optional[int] = None,
    min_entity_rows: int = 1,
    intercept_index: Optional[int] = None,
    dtype=np.float32,
    max_features_per_entity: Optional[int] = None,
) -> RandomEffectDataset:
    """Original entity-at-a-time builder, kept as the oracle for the
    vectorized path's equivalence test (tests/test_random_effect.py)."""
    n, k = idx.shape
    labels = np.asarray(labels, dtype)
    weights = np.ones(n, dtype) if weights is None else np.asarray(weights, dtype)

    keys, inv = np.unique(entity_keys_per_row, return_inverse=True)
    order = np.argsort(inv, kind="stable")
    counts = np.bincount(inv, minlength=len(keys))

    # Per-entity row lists in original order; drop tiny entities.
    starts = np.zeros(len(keys) + 1, np.int64)
    np.cumsum(counts, out=starts[1:])
    kept = [e for e in range(len(keys)) if counts[e] >= min_entity_rows]

    # Build per-entity projections + local data (numpy, then bucketed).
    entities = []
    for e in kept:
        rows = order[starts[e]:starts[e + 1]]
        e_idx = idx[rows]             # [s, k] global ids (ghost == global_dim)
        e_val = val[rows]
        cols = np.unique(e_idx[e_idx < global_dim])
        if intercept_index is not None and intercept_index not in cols:
            cols = np.sort(np.append(cols, intercept_index))
        if len(cols) == 0:
            cols = np.asarray([0], np.int64)
        # local remap: ghost -> len(cols) (local ghost)
        local = np.searchsorted(cols, np.minimum(e_idx, global_dim - 1)).astype(np.int32)
        local = np.where(e_idx >= global_dim, len(cols), local)

        if (
            max_features_per_entity is not None
            and len(cols) > max_features_per_entity
        ):
            scores = pearson_scores(
                local, e_val, np.asarray(labels[rows], np.float64), len(cols)
            )
            # Top-m by |corr|, ties to the lower column id (deterministic);
            # the intercept is force-kept regardless of its (zero) score.
            order_by_score = np.lexsort((np.arange(len(cols)), -scores))
            chosen = np.zeros(len(cols), bool)
            chosen[order_by_score[:max_features_per_entity]] = True
            if intercept_index is not None:
                at = int(np.searchsorted(cols, intercept_index))
                if at < len(cols) and cols[at] == intercept_index:
                    chosen[at] = True
            cols = cols[chosen]
            in_kept = np.isin(e_idx, cols)
            local = np.searchsorted(
                cols, np.minimum(e_idx, global_dim - 1)
            ).astype(np.int32)
            local = np.where(in_kept, local, len(cols))
            e_val = np.where(in_kept, e_val, 0.0)
        entities.append((e, rows, cols, local, e_val))

    # Bucket by (pow2 samples, pow2 local dim).
    bucket_map: dict[tuple[int, int], list] = {}
    for ent in entities:
        s_cap = len(ent[1])
        p_cap = len(ent[2])
        key = (_next_pow2(s_cap), _next_pow2(p_cap))
        bucket_map.setdefault(key, []).append(ent)

    buckets = []
    bucket_rows = []
    entity_keys_out = []
    entity_to_slot = {}
    for (s_pad, p_pad), members in sorted(bucket_map.items()):
        ecount = len(members)
        b_idx = np.full((ecount, s_pad, k), p_pad, np.int32)   # local ghost
        b_val = np.zeros((ecount, s_pad, k), dtype)
        b_lab = np.zeros((ecount, s_pad), dtype)
        b_w = np.zeros((ecount, s_pad), dtype)
        b_tw = np.zeros((ecount, s_pad), dtype)
        b_rows = np.full((ecount, s_pad), n, np.int32)         # global ghost row
        b_proj = np.full((ecount, p_pad), global_dim, np.int32)
        b_eids = np.full((ecount,), -1, np.int32)
        for lane, (e, rows, cols, local, vals) in enumerate(members):
            s = len(rows)
            b_idx[lane, :s] = local
            b_val[lane, :s] = vals
            b_lab[lane, :s] = labels[rows]
            b_w[lane, :s] = weights[rows]
            tw = weights[rows].copy()
            if active_bound is not None and s > active_bound:
                tw[active_bound:] = 0.0      # passive rows: scored, not trained
            b_tw[lane, :s] = tw
            b_rows[lane, :s] = rows
            b_proj[lane, : len(cols)] = cols
            dense_id = len(entity_keys_out)
            b_eids[lane] = dense_id
            entity_keys_out.append(keys[e])
            entity_to_slot[dense_id] = (len(buckets), lane)
        buckets.append(EntityBucket(
            idx=jnp.asarray(b_idx), val=jnp.asarray(b_val),
            labels=jnp.asarray(b_lab), weights=jnp.asarray(b_w),
            train_weights=jnp.asarray(b_tw), row_ids=jnp.asarray(b_rows),
            proj=jnp.asarray(b_proj), entity_ids=jnp.asarray(b_eids),
        ))
        bucket_rows.append(sum(len(ent[1]) for ent in members))

    return RandomEffectDataset(
        re_type=re_type,
        buckets=tuple(buckets),
        entity_keys=list(entity_keys_out),
        entity_to_slot=entity_to_slot,
        n_rows=n,
        global_dim=global_dim,
        bucket_rows=tuple(bucket_rows),
    )
