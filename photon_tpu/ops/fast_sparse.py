"""The ``fast`` formulation of the sparse pass: MXU-friendly matvec/rmatvec.

One of the two formulations behind ``SparseFeatures`` (``data/batch.py``):
the ops run this one where the tables built here are attached, and the
``plain`` gather / ``segment_sum`` where they are not. This module replaces
XLA's generic gather and scatter-add with formulations it compiles to
vector/MXU code. What a pass costs on the chip, operation by operation, is
in ``PERF.md`` §5; which formulation a run's programs hold, in the counter
``sparse_op_traces_total``.

* ``matvec`` (and the gather side of ``rmatvec``): **row-slice gather +
  lane-select**.  The coefficient vector is viewed as ``[D/128, 128]``; each
  entry fetches its 128-wide row slice (``w2[idx >> 7]`` — a contiguous-slice
  gather XLA vectorizes) and selects its lane with a fused
  ``where(lo == iota)`` reduction.
  ``matvec`` selects on the flat ``[rows*nnz, 128]`` array the gather writes: the
  TPU tiles the last two dimensions (8, 128), so a ``[N, K, 128]`` view of it
  is a physical copy whenever K is no multiple of 8 (76 pads to 80).

* ``rmatvec`` reduction: **column-sorted one-hot matmul**.  Entries are
  pre-sorted (host-side, once — indices are static data) by column and grouped
  into rows of a ``[B, Q]`` table whose columns all fall in one aligned
  128-column range.  The scatter-add then becomes
  ``einsum("bql,bq->bl", onehot(col & 127), contrib)`` — an MXU contraction
  with the one-hot fused from an int8 compare, never materialized — followed
  by a tiny sorted segment-sum over ranges.

The plan arrays are built on the host (NumPy), a pure function of the feature
object, and ride along as an optional pytree on ``SparseFeatures``; all ops
stay pure/jittable. ``build_fast_aux`` itself keeps nothing: each call is a
build. "Once per dataset" is the caller's to hold — ``GameEstimator`` keeps
the tables with its prepared bundle, so every fit on that bundle after the
first attaches them; the one-shot drivers build once a run.
Ghost-padding entries (column id == dim) are mapped to a zero row with value
0, so no masking is needed in the hot loop.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

LANE = 128
# The digit streams cover whole blocks of this many rows, so the flat
# ``[rows*nnz]`` lane-select result is whole 1024-element tiles at any nnz:
# off that grid the TPU compiler's ``[rows*nnz] -> [rows, nnz]`` reshape takes
# minutes to compile (70 s at 1,002,640 x 8, 1 s at 1,003,520 x 8).
ROW_PAD = 1024


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class FastSparseAux:
    """Static auxiliary layouts for the fast paths.

    Row-major digit split (for matvec's row-slice gather), flat in the
    order of ``val.ravel()`` so the program reshapes no index stream, over
    ``Np`` rows (N rounded up to ``ROW_PAD``, the added rows all ghosts):
      ``hi[Np*K]`` int16/int32 — column id >> 7 (ghost entries point at the
      zero row appended to the coefficient table; int16 when the block count
      fits, halving that index stream's HBM traffic); ``lo[Np*K]`` int8 —
      column & 127.

    Column-sorted table (for rmatvec's one-hot reduce): ``B`` rows of capacity
    ``Q``; every slot in row b carries an entry whose column lies in the
    128-aligned range ``cs_range[b]``. ``cs_rhi``/``cs_rlo`` split the entry's
    ROW id for the dz gather; ``cs_clo`` is its lane within the range;
    ``cs_val`` is the feature value (0 in padding slots).
    """

    hi: Array        # [Np*K] int16 or int32 (see _digit_dtype)
    lo: Array        # [Np*K] int8
    cs_rhi: Array    # [B, Q] int16 or int32
    cs_rlo: Array    # [B, Q] int8
    cs_clo: Array    # [B, Q] int8
    cs_val: Array    # [B, Q] float32
    cs_range: Array  # [B] int32 (sorted; == n_ranges for padding rows)
    n_ranges: int = dataclasses.field(metadata=dict(static=True))
    n_row_blocks: int = dataclasses.field(metadata=dict(static=True))


def _digit_dtype(n_blocks: int):
    """Narrowest int dtype for a >>7 digit stream with ``n_blocks`` valid
    block ids PLUS the ghost/zero block. The digit arrays are pure HBM
    traffic in the hot loop, so int16 (feature spaces <= 128*32767 ≈ 4.19M,
    row spaces likewise) halves their share of the stream; beyond that the
    layout transparently stays int32."""
    return np.int16 if n_blocks + 1 <= np.iinfo(np.int16).max else np.int32


def build_fast_aux(
    idx: np.ndarray, val: np.ndarray, dim: int, q_capacity: int = 2048
) -> FastSparseAux:
    """Host-side construction of both static layouts from ELL arrays.

    ``idx``/``val`` are the ``SparseFeatures`` arrays ([N, K], ghost column ==
    ``dim`` with value 0). ``q_capacity`` bounds the column-table row width; a
    popular column range simply occupies several table rows (so skewed or
    dense columns — e.g. the intercept — need no special casing).
    """
    idx = np.asarray(idx)
    val = np.asarray(val)
    n, k = idx.shape
    n_row_blocks = -(-n // LANE)
    n_col_blocks = -(-dim // LANE)

    # Row-major digit split, flat; ghost entries, and the ghost rows that
    # fill the stream to whole ROW_PAD blocks, -> appended zero row of w table.
    ghost = idx >= dim
    n_pad = -(-n // ROW_PAD) * ROW_PAD
    hi = np.full((n_pad, k), n_col_blocks, _digit_dtype(n_col_blocks))
    lo = np.zeros((n_pad, k), np.int8)
    hi[:n] = np.where(ghost, n_col_blocks, idx >> 7)
    lo[:n] = np.where(ghost, 0, idx & 127)

    # Column-sorted table.
    flat_col = idx.ravel()
    keep = flat_col < dim
    cols = flat_col[keep].astype(np.int64)
    rows = np.repeat(np.arange(n, dtype=np.int64), k)[keep]
    vals = val.ravel()[keep]
    order = np.argsort(cols, kind="stable")
    cols, rows, vals = cols[order], rows[order], vals[order]

    rng_of = (cols >> 7).astype(np.int64)
    counts = np.bincount(rng_of, minlength=n_col_blocks)
    rows_per_range = np.maximum(1, -(-counts // q_capacity))
    b_total = int(rows_per_range.sum())
    b_pad = -(-b_total // 8) * 8

    cs_rhi = np.zeros((b_pad, q_capacity), _digit_dtype(n_row_blocks))
    cs_rlo = np.zeros((b_pad, q_capacity), np.int8)
    cs_clo = np.zeros((b_pad, q_capacity), np.int8)
    cs_val = np.zeros((b_pad, q_capacity), np.float32)
    cs_range = np.full((b_pad,), n_col_blocks, np.int32)

    starts = np.concatenate([[0], np.cumsum(counts)])
    b = 0
    for r in range(n_col_blocks):
        lo_e, hi_e = int(starts[r]), int(starts[r + 1])
        for off in range(lo_e, max(hi_e, lo_e + 1), q_capacity):
            end = min(off + q_capacity, hi_e)
            m = end - off
            if m > 0:
                sl = slice(off, end)
                cs_rhi[b, :m] = (rows[sl] >> 7).astype(cs_rhi.dtype)
                cs_rlo[b, :m] = (rows[sl] & 127).astype(np.int8)
                cs_clo[b, :m] = (cols[sl] & 127).astype(np.int8)
                cs_val[b, :m] = vals[sl]
            cs_range[b] = r
            b += 1

    return FastSparseAux(
        hi=jnp.asarray(hi.ravel()),
        lo=jnp.asarray(lo.ravel()),
        cs_rhi=jnp.asarray(cs_rhi),
        cs_rlo=jnp.asarray(cs_rlo),
        cs_clo=jnp.asarray(cs_clo),
        cs_val=jnp.asarray(cs_val),
        cs_range=jnp.asarray(cs_range),
        n_ranges=n_col_blocks,
        n_row_blocks=n_row_blocks,
    )


def _lane_iota() -> Array:
    return jax.lax.broadcasted_iota(jnp.int8, (1, LANE), 1)


@functools.partial(jax.jit, static_argnames="dim")
def matvec_fast(aux: FastSparseAux, val: Array, w: Array, dim: int) -> Array:
    """z[i] = Σ_k val[i,k] · w[idx[i,k]] via row-slice gather + lane select.

    One program also where the caller runs op by op (the coordinate scorers):
    eagerly every ``[entries, 128]`` intermediate below would be materialized.
    """
    nblk = -(-dim // LANE)
    w2 = jnp.pad(w, (0, nblk * LANE - dim)).reshape(nblk, LANE)
    w2 = jnp.concatenate([w2, jnp.zeros((1, LANE), w.dtype)])  # ghost row
    n, k = val.shape
    rows = w2[aux.hi]                                  # [Np*K, 128]
    sel = jnp.where(aux.lo[:, None] == _lane_iota(), rows, 0.0)
    picked = jnp.sum(sel, axis=-1).reshape(-1, k)      # [Np, K]
    # Narrow-stored values (bfloat16 via with_value_dtype) upcast on load:
    # the accumulation stays in w's precision, only the HBM stream shrinks.
    valf = val.astype(jnp.promote_types(val.dtype, w.dtype))
    valf = jnp.pad(valf, ((0, picked.shape[0] - n), (0, 0)))
    return jnp.sum(picked * valf, axis=-1)[:n]


def rmatvec_fast(
    aux: FastSparseAux, dz: Array, dim: int, square_vals: bool = False
) -> Array:
    """g[c] = Σ_{entries of column c} val · dz[row] — scatter-free.

    dz is gathered by row-slice + lane select (same trick as matvec), the
    per-column reduction is a fused one-hot MXU contraction per 128-column
    range, and ranges assemble with one small sorted segment-sum.
    """
    n = dz.shape[0]
    nb = aux.n_row_blocks
    dz2 = jnp.pad(dz, (0, nb * LANE - n)).reshape(nb, LANE)
    rows = dz2[aux.cs_rhi]                             # [B, Q, 128]
    iota = _lane_iota()
    dz_at = jnp.sum(jnp.where(aux.cs_rlo[..., None] == iota, rows, 0.0), axis=-1)
    # Upcast BEFORE squaring: bfloat16-stored values must square in the
    # accumulation precision, not in 8 mantissa bits.
    csv = aux.cs_val.astype(jnp.promote_types(aux.cs_val.dtype, dz.dtype))
    v = csv * csv if square_vals else csv
    contrib = dz_at * v                                # [B, Q]
    oh = jnp.where(aux.cs_clo[..., None] == iota, 1.0, 0.0)
    out_b = jnp.einsum(
        "bql,bq->bl", oh, contrib, preferred_element_type=jnp.float32
    )                                                  # [B, 128]
    out_r = jax.ops.segment_sum(
        out_b, aux.cs_range, num_segments=aux.n_ranges + 1,
        indices_are_sorted=True,
    )[: aux.n_ranges]
    return out_r.reshape(-1)[:dim]


# Note: no custom_vjp wrapper is needed — every optimizer-facing path
# (GLMObjective.value_and_grad / hessian_vector / hessian_diagonal) is
# hand-fused and calls matvec/rmatvec explicitly, so autodiff never
# differentiates through these functions.
