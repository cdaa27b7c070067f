"""Pallas kernels for the ELL sparse hot ops (matvec / rmatvec).

**Status (PR 22): these kernels run only in the Pallas interpreter. The TPU
compiler refuses them** — asked to compile ``_run_op`` for a described v5e
(``tests/test_chip_compile.py``) it says ``Unimplemented primitive in
Pallas TPU lowering for KernelType.TC: dynamic_slice`` (the value-level
slices in ``_gather_onehot_kernel.chunk``), and behind that the design's
central step is refused too: a same-shape ``take_along_axis`` over a
``[2048,128]`` / ``[4096,128]`` table is ``Not implemented: Multiple source
vregs along gather dimension`` — the hardware gather reads within one
8x128 register, not across a VMEM table. So nothing attaches these tables
by default (``SparseFeatures.with_accelerator_paths`` attaches the XLA fast
path); the module stays as the starting point for ROADMAP S1/D2. The
paragraphs below describe the intent, not a measured result.

Why: the XLA fast paths in :mod:`photon_tpu.ops.fast_sparse` still run ~200x
off the HBM roofline (BENCH_DETAILS.json ``fraction_of_roofline`` ~0.005 on
v5e) because their gathers materialize a 128-wide row slice per entry —
8.6 GB of traffic for a 200 MB dataset. These kernels cut the blow-up by
keeping every intermediate in VMEM and doing the per-entry lookup with the
TPU's hardware ``dynamic_gather`` (Mosaic lowers a same-shape
``jnp.take_along_axis(table, idx, axis=0)`` to one vector gather).

Design (SURVEY.md §7 hard-part #2, VERDICT round-2 ask #2):

* Sparsity is STATIC per dataset, so ALL routing is precomputed on host.
  Entries are packed into slot tables of shape ``[S, 128]``:

  - ``rmatvec`` (g = Aᵀdz): slots grouped by 128-wide COLUMN range; within a
    group a slot sits at lane ``row & 127``, so the dz lookup is exactly the
    hardware gather ``dz2[rhi[s, l], l]``. The per-group reduce over columns
    is a fused one-hot MXU contraction per 8-sublane chunk (chunks never
    cross groups), finished by one tiny sorted ``segment_sum`` outside the
    kernel.
  - ``matvec`` (z = Aw): the exact mirror — slots grouped by 128-row RANGE,
    lane ``col & 127`` so the coefficient lookup is ``w2[chi[s, l], l]``,
    one-hot reduce over ``row & 127``.

* Ghost/padding slots carry value 0 and index 0 — they contribute nothing
  and need no masking in the hot loop.

* Datasets larger than one VMEM-resident lookup table (512K rows for the dz
  table, 256K features for the w table) are CHUNKED: entries are split by
  row range (rmatvec) / column range (matvec), each chunk packs its own slot
  tables indexed against its slice of the lookup vector, and the op sums the
  per-chunk group partials — same kernels, one ``pallas_call`` per chunk.
  A ``max_table_bytes`` budget bounds total table memory (group padding is
  per-chunk, so extreme row-chunking of a very wide dataset can inflate it);
  over budget, construction raises and ``with_pallas_path`` falls back to
  the XLA fast path.

Layouts ride on ``SparseFeatures.pallas`` (see ``with_pallas_path``, an
explicit opt-in); the kernels are f32-only, off-TPU the XLA path serves
(tests run them in Pallas interpret mode on CPU), and on a TPU backend
they are compiled — never interpreted — and so fail loudly today.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

Array = jax.Array

LANE = 128
CHUNK = 8              # sublanes per one-hot MXU chunk; groups pad to this
TABLE_SUBLANES = {
    "rmatvec": 4096,   # dz table [4096, 128] -> up to 512K rows per chunk
    "matvec": 2048,    # w table [2048, 128] -> up to 256K features
}


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class _OpTables:
    """Slot tables for one direction. All are [S, 128] with S a multiple of
    the block sublane count; ``chunk_group`` is [S / CHUNK] sorted group ids
    (ghost group == n_groups)."""

    hi: Array           # int32 — table-sublane index fed to the hw gather
    lo: Array           # int32 — one-hot key (col&127 / row&127)
    val: Array          # f32 — feature value (0 in padding slots)
    chunk_group: Array  # int32 [S/CHUNK]
    n_groups: int = dataclasses.field(metadata=dict(static=True))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PallasSparseAux:
    """Static Pallas layouts for both ops of one dataset.

    ``rmat``/``mat`` hold one table set per non-empty chunk (row chunks of
    512K for rmatvec, column chunks of 256K for matvec); ``rmat_chunks`` /
    ``mat_chunks`` are the matching chunk indices into the dz / w vector
    (static — chunk boundaries are compile-time slices)."""

    rmat: tuple
    mat: tuple
    rmat_chunks: tuple = dataclasses.field(metadata=dict(static=True))
    mat_chunks: tuple = dataclasses.field(metadata=dict(static=True))
    n_rows: int = dataclasses.field(metadata=dict(static=True))
    dim: int = dataclasses.field(metadata=dict(static=True))


def _pack_tables(
    group: np.ndarray,     # per entry: reduce-group id (sorted not required)
    lane: np.ndarray,      # per entry: slot lane (gather alignment)
    hi: np.ndarray,        # per entry: table sublane for the hw gather
    lo: np.ndarray,        # per entry: one-hot key within the group
    val: np.ndarray,
    n_groups: int,
    block_sublanes: int,
) -> _OpTables:
    """Pack entries into lane-aligned slot tables, greedily stacking each
    (group, lane) run into sublanes; groups pad to CHUNK sublanes, the whole
    table pads to a multiple of ``block_sublanes``."""
    order = np.lexsort((lane, group))
    group, lane, hi, lo, val = (a[order] for a in (group, lane, hi, lo, val))
    # rank of each entry within its (group, lane) run = its sublane offset
    gl = group.astype(np.int64) * LANE + lane
    new_run = np.concatenate([[True], gl[1:] != gl[:-1]])
    run_start = np.maximum.accumulate(np.where(new_run, np.arange(len(gl)), 0))
    sub_in_run = np.arange(len(gl)) - run_start
    # sublanes needed per group = max run length in that group
    need = np.zeros(n_groups, np.int64)
    np.maximum.at(need, group, sub_in_run + 1)
    need = -(-need // CHUNK) * CHUNK                     # pad to CHUNK
    g_off = np.zeros(n_groups + 1, np.int64)
    np.cumsum(need, out=g_off[1:])
    total = int(-(-g_off[-1] // block_sublanes) * block_sublanes)

    t_hi = np.zeros((total, LANE), np.int32)
    t_lo = np.zeros((total, LANE), np.int32)
    t_val = np.zeros((total, LANE), np.float32)
    srow = g_off[group] + sub_in_run
    t_hi[srow, lane] = hi
    t_lo[srow, lane] = lo
    t_val[srow, lane] = val

    cg = np.full(total // CHUNK, n_groups, np.int32)     # ghost group at end
    used = np.repeat(np.arange(n_groups, dtype=np.int32), need // CHUNK)
    cg[: len(used)] = used
    # numpy for now: the caller budget-checks total bytes across all chunks
    # BEFORE anything is uploaded to device memory.
    return _OpTables(hi=t_hi, lo=t_lo, val=t_val, chunk_group=cg,
                     n_groups=n_groups)


def _np_bytes(t: _OpTables) -> int:
    return t.hi.nbytes + t.lo.nbytes + t.val.nbytes + t.chunk_group.nbytes


def _to_device(t: _OpTables) -> _OpTables:
    return _OpTables(
        hi=jnp.asarray(t.hi), lo=jnp.asarray(t.lo), val=jnp.asarray(t.val),
        chunk_group=jnp.asarray(t.chunk_group), n_groups=t.n_groups,
    )


def _chunked_tables(
    split_key: np.ndarray,      # per entry: chunk index (row or col chunk)
    chunk_elems: int,           # rows/cols covered by one chunk
    group: np.ndarray,
    lane: np.ndarray,
    hi_global: np.ndarray,      # hi before localizing to the chunk's slice
    lo: np.ndarray,
    val: np.ndarray,
    n_groups: int,
    block_sublanes: int,
) -> tuple[list, list]:
    """Pack one table set per non-empty chunk; ``hi`` is localized to the
    chunk's slice of the lookup vector (its table sublane index)."""
    # One stable sort partitions all entries into contiguous chunk runs
    # (each entry gathered once) instead of a full rescan per chunk.
    order = np.argsort(split_key, kind="stable")
    sk = split_key[order]
    uniq, starts = np.unique(sk, return_index=True)
    bounds = np.append(starts, len(sk))
    tables, chunks = [], []
    for c, lo_i, hi_i in zip(uniq, bounds[:-1], bounds[1:]):
        sl = order[lo_i:hi_i]
        tables.append(_pack_tables(
            group=group[sl], lane=lane[sl],
            hi=hi_global[sl] - int(c) * (chunk_elems // LANE), lo=lo[sl],
            val=val[sl], n_groups=n_groups, block_sublanes=block_sublanes,
        ))
        chunks.append(int(c))
    return tables, chunks


def build_pallas_aux(
    idx: np.ndarray, val: np.ndarray, dim: int,
    max_table_bytes: int = 2 << 30,
) -> PallasSparseAux:
    """Host-side construction of both directions' tables from ELL arrays
    (``idx[N, K]`` with ghost column == ``dim``, value 0). Datasets beyond
    one chunk (512K rows / 256K features) split into per-chunk tables;
    raises ``ValueError`` if the packed tables would exceed
    ``max_table_bytes`` (callers fall back to the XLA fast path)."""
    idx = np.asarray(idx)
    val = np.asarray(val, np.float32)
    n, k = idx.shape
    # Cheap lower bound BEFORE any packing: each real entry occupies one
    # 12-byte slot (hi+lo+val) in each direction's tables, so a dataset that
    # cannot fit is rejected in O(1) instead of after two full lexsorts and
    # multi-GB transient allocations.
    if 24 * n * k > max_table_bytes * 4:  # k includes ghost padding; x4 slack
        if 24 * int(np.count_nonzero(idx < dim)) > max_table_bytes:
            raise ValueError(
                f"Pallas slot tables need >= 24 bytes/entry x ~{n * k} "
                f"entries (> {max_table_bytes / 1e9:.2f} GB budget); "
                "falling back to the XLA fast path"
            )
    flat = idx.ravel().astype(np.int64)
    keep = flat < dim
    col = flat[keep]
    row = np.repeat(np.arange(n, dtype=np.int64), k)[keep]
    v = val.ravel()[keep]

    n_col_groups = -(-dim // LANE)
    n_row_groups = -(-n // LANE)
    row_chunk_elems = TABLE_SUBLANES["rmatvec"] * LANE
    col_chunk_elems = TABLE_SUBLANES["matvec"] * LANE

    rmat, rmat_chunks = _chunked_tables(
        split_key=row // row_chunk_elems, chunk_elems=row_chunk_elems,
        group=(col >> 7), lane=(row & 127).astype(np.int64),
        hi_global=(row >> 7).astype(np.int64), lo=(col & 127).astype(np.int64),
        val=v, n_groups=n_col_groups,
        block_sublanes=TABLE_SUBLANES["rmatvec"],
    )
    mat, mat_chunks = _chunked_tables(
        split_key=col // col_chunk_elems, chunk_elems=col_chunk_elems,
        group=(row >> 7), lane=(col & 127).astype(np.int64),
        hi_global=(col >> 7).astype(np.int64), lo=(row & 127).astype(np.int64),
        val=v, n_groups=n_row_groups,
        block_sublanes=TABLE_SUBLANES["matvec"],
    )
    total_bytes = sum(_np_bytes(t) for t in rmat + mat)
    if total_bytes > max_table_bytes:
        raise ValueError(
            f"Pallas slot tables would take {total_bytes / 1e9:.2f} GB "
            f"(> {max_table_bytes / 1e9:.2f} GB budget) for {n} rows x "
            f"{dim} features; falling back to the XLA fast path"
        )
    return PallasSparseAux(
        rmat=tuple(_to_device(t) for t in rmat),
        mat=tuple(_to_device(t) for t in mat),
        rmat_chunks=tuple(rmat_chunks), mat_chunks=tuple(mat_chunks),
        n_rows=n, dim=dim,
    )


# ---------------------------------------------------------------- kernels


def _gather_onehot_kernel(table_ref, hi_ref, lo_ref, val_ref, out_ref,
                          *, square_vals: bool):
    """One slot block: hw-gather the table, multiply by values, one-hot
    MXU-reduce each 8-sublane chunk to a 128-vector partial."""
    nb = hi_ref.shape[0]
    gathered = jnp.take_along_axis(
        table_ref[:], hi_ref[:], axis=0, mode="fill", fill_value=0.0
    )
    v = val_ref[:]
    if square_vals:
        v = v * v
    contrib = gathered * v                               # [nb, 128]
    lo = lo_ref[:]

    def chunk(i, _):
        c = lax.dynamic_slice_in_dim(contrib, i * CHUNK, CHUNK, 0)
        keys = lax.dynamic_slice_in_dim(lo, i * CHUNK, CHUNK, 0)
        oh = (
            keys.reshape(CHUNK * LANE, 1)
            == lax.broadcasted_iota(jnp.int32, (CHUNK * LANE, LANE), 1)
        )
        out_ref[i, :] = jnp.dot(
            c.reshape(1, CHUNK * LANE), oh.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )[0]
        return 0

    lax.fori_loop(0, nb // CHUNK, chunk, 0)


def _run_op(tables: _OpTables, vec2: Array, block_sublanes: int,
            square_vals: bool, interpret: bool) -> Array:
    """Shared driver: grid over slot blocks, then the tiny sorted
    segment-sum of chunk partials by group. Returns [n_groups, 128]."""
    total = tables.hi.shape[0]
    n_blocks = total // block_sublanes
    partials = pl.pallas_call(
        functools.partial(_gather_onehot_kernel, square_vals=square_vals),
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((block_sublanes, LANE), lambda i: (0, 0)),
            pl.BlockSpec((block_sublanes, LANE), lambda i: (i, 0)),
            pl.BlockSpec((block_sublanes, LANE), lambda i: (i, 0)),
            pl.BlockSpec((block_sublanes, LANE), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_sublanes // CHUNK, LANE),
                               lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((total // CHUNK, LANE), jnp.float32),
        interpret=interpret,
    )(vec2, tables.hi, tables.lo, tables.val)
    return jax.ops.segment_sum(
        partials, tables.chunk_group, num_segments=tables.n_groups + 1,
        indices_are_sorted=True,
    )[: tables.n_groups]


def _chunk_slice(vec: Array, chunk: int, chunk_elems: int, nb: int) -> Array:
    """The chunk's slice of the lookup vector, zero-padded to a full
    [nb, 128] table (static bounds — chunk indices are compile-time)."""
    lo = chunk * chunk_elems
    size = min(chunk_elems, vec.shape[0] - lo)
    piece = jax.lax.slice_in_dim(vec, lo, lo + size, axis=0)
    return jnp.pad(piece, (0, chunk_elems - size)).reshape(nb, LANE)


def rmatvec_pallas(
    aux: PallasSparseAux, dz: Array, square_vals: bool = False,
    interpret: bool = False,
) -> Array:
    """g[c] = Σ entries val·dz[row] (val² with ``square_vals``); per-chunk
    group partials sum across row chunks."""
    nb = TABLE_SUBLANES["rmatvec"]
    dzf = dz.astype(jnp.float32)
    out = None
    for tables, chunk in zip(aux.rmat, aux.rmat_chunks):
        dz2 = _chunk_slice(dzf, chunk, nb * LANE, nb)
        part = _run_op(tables, dz2, nb, square_vals, interpret)
        out = part if out is None else out + part
    if out is None:  # dataset with zero real entries
        return jnp.zeros((aux.dim,), jnp.float32)
    return out.reshape(-1)[: aux.dim]


def matvec_pallas(
    aux: PallasSparseAux, w: Array, interpret: bool = False
) -> Array:
    """z[r] = Σ entries val·w[col]; per-chunk row partials sum across
    column chunks."""
    nb = TABLE_SUBLANES["matvec"]
    wf = w.astype(jnp.float32)
    out = None
    for tables, chunk in zip(aux.mat, aux.mat_chunks):
        w2 = _chunk_slice(wf, chunk, nb * LANE, nb)
        part = _run_op(tables, w2, nb, False, interpret)
        out = part if out is None else out + part
    if out is None:  # dataset with zero real entries
        return jnp.zeros((aux.n_rows,), jnp.float32)
    return out.reshape(-1)[: aux.n_rows]
