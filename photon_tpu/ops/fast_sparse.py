"""The table formulations of the sparse pass: ``window``, its second
arrangement ``planes``, and ``fast``.

The formulations behind ``SparseFeatures`` (``data/batch.py``) beside
``plain``: the ops run one of these where the tables built here are
attached, and the ``plain`` gather / ``segment_sum`` where they are not.
All replace XLA's generic gather and scatter-add, which the TPU runs one
element at a time, with layouts built once on the host from the (static)
indices. What a pass costs on the chip, operation by operation, is in
``PERF.md`` §5; which formulation a run's programs hold, in the counter
``sparse_op_traces_total`` and in the span ``data.accel_tables``
(``formulation_matvec``, ``formulation_rmatvec``).

``build_fast_aux`` builds one table for each op (``X.w``, and ``X^T.r`` with
its squared twin) and chooses the formulation of each from numbers it can
observe: the mean MXU passes a slot of the ``window`` table
(``WINDOW_BREAK_EVEN_PASSES``) and, for ``X.w``, the chunks and passes either
arrangement of the kernel would run on the matrix, weighed by the kernel's
measured costs (``_xw_table``):

* ``window`` (``WindowTable``, ``gather_reduce``): **one Pallas kernel for
  both ops, no gather at all.** A table is ``[B, Q]`` slots; every slot of
  table row ``b`` reduces into the same aligned 128-range of the output and
  carries, in one int32, where in the gathered vector its operand lives and
  which of the 128 outputs it adds to; a second stream carries its value
  (0 in padding slots, so the hot loop masks nothing). ``X^T.r`` reduces
  into column ranges and gathers ``dz`` by row; ``X.w`` is its mirror:
  128-row ranges, ``w`` gathered by column. Entries are sorted by (range,
  gathered index), so a chunk of ``CHUNK`` consecutive slots reads a narrow
  span of the vector. The "which 128-block" half of the lookup is a one-hot
  product on the MXU against a *window* of ``WINDOW_BLOCKS`` blocks of the
  vector, which is resident in VMEM as three bfloat16 parts; the "which
  lane" half is a compare-select on the VPU. A float32 splits exactly into
  three bfloat16 parts and a one-hot is exact in bfloat16, so the product
  (float32 accumulation) **returns the float32 bits of the vector**: no
  precision is given up. The multiply by the value and the reduction into
  the 128 outputs are float32 on the VPU. Nothing of an entries' length is
  written to HBM: a slot costs 8 B of table read.

* ``planes`` (``PlaneTable``, ``plane_lookup``): **the same lookup over the
  ELL block as it lies, for ``X.w`` on a tall, narrow matrix.** The rows of
  ``X.w`` are already where the output is: ``z[i] = Σ_k val[i,k]·w[idx[i,k]]``
  needs a lookup an entry and no reduction across rows. Column ``k`` of the
  ELL block (a *plane*) is one lookup of ``CHUNK`` consecutive rows, full by
  construction; its result is multiplied by the plane's values and added,
  plane after plane, to a ``[1, CHUNK]`` accumulator that is stored once: no
  sort, no out lane, no epilogue, no transpose, 8 B an entry resident. A
  plane-chunk reads the windows from its least to its greatest live column,
  so it pays where planes are typed (a head plane, an intercept plane) or
  ``w`` is a window long; the sorted table pays where rows are wide and
  their columns spread (``glm_fit``: 76 entries over nine windows). The
  pass itself (``_lookup``) is shared with ``window``, bit for bit.

* ``fast`` (``RowSliceXw``, ``RowSliceXtr``): **row-slice gather +
  lane-select**, the formulation before the kernel and what an op keeps
  whose entries do not sort into narrow windows (``chip_smoke.py``'s uniform
  random columns), or whose vector is too long for VMEM. The vector is
  viewed as ``[D/128, 128]``; each entry fetches its 128-wide row slice
  (a contiguous-slice gather XLA vectorizes) and selects its lane with a
  fused ``where(lo == iota)`` reduction: 512 B written to HBM and read back
  an entry. ``X^T.r`` then reduces with a one-hot contraction per
  128-column range.

The tables are built on the host (NumPy, vectorised), a pure function of
the feature object, and ride along as an optional pytree on
``SparseFeatures``; all ops stay pure/jittable. ``build_fast_aux`` itself
keeps nothing: each call is a build. "Once per dataset" is the caller's to
hold — ``GameEstimator`` keeps the tables with its prepared bundle, so every
fit on that bundle after the first attaches them; the one-shot drivers build
once a run. Ghost-padding entries (column id == dim, value 0) are in no
``window`` table, hold value 0 in a ``planes`` table (and widen no
plane-chunk's windows) and point at a zero row in the ``fast`` ones.

One thing the kernel does differently from a gather: the one-hot product
multiplies every element of a window by 0 or 1, so a non-finite element
makes every slot that reads its window NaN, not only the slots that read
the element. A solver rejects such a trial point by its value either way.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib
import sys
import threading
from typing import Optional, Union

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

# --- the ``window`` formulation's geometry -------------------------------
# A window is this many 128-blocks of the gathered vector: the three
# bfloat16 parts of its blocks lie side by side on the contraction axis of
# one MXU pass (3 x 42 = 126 of 128 rows), so the pass sums the parts.
WINDOW_BLOCKS = 42
# Slots a pass handles at once; table rows are whole chunks, and each chunk
# carries the contiguous run of windows its (sorted) slots read.
CHUNK = 1024
# Table rows a grid step of the kernel walks (a loop, not an unrolled body);
# by planes, chunks of rows, each of them K table rows.
ROWS_PER_STEP = 8
# Field widths of a slot's int32: window | block in window | lane | out lane.
_WIN_SHIFT, _BLK_SHIFT, _LANE_SHIFT = 20, 14, 7
MAX_WINDOWS = 1 << (31 - _WIN_SHIFT)
MAX_PASSES_A_CHUNK = 255
# The gathered vector's three parts are resident in VMEM, 6 B an element.
WINDOW_VMEM_VECTOR_BYTES = 48 << 20
# Mean MXU passes a slot over which an op keeps the row-slice formulation:
# a chunk of 1,024 slots costs ``window`` about 0.22 us and 0.30 us a pass on
# a v5e where 1,024 entries cost ``fast`` 2.7 to 3.0 us (PERF.md §6, PR 31:
# the readings of ``scripts/sparse_formulation_check.py ops`` on the chip; the
# three cells read 1.0 to 1.9 passes, chip_smoke.py's shape 10.7 and 13.0).
WINDOW_BREAK_EVEN_PASSES = 8.0
# What the kernel costs on a v5e, by which ``build_fast_aux`` weighs X.w's two
# arrangements against each other, in microseconds: a pass over 1,024 slots;
# a chunk of the sorted table beside its passes (the out-lane select, eight
# adds, a share of the table row's transpose and of its ``segment_sum``); a
# plane-chunk beside its passes (a decode, a product, an add). Least squares
# over the twelve readings of ``scripts/sparse_formulation_check.py ops`` on
# the chip (PERF.md §6, PR 37: either arrangement forced at the five cells'
# shapes and the smoke's, 2.6 to 135 ms a call): 0.297, 0.238 and 0.035, and
# these three give every one of the twelve back within 5%.
WINDOW_PASS_US = 0.30
WINDOW_CHUNK_US = 0.24
PLANE_CHUNK_US = 0.035
# X.w goes by planes only where that is cheaper than the sorted table by a
# clear margin (its modelled cost under this share of the table's): a matrix
# near level keeps the table it has and does not flip on a rounding
# (``glm_fit_tron``'s reads 2.90 ms a call by planes and 2.66 by the table,
# PR 37; the cells that go by planes read 0.61, 0.27 and 0.23 of it).
PLANES_MARGIN = 0.75
# ``fast`` writes a float32 row slice (512 B) a slot, so ``rmatvec_fast``
# reduces its table a block of table rows at a time, each block's slices
# about this many bytes (the whole of a 40 M-entry table would be 20 GB).
ROW_SLICE_STEP_BYTES = 1 << 30


def _mean_passes(passes) -> float:
    n_pass = np.asarray(passes) & 255
    return float(n_pass[n_pass > 0].mean()) if n_pass.any() else 0.0


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class WindowTable:
    """One op's ``window`` table: ``out[range[b]*128 + l] += val * vec[g]``
    for every slot of table row ``b``.

    ``word[B, Q]`` int32 packs, per slot, the window of ``vec`` its operand
    lies in, the 128-block within that window, the lane within the block,
    and the lane ``l`` of the output range; ``val[B, Q]`` is its value (0 in
    padding slots; may be stored narrower, widened on load). ``passes``
    holds, per ``CHUNK`` slots of a row, ``first window << 8 | windows`` the
    chunk reads (0 for an all-padding chunk: the kernel skips it).
    ``range[B]`` is sorted (``n_ranges`` for the rows that pad ``B`` to whole
    grid steps).
    """

    word: Array      # [B, Q] int32
    val: Array       # [B, Q] float32 (or the features' narrower value dtype)
    passes: Array    # [B * Q / CHUNK] int32
    range: Array     # [B] int32, sorted
    n_ranges: int = dataclasses.field(metadata=dict(static=True))
    n_windows: int = dataclasses.field(metadata=dict(static=True))

    formulation = "window"

    def passes_per_slot(self) -> float:
        """Mean MXU passes over the chunks that hold an entry: what
        ``build_fast_aux`` chose by (a host read of ``passes``; not for
        traced code)."""
        return _mean_passes(self.passes)

    def cast_values(self, dtype) -> "WindowTable":
        return dataclasses.replace(self, val=self.val.astype(dtype))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PlaneTable:
    """``X.w``'s ``planes`` table: the ELL block as it lies, a *plane*
    (column ``k`` of ``idx`` and ``val``) of ``CHUNK`` consecutive rows a
    table row. ``z[c*CHUNK + j] = Σ_k val[c*K + k, j] · w[column of
    word[c*K + k, j]]``.

    ``word[C*K, CHUNK]`` int32 packs a slot's column in ``WindowTable``'s
    window | block | lane fields (the out lane is the slot's own position,
    its field 0); ``val`` is its value (0 for ghost entries and for the
    rows that pad ``C`` to whole grid steps; may be stored narrower).
    ``passes[C*K]`` holds ``first window << 8 | windows`` a plane-chunk, from
    its least live column to its greatest (0: no live entry, skipped).
    """

    word: Array      # [C*K, CHUNK] int32
    val: Array       # [C*K, CHUNK] float32 (or narrower)
    passes: Array    # [C*K] int32
    n_planes: int = dataclasses.field(metadata=dict(static=True))
    n_windows: int = dataclasses.field(metadata=dict(static=True))

    formulation = "planes"

    def passes_per_slot(self) -> float:
        """Mean MXU passes over the plane-chunks that hold an entry."""
        return _mean_passes(self.passes)

    def cast_values(self, dtype) -> "PlaneTable":
        return dataclasses.replace(self, val=self.val.astype(dtype))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class RowSliceXw:
    """``X.w`` by row-slice gather: the row-major digit split of the column
    ids, flat in the order of ``val.ravel()`` so the program reshapes no
    index stream, over ``Np`` rows (N rounded up to ``ROW_PAD``, the added
    rows all ghosts). ``hi[Np*K]`` is column id >> 7 (ghost entries point at
    the zero row appended to the coefficient table; int16 when the block
    count fits, halving that index stream's HBM traffic); ``lo[Np*K]`` int8
    is column & 127. The values are the features' own ``val``."""

    hi: Array        # [Np*K] int16 or int32 (see _digit_dtype)
    lo: Array        # [Np*K] int8

    formulation = "fast"

    def cast_values(self, dtype) -> "RowSliceXw":
        return self


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class RowSliceXtr:
    """``X^T.r`` by row-slice gather and one-hot reduce: ``B`` rows of
    capacity ``Q``; every slot in row b carries an entry whose column lies in
    the 128-aligned range ``cs_range[b]``. ``cs_rhi``/``cs_rlo`` split the
    entry's ROW id for the dz gather; ``cs_clo`` is its lane within the
    range; ``cs_val`` is the feature value (0 in padding slots)."""

    cs_rhi: Array    # [B, Q] int16 or int32
    cs_rlo: Array    # [B, Q] int8
    cs_clo: Array    # [B, Q] int8
    cs_val: Array    # [B, Q] float32
    cs_range: Array  # [B] int32 (sorted; == n_ranges for padding rows)
    n_ranges: int = dataclasses.field(metadata=dict(static=True))
    n_row_blocks: int = dataclasses.field(metadata=dict(static=True))

    formulation = "fast"

    def cast_values(self, dtype) -> "RowSliceXtr":
        return dataclasses.replace(self, cs_val=self.cs_val.astype(dtype))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class FastSparseAux:
    """The tables ``SparseFeatures.fast`` holds: one for ``X.w`` and one for
    ``X^T.r`` (and its squared twin), each in the formulation
    ``build_fast_aux`` chose for it."""

    xw: Union[WindowTable, PlaneTable, RowSliceXw]
    xtr: Union[WindowTable, RowSliceXtr]

    def formulation(self, op: str) -> str:
        """``"window"``, ``"planes"`` or ``"fast"``: what ``op`` runs on
        these tables."""
        return (self.xw if op == "matvec" else self.xtr).formulation

    def span_arguments(self) -> dict:
        """What the ``data.accel_tables`` span says of a build: each op's
        formulation, and the mean MXU passes a slot of a table the kernel
        reads (``window``, ``planes``)."""
        out = {}
        for op, table in (("matvec", self.xw), ("rmatvec", self.xtr)):
            out[f"formulation_{op}"] = table.formulation
            if isinstance(table, (WindowTable, PlaneTable)):
                out[f"passes_per_slot_{op}"] = round(table.passes_per_slot(), 4)
        return out

    def cast_values(self, dtype) -> "FastSparseAux":
        """The tables with their stored values in ``dtype`` (the ops widen
        on load): ``SparseFeatures.with_value_dtype``'s half of the tables."""
        return FastSparseAux(xw=self.xw.cast_values(dtype),
                             xtr=self.xtr.cast_values(dtype))


def _digit_dtype(n_blocks: int):
    """Narrowest int dtype for a >>7 digit stream with ``n_blocks`` valid
    block ids PLUS the ghost/zero block. The digit arrays are pure HBM
    traffic in the hot loop, so int16 (feature spaces <= 128*32767 ≈ 4.19M,
    row spaces likewise) halves their share of the stream; beyond that the
    layout transparently stays int32."""
    return np.int16 if n_blocks + 1 <= np.iinfo(np.int16).max else np.int32


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


# ------------------------------------------------------------- the builders


def _sorted_by_column_block(idx: np.ndarray, val: np.ndarray, dim: int):
    """The live entries as ``(col, row, val)``, sorted by (column >> 7, row):
    a stable sort of the row-major entries by column block alone."""
    k = idx.shape[1]
    flat = idx.ravel()
    keep = np.flatnonzero(flat < dim).astype(np.int32)
    col = flat[keep].astype(np.int32)
    # 16-bit keys take NumPy's radix sort.
    blk = (col >> 7).astype(np.uint16 if dim <= LANE << 16 else np.int32)
    at = keep[np.argsort(blk, kind="stable")]
    return flat[at].astype(np.int32), at // k, val.ravel()[at]


def _sorted_by_row_block(idx: np.ndarray, val: np.ndarray, dim: int):
    """The live entries as ``(row, col, val)``, sorted by (row >> 7, column):
    the entries of 128 rows are contiguous already, so one sort along the
    second axis of ``[row blocks, 128 * K]`` (ghosts sort last in each)."""
    n, k = idx.shape
    width = LANE * k
    # Column above, position in the block below: sorting the packed key
    # sorts the positions with it.
    bits = max(int(width - 1).bit_length(), 1)
    key = np.full((_round_up(n, LANE), k), dim, np.int64)
    key[:n] = np.minimum(idx, dim)
    key = key.reshape(-1, width)
    key <<= bits
    key |= np.arange(width, dtype=np.int64)
    key.sort(axis=1)
    col = (key >> bits).astype(np.int32).ravel()
    key &= (1 << bits) - 1
    key += np.arange(key.shape[0], dtype=np.int64)[:, None] * width
    live = np.flatnonzero(col < dim)
    at = key.ravel()[live].astype(np.int32)
    return at // k, col[live], val.ravel()[at]


def _slots(counts: np.ndarray, q: int, rows_floor: int):
    """Where the entries of a table go, in their sorted order, given how
    many each range holds: each range fills whole rows of ``q`` slots from
    the left, at least ``rows_floor`` of them. ``(flat slot of every entry,
    range of every table row)``, the rows padded to whole grid steps with
    the range one past the last."""
    rows_of = np.maximum(rows_floor, -(-counts // q))
    # A range's entries are consecutive: its first slot less its first
    # entry's position is what every one of them is shifted by.
    shift = (np.cumsum(rows_of) - rows_of) * q - (np.cumsum(counts) - counts)
    flat = np.repeat(shift.astype(np.int32), counts)
    flat += np.arange(len(flat), dtype=np.int32)
    b_live = int(rows_of.sum())
    ranges = np.full(_round_up(max(b_live, 1), ROWS_PER_STEP), len(counts),
                     np.int32)
    ranges[:b_live] = np.repeat(np.arange(len(counts), dtype=np.int32),
                                rows_of)
    return flat, ranges


def _windows_of(n_gat: int) -> Optional[int]:
    """The windows a gathered vector of ``n_gat`` elements spans; None where
    the kernel cannot hold it (a slot's field, the VMEM the parts take)."""
    n_windows = max(1, -(-n_gat // (WINDOW_BLOCKS * LANE)))
    if (n_windows > MAX_WINDOWS
            or 6 * n_windows * WINDOW_BLOCKS * LANE > WINDOW_VMEM_VECTOR_BYTES):
        return None
    return n_windows


def _placed(table):
    """A table built on the host, on the device."""
    return jax.tree.map(jnp.asarray, table)


def _window_table(red, gat, val, n_red: int, n_gat: int,
                  q_capacity: int) -> Optional[WindowTable]:
    """The ``window`` table of entries that reduce into index ``red`` (of
    ``n_red``) and gather index ``gat`` (of ``n_gat``), both int32 and sorted
    by (``red >> 7``, ``gat``), its arrays still on the host (``_placed``
    once it is chosen); None where the geometry cannot hold them (the
    vector too long, a chunk over too many windows)."""
    n_ranges = -(-n_red // LANE)
    n_windows = _windows_of(n_gat)
    if n_windows is None:
        return None
    counts = np.bincount(red >> 7, minlength=n_ranges)
    # Q from the shape: no wider than the fullest range needs.
    q = max(CHUNK, min(_round_up(q_capacity, CHUNK),
                       _round_up(int(counts.max(initial=0)), CHUNK)))
    flat, ranges = _slots(counts, q, rows_floor=0)
    b = len(ranges)

    win = gat // (WINDOW_BLOCKS * LANE)
    packed = gat - win * (WINDOW_BLOCKS * LANE)     # block in window | lane
    packed <<= _LANE_SHIFT
    packed |= red & 127
    packed |= win << _WIN_SHIFT
    word = np.zeros(b * q, np.int32)
    word[flat] = packed
    values = np.zeros(b * q, np.float32)
    values[flat] = val

    # A chunk's slots are sorted by gathered index: it reads the windows
    # from its first slot's to its last live slot's.
    passes = np.zeros(b * q // CHUNK, np.int32)
    if len(flat):
        flat //= CHUNK
        starts = np.flatnonzero(np.r_[True, flat[1:] != flat[:-1]])
        ends = np.r_[starts[1:], len(flat)] - 1
        n_pass = win[ends] - win[starts] + 1
        if int(n_pass.max()) > MAX_PASSES_A_CHUNK:
            return None
        passes[flat[starts]] = (win[starts] << 8) | n_pass
    return WindowTable(
        word=word.reshape(b, q), val=values.reshape(b, q), passes=passes,
        range=ranges, n_ranges=n_ranges, n_windows=n_windows)


def _row_slice_xw(idx: np.ndarray, dim: int) -> RowSliceXw:
    n, k = idx.shape
    n_col_blocks = -(-dim // LANE)
    # Ghost entries, and the ghost rows that fill the stream to whole
    # ROW_PAD blocks, -> appended zero row of the w table.
    ghost = idx >= dim
    n_pad = _round_up(n, ROW_PAD)
    hi = np.full((n_pad, k), n_col_blocks, _digit_dtype(n_col_blocks))
    lo = np.zeros((n_pad, k), np.int8)
    hi[:n] = np.where(ghost, n_col_blocks, idx >> 7)
    lo[:n] = np.where(ghost, 0, idx & 127)
    return RowSliceXw(hi=jnp.asarray(hi.ravel()), lo=jnp.asarray(lo.ravel()))


def _row_slice_xtr(col, row, val, n: int, dim: int,
                   q_capacity: int) -> RowSliceXtr:
    """From the entries sorted by column block. A popular column range
    simply occupies several table rows (so skewed or dense columns — e.g.
    the intercept — need no special casing)."""
    n_row_blocks = -(-n // LANE)
    n_col_blocks = -(-dim // LANE)
    flat, cs_range = _slots(np.bincount(col >> 7, minlength=n_col_blocks),
                            q_capacity, rows_floor=1)
    b = len(cs_range)

    def table(dtype, values):
        out = np.zeros(b * q_capacity, dtype)
        out[flat] = values
        return jnp.asarray(out.reshape(b, q_capacity))

    return RowSliceXtr(
        cs_rhi=table(_digit_dtype(n_row_blocks), row >> 7),
        cs_rlo=table(np.int8, row & 127),
        cs_clo=table(np.int8, col & 127),
        cs_val=table(np.float32, val),
        cs_range=jnp.asarray(cs_range),
        n_ranges=n_col_blocks, n_row_blocks=n_row_blocks)


def _keeps_window(table: Optional[WindowTable]) -> bool:
    return (table is not None
            and table.passes_per_slot() <= WINDOW_BREAK_EVEN_PASSES)


def _kernel_us(n_pass: np.ndarray, chunk_us: float) -> float:
    """The kernel's modelled microseconds over chunks (or plane-chunks) of
    ``n_pass`` passes each; one of no pass is skipped."""
    return float(np.count_nonzero(n_pass) * chunk_us
                 + n_pass.sum() * WINDOW_PASS_US)


@dataclasses.dataclass(frozen=True)
class _PlaneCount:
    """What ``X.w`` by planes would run on a matrix, and the least the
    sorted table could: counted from the ELL block, before any sort."""

    passes: np.ndarray       # [C*K] int32, ``PlaneTable.passes``
    n_windows: int
    us: float                # the planes' modelled cost
    sorted_floor_us: float   # what no row-sorted table of it goes under


def _count_planes(idx: np.ndarray, dim: int) -> Optional[_PlaneCount]:
    """From the least and the greatest live column of every (128 rows,
    plane), O(entries): the windows a plane-chunk spans, and for the sorted
    table a floor (a 128-row range fills ``ceil(entries / CHUNK)`` chunks,
    which between them read every window from the range's least to its
    greatest: exactly that where a range is one chunk). None where the
    planes cannot hold the matrix (``w`` too long for VMEM, a plane-chunk
    over too many windows) or read over ``WINDOW_BREAK_EVEN_PASSES`` windows
    a plane-chunk, the row-slice table's ground."""
    n, k = idx.shape
    n_windows = _windows_of(dim)
    if n_windows is None:
        return None
    blocks = np.full((_round_up(n, ROWS_PER_STEP * CHUNK), k), dim, idx.dtype)
    blocks[:n] = np.minimum(idx, dim)
    blocks = blocks.reshape(-1, LANE, k)
    live = blocks < dim
    # Ghosts read ``dim``, the greatest. Where no entry is live: first =
    # dim's window, last = -1, no pass.
    first = blocks.min(axis=1) // (WINDOW_BLOCKS * LANE)
    last = np.where(live, blocks, -1).max(axis=1) // (WINDOW_BLOCKS * LANE)

    def spans(lo, hi):
        return np.maximum(hi - lo + 1, 0)

    per_chunk = CHUNK // LANE
    first_pc = first.reshape(-1, per_chunk, k).min(axis=1)
    n_pass = spans(first_pc, last.reshape(-1, per_chunk, k).max(axis=1))
    if (int(n_pass.max(initial=0)) > MAX_PASSES_A_CHUNK
            or _mean_passes(n_pass) > WINDOW_BREAK_EVEN_PASSES):
        return None
    chunks = -(-live.sum(axis=(1, 2)) // CHUNK)
    floor = np.maximum(chunks, spans(first.min(axis=1), last.max(axis=1)))
    return _PlaneCount(
        passes=np.where(n_pass > 0, first_pc << 8 | n_pass, 0)
        .astype(np.int32).ravel(),
        n_windows=n_windows,
        us=_kernel_us(n_pass, PLANE_CHUNK_US),
        sorted_floor_us=float(chunks.sum() * WINDOW_CHUNK_US
                              + floor.sum() * WINDOW_PASS_US))


def _plane_table(idx: np.ndarray, val: np.ndarray, dim: int,
                 count: _PlaneCount) -> PlaneTable:
    n, k = idx.shape
    live = idx < dim
    win = idx // (WINDOW_BLOCKS * LANE)
    word = idx - win * (WINDOW_BLOCKS * LANE)       # block in window | lane
    word <<= _LANE_SHIFT
    word |= win << _WIN_SHIFT

    def by_plane(entries, dtype):
        """``[N, K]`` -> ``[C*K, CHUNK]``: row ``c*K + k`` is plane ``k`` of
        chunk ``c``'s rows; 0 in ghost entries and in the padding rows."""
        out = np.zeros((len(count.passes) // k * CHUNK, k), dtype)
        out[:n] = np.where(live, entries, 0)
        return jnp.asarray(np.ascontiguousarray(
            out.reshape(-1, CHUNK, k).transpose(0, 2, 1)).reshape(-1, CHUNK))

    return PlaneTable(
        word=by_plane(word, np.int32), val=by_plane(val, np.float32),
        passes=jnp.asarray(count.passes), n_planes=k,
        n_windows=count.n_windows)


def _xw_table(idx: np.ndarray, val: np.ndarray, dim: int, q_capacity: int
              ) -> Union[WindowTable, PlaneTable, RowSliceXw]:
    """``X.w``'s table in the arrangement that is cheapest on this matrix:
    by planes where they cost under ``PLANES_MARGIN`` of the sorted table
    (outright, and with no sort, where they cost under that share of its
    floor), else the sorted table, else (over the break-even) row slices."""
    planes = _count_planes(idx, dim)
    if (planes is not None
            and planes.us < PLANES_MARGIN * planes.sorted_floor_us):
        return _plane_table(idx, val, dim, planes)
    table = _window_table(*_sorted_by_row_block(idx, val, dim), idx.shape[0],
                          dim, q_capacity)
    if not _keeps_window(table):
        table = None
    if planes is not None and (
            table is None or planes.us < PLANES_MARGIN * _kernel_us(
                table.passes & 255, WINDOW_CHUNK_US)):
        return _plane_table(idx, val, dim, planes)
    return _row_slice_xw(idx, dim) if table is None else _placed(table)


def _import_the_kernels_modules_meanwhile() -> None:
    """Pallas takes about a second to import, and the first trace of a
    program that holds the kernel would wait for it (a fresh process's
    first fit: ``setup_s``). A build's sorts release the interpreter lock,
    so a thread imports it beside them; an import another thread has begun
    is waited for, not repeated."""
    name = "jax.experimental.pallas.tpu"
    if name not in sys.modules:
        threading.Thread(target=importlib.import_module, args=(name,),
                         daemon=True).start()


def build_fast_aux(
    idx: np.ndarray, val: np.ndarray, dim: int, q_capacity: int = 2048
) -> FastSparseAux:
    """Host-side construction of both ops' tables from ELL arrays.

    ``idx``/``val`` are the ``SparseFeatures`` arrays ([N, K], ghost column ==
    ``dim`` with value 0). ``q_capacity`` bounds a table row's width. Each op
    gets the ``window`` table unless its mean passes a slot lie over
    ``WINDOW_BREAK_EVEN_PASSES`` (or the table cannot be built), and the
    row-slice table then; ``X.w`` the ``planes`` table where that is the
    cheaper arrangement of the same lookup (``_xw_table``). Only the tables
    chosen are placed on the device.
    """
    _import_the_kernels_modules_meanwhile()
    idx = np.asarray(idx)
    val = np.asarray(val).astype(np.float32, copy=False)
    n, k = idx.shape

    col, row, by_col = _sorted_by_column_block(idx, val, dim)
    xtr = _window_table(col, row, by_col, dim, n, q_capacity)
    xtr = (_placed(xtr) if _keeps_window(xtr)
           else _row_slice_xtr(col, row, by_col, n, dim, q_capacity))
    del col, row, by_col
    return FastSparseAux(xw=_xw_table(idx, val, dim, q_capacity), xtr=xtr)


# ------------------------------------------------------ ``window``: the kernel


def _interpret() -> bool:
    """Off the TPU the kernel runs in the Pallas interpreter (the CPU tests
    and rehearsals; what the estimator attaches there is ``plain``)."""
    return jax.devices()[0].platform != "tpu"


def _truncate_to_bfloat16(x: Array) -> Array:
    """``x`` with the low 16 bits of its float32 cleared: a bfloat16 value
    exactly. By bits, not by a conversion pair, which the compiler may
    elide under its excess-precision rule."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jax.lax.bitcast_convert_type(
        bits & jnp.uint32(0xFFFF0000), jnp.float32)


def _window_parts(vec: Array, n_windows: int) -> Array:
    """``vec`` as the kernel reads it: ``[128, n_windows * 128]`` bfloat16,
    lane of the vector on rows; window ``w`` holds in its 128 columns the
    three exact parts (``a + b + c == x`` bit for bit) of its 42 blocks
    side by side, part ``p`` of block ``j`` at column ``42 p + j``."""
    blocks = n_windows * WINDOW_BLOCKS
    x = jnp.pad(vec.astype(jnp.float32), (0, blocks * LANE - vec.shape[0]))
    a = _truncate_to_bfloat16(x)
    b = _truncate_to_bfloat16(x - a)
    c = (x - a) - b
    parts = jnp.stack([a, b, c]).astype(jnp.bfloat16)
    parts = parts.reshape(3, n_windows, WINDOW_BLOCKS, LANE)
    parts = parts.transpose(3, 1, 0, 2).reshape(
        LANE, n_windows, 3 * WINDOW_BLOCKS)
    parts = jnp.pad(parts, ((0, 0), (0, 0), (0, LANE - 3 * WINDOW_BLOCKS)))
    return parts.reshape(LANE, n_windows * LANE)


def _window_iotas():
    """``(lane_of, block_of)``, ``[128, CHUNK]``: a row's own index, and the
    block a window holds in that row."""
    lane_of = jax.lax.broadcasted_iota(jnp.int32, (LANE, CHUNK), 0)
    # Row k of a window holds block k mod 42 (rows 126, 127: none).
    block_of = (lane_of - jnp.where(lane_of >= WINDOW_BLOCKS, WINDOW_BLOCKS, 0)
                - jnp.where(lane_of >= 2 * WINDOW_BLOCKS, WINDOW_BLOCKS, 0))
    return lane_of, jnp.where(lane_of < 3 * WINDOW_BLOCKS, block_of, -1)


def _lookup(vec_ref, word, packed, lane_of, block_of):
    """The lookup both arrangements share: ``vec``'s float32 bits at the
    element each of ``word``'s ``[1, CHUNK]`` slots names, over the windows
    ``packed`` (``first << 8 | windows``) says its chunk reads; 0 in a slot
    whose window is not among them."""
    from jax.experimental import pallas as pl

    lane = (word >> _LANE_SHIFT) & 127
    block = (word >> _BLK_SHIFT) & 63
    window = word >> _WIN_SHIFT
    first = packed >> 8

    def one_pass(p, got):
        w = first + p
        onehot = (block_of == jnp.where(window == w, block, 63)
                  ).astype(jnp.bfloat16)                        # [128, CHUNK]
        rows = jnp.dot(
            vec_ref[:, pl.ds(pl.multiple_of(w * LANE, LANE), LANE)],
            onehot, preferred_element_type=jnp.float32)
        # A slot is in one window: the other passes add 0 to it.
        return got + jnp.sum(jnp.where(lane_of == lane, rows, 0.0),
                             axis=0, keepdims=True)

    return jax.lax.fori_loop(0, packed & 255, one_pass,
                             jnp.zeros((1, CHUNK), jnp.float32))


def _window_kernel(passes_ref, word_ref, val_ref, vec_ref, out_ref, *, q: int):
    from jax.experimental import pallas as pl

    chunks = q // CHUNK
    step = pl.program_id(0)
    lane_of, block_of = _window_iotas()

    def table_row(r, carry):
        def chunk(c, acc):
            at = pl.ds(pl.multiple_of(c * CHUNK, CHUNK), CHUNK)
            word = word_ref[pl.ds(r, 1), at]                    # [1, CHUNK]
            val = val_ref[pl.ds(r, 1), at]
            got = _lookup(
                vec_ref, word,
                passes_ref[(step * ROWS_PER_STEP + r) * chunks + c],
                lane_of, block_of)
            sel = jnp.where(lane_of == (word & 127), got * val, 0.0)
            for j in range(CHUNK // LANE):
                acc = acc + sel[:, j * LANE:(j + 1) * LANE]
            return acc

        acc = jax.lax.fori_loop(
            0, chunks, chunk, jnp.zeros((LANE, LANE), jnp.float32))
        # acc[l, j]: output lane l, slot j mod 128; the row is its sum over j.
        out_ref[pl.ds(r, 1), :] = jnp.sum(acc.T, axis=0, keepdims=True)
        return carry

    jax.lax.fori_loop(0, ROWS_PER_STEP, table_row, 0)


def _plane_kernel(passes_ref, word_ref, val_ref, vec_ref, out_ref, *,
                  planes: int):
    """``ROWS_PER_STEP`` chunks of rows a grid step: a chunk's planes are
    looked up one after another, each times its values, and summed in plane
    order; one ``[1, CHUNK]`` store a chunk."""
    from jax.experimental import pallas as pl

    step = pl.program_id(0)
    lane_of, block_of = _window_iotas()

    def chunk(c, carry):
        def plane(k, acc):
            r = c * planes + k
            got = _lookup(
                vec_ref, word_ref[pl.ds(r, 1), :],
                passes_ref[step * ROWS_PER_STEP * planes + r],
                lane_of, block_of)
            return acc + got * val_ref[pl.ds(r, 1), :]

        out_ref[pl.ds(c, 1), :] = jax.lax.fori_loop(
            0, planes, plane, jnp.zeros((1, CHUNK), jnp.float32))
        return carry

    jax.lax.fori_loop(0, ROWS_PER_STEP, chunk, 0)


def gather_reduce(table: WindowTable, vec: Array,
                  square_vals: bool = False) -> Array:
    """``out[range[b]*128 + l] = Σ val · vec[g]`` over the slots of ``table``
    that add to lane ``l`` of row ``b``'s range: ``[n_ranges * 128]`` float32.

    The lookup returns ``vec``'s float32 bits (module docstring); the
    products and both sums are float32. ``val * val`` under ``square_vals``;
    values stored narrower are widened first.
    """
    return _gather_reduce(table, vec, square_vals, _interpret())


# A program of its own inside the program that calls it: a fit holds the
# kernel at four or five places (value and gradient, the line search, the
# Hessian-vector product), and the places that share a table share one
# lowering of it.
@functools.partial(jax.jit, static_argnames=("square_vals", "interpret"))
def _gather_reduce(table: WindowTable, vec: Array, square_vals: bool,
                   interpret: bool) -> Array:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, q = table.word.shape
    val = table.val.astype(jnp.float32)
    if square_vals:
        val = val * val
    parts = _window_parts(vec, table.n_windows)
    rows = pl.BlockSpec((ROWS_PER_STEP, q), lambda i, passes: (i, 0))
    out_b = pl.pallas_call(
        functools.partial(_window_kernel, q=q),
        out_shape=jax.ShapeDtypeStruct((b, LANE), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b // ROWS_PER_STEP,),
            in_specs=[rows, rows, pl.BlockSpec(memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec((ROWS_PER_STEP, LANE),
                                   lambda i, passes: (i, 0)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=(16 << 20) + 2 * parts.size),
        name="sparse_gather_reduce",
        interpret=interpret,
    )(table.passes, table.word, val, parts)
    out_r = jax.ops.segment_sum(
        out_b, table.range, num_segments=table.n_ranges + 1,
        indices_are_sorted=True)[: table.n_ranges]
    return out_r.reshape(-1)


def plane_lookup(table: PlaneTable, vec: Array) -> Array:
    """``z[i] = Σ_k val[i, k] · vec[idx[i, k]]`` over ``table``'s planes:
    ``[C * CHUNK]`` float32, the rows past the matrix's own all 0.

    The lookup is ``gather_reduce``'s (``vec``'s float32 bits); the products
    are float32 and summed in plane order. Values stored narrower are
    widened first.
    """
    return _plane_lookup(table, vec, _interpret())


@functools.partial(jax.jit, static_argnames="interpret")
def _plane_lookup(table: PlaneTable, vec: Array, interpret: bool) -> Array:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    k = table.n_planes
    chunks = table.word.shape[0] // k
    parts = _window_parts(vec, table.n_windows)
    rows = pl.BlockSpec((ROWS_PER_STEP * k, CHUNK), lambda i, passes: (i, 0))
    out = pl.pallas_call(
        functools.partial(_plane_kernel, planes=k),
        out_shape=jax.ShapeDtypeStruct((chunks, CHUNK), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(chunks // ROWS_PER_STEP,),
            in_specs=[rows, rows, pl.BlockSpec(memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec((ROWS_PER_STEP, CHUNK),
                                   lambda i, passes: (i, 0)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=(16 << 20) + 2 * parts.size
            + 16 * ROWS_PER_STEP * k * CHUNK),
        name="sparse_plane_lookup",
        interpret=interpret,
    )(table.passes, table.word, table.val.astype(jnp.float32), parts)
    return out.reshape(-1)


# ------------------------------------------------------------------ the ops


def _lane_iota() -> Array:
    return jax.lax.broadcasted_iota(jnp.int8, (1, LANE), 1)


@functools.partial(jax.jit, static_argnames="dim")
def matvec_fast(aux: FastSparseAux, val: Array, w: Array, dim: int) -> Array:
    """z[i] = Σ_k val[i,k] · w[idx[i,k]] on the ``X.w`` table.

    One program also where the caller runs op by op (the coordinate scorers):
    eagerly every intermediate below would be materialized.
    """
    n, k = val.shape
    if isinstance(aux.xw, WindowTable):
        return gather_reduce(aux.xw, w)[:n]
    if isinstance(aux.xw, PlaneTable):
        return plane_lookup(aux.xw, w)[:n]
    nblk = -(-dim // LANE)
    w2 = jnp.pad(w, (0, nblk * LANE - dim)).reshape(nblk, LANE)
    w2 = jnp.concatenate([w2, jnp.zeros((1, LANE), w.dtype)])  # ghost row
    rows = w2[aux.xw.hi]                               # [Np*K, 128]
    sel = jnp.where(aux.xw.lo[:, None] == _lane_iota(), rows, 0.0)
    picked = jnp.sum(sel, axis=-1).reshape(-1, k)      # [Np, K]
    # Narrow-stored values (bfloat16 via with_value_dtype) upcast on load:
    # the accumulation stays in w's precision, only the HBM stream shrinks.
    valf = val.astype(jnp.promote_types(val.dtype, w.dtype))
    valf = jnp.pad(valf, ((0, picked.shape[0] - n), (0, 0)))
    return jnp.sum(picked * valf, axis=-1)[:n]


def rmatvec_fast(
    aux: FastSparseAux, dz: Array, dim: int, square_vals: bool = False
) -> Array:
    """g[c] = Σ_{entries of column c} val · dz[row] on the ``X^T.r`` table —
    scatter-free.

    ``fast``: dz is gathered by row-slice + lane select (same trick as
    matvec), the per-column reduction is a fused one-hot contraction per
    128-column range, and ranges assemble with one small sorted segment-sum.
    """
    t = aux.xtr
    if isinstance(t, WindowTable):
        return gather_reduce(t, dz, square_vals)[:dim]
    n = dz.shape[0]
    nb = t.n_row_blocks
    dz2 = jnp.pad(dz, (0, nb * LANE - n)).reshape(nb, LANE)
    iota = _lane_iota()
    # Upcast BEFORE squaring: bfloat16-stored values must square in the
    # accumulation precision, not in 8 mantissa bits.
    csv = t.cs_val.astype(jnp.promote_types(t.cs_val.dtype, dz.dtype))

    def reduce_rows(rhi, rlo, clo, val):
        """``[..., Q]`` slots of table rows -> their ``[..., 128]`` sums."""
        rows = dz2[rhi]                                # [..., Q, 128]
        dz_at = jnp.sum(jnp.where(rlo[..., None] == iota, rows, 0.0), axis=-1)
        contrib = dz_at * (val * val if square_vals else val)
        oh = jnp.where(clo[..., None] == iota, 1.0, 0.0)
        return jnp.einsum("...ql,...q->...l", oh, contrib,
                          preferred_element_type=jnp.float32)

    b, q = t.cs_rhi.shape
    slice_bytes = 4 * LANE * q                         # of one table row
    out_b = jax.lax.map(                               # [B, 128]
        lambda row: reduce_rows(*row), (t.cs_rhi, t.cs_rlo, t.cs_clo, csv),
        batch_size=max(1, min(b, ROW_SLICE_STEP_BYTES // slice_bytes)))
    out_r = jax.ops.segment_sum(
        out_b, t.cs_range, num_segments=t.n_ranges + 1,
        indices_are_sorted=True,
    )[: t.n_ranges]
    return out_r.reshape(-1)[:dim]


# Note: no custom_vjp wrapper is needed — every optimizer-facing path
# (GLMObjective.value_and_grad / hessian_vector / hessian_diagonal) is
# hand-fused and calls matvec/rmatvec explicitly, so autodiff never
# differentiates through these functions.
