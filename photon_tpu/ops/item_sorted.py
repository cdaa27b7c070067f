"""A factored projection step's two products over rows sorted by item.

A factored random effect whose shard holds one entry a row (the row's item
``m_n``, its value ``val_n``) refits ``P [D, p]`` against the rows' latent
vectors ``B_n`` (``game/factored_random_effect.py``). Its two products are

* ``sorted_times``: ``z_n = val_n · P[m_n] · B_n``, and
* ``sorted_scatter``: ``G[m] = Σ_{n: m_n = m} r_n · val_n · B_n``.

Over rows sorted by item, a chunk of ``CHUNK`` rows reads a narrow span of
``P``: its items from the least to the greatest, a *window* of ``LANE``
consecutive items at a time (one window for a head item's rows, several
where a chunk holds many items of few rows). Both products are then the
one-hot product of ``ops/fast_sparse.py``'s planes, with the ``p`` latent
columns on the rows of one MXU operand:

* ``Pᵀ`` is resident in VMEM as its three exact bfloat16 parts
  (``[3·p8, D_pad]``, ``p8`` = ``p`` rounded up to 8). For each window a
  chunk reads, the one-hot ``[LANE, CHUNK]`` of its rows' items against the
  window (built on the VPU) times the window's parts (the MXU) gives the
  parts of ``P[m_n]``; summed in float32 they are its float32 bits. Times
  ``Bᵀ`` (streamed, ``[p8, CHUNK]`` a chunk) and ``val``, summed over the
  ``p`` sublanes: a ``[1, CHUNK]`` store.
* A chunk's contributions ``r · val · Bᵀ`` (``[p8, CHUNK]``) are split into
  three exact bfloat16 parts and contracted over ``CHUNK`` with the same
  one-hot on the MXU; the gradient ``Gᵀ`` is resident in VMEM with a
  compensation beside it (as ``_scatter_kernel``'s), so an item's sum over
  its rows is held to about one float32 rounding.

Every product on the MXU is a bfloat16 part times 0 or 1, so exact, and
every sum float32: no precision is given up. Nothing of the rows'
length times ``p`` is written but ``Bᵀ``, which the caller lays out once
for every product of a solve. As with the planes, a non-finite element of
``P`` in a window makes every row that reads the window NaN, and a
non-finite contribution the ``LANE`` items of its window: a solver rejects
such a trial point by its value either way.

The table the kernels walk (``item_windows``) is built on the host from
the sorted items: per chunk, ``first block << 16 | windows``, 0 for a chunk
of no live row (skipped).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from photon_tpu.ops import fast_sparse
from photon_tpu.ops.fast_sparse import (
    CHUNK,
    LANE,
    ROWS_PER_STEP,
    WINDOW_VMEM_VECTOR_BYTES,
    _truncate_to_bfloat16,
)

Array = jax.Array

# A chunk's table entry: the first 128-block of its items above, the
# windows it reads below.
_FIRST_SHIFT = 16
_PASSES_MASK = (1 << _FIRST_SHIFT) - 1
# Rows the sorted plan pads to: whole grid steps of the kernels.
STEP_ROWS = ROWS_PER_STEP * CHUNK


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def latent_rows(p: int) -> int:
    """``p`` rounded up to whole sublanes: the rows ``Bᵀ`` and ``Gᵀ`` are
    laid on."""
    return _round_up(p, 8)


def _part_rows(p8: int) -> int:
    """The rows of the three parts laid one above the other, in whole
    bfloat16 tiles."""
    return _round_up(3 * p8, 16)


def holds(dim: int, p: int) -> bool:
    """Whether the kernels take a table of ``dim`` items by ``p``: the
    three parts of ``p`` latent columns on one MXU operand's 128 rows, and
    the resident gradient (itself, its output buffers and its compensation:
    12 B an element, more than the parts' 6) within the VMEM budget the
    planes use."""
    p8 = latent_rows(p)
    return (3 * p8 <= LANE
            and 12 * p8 * _round_up(dim, LANE) <= WINDOW_VMEM_VECTOR_BYTES)


def item_windows(col: np.ndarray, dim: int) -> np.ndarray:
    """``[C]`` int32 from the sorted items ``col`` (``[C * CHUNK]``; ``dim``
    or above: a ghost or padding row): per chunk, ``first block << 16 |
    windows`` from its least live item's 128-block to its greatest's, 0
    where no row of the chunk is live."""
    chunks = col.reshape(-1, CHUNK)
    live = chunks < dim
    first = np.where(live, chunks, dim).min(axis=1) // LANE
    last = np.where(live, chunks, -1).max(axis=1) // LANE
    passes = np.maximum(last - first + 1, 0)
    return np.where(passes > 0, first << _FIRST_SHIFT | passes, 0).astype(
        np.int32)


def window_passes(windows: np.ndarray) -> int:
    """The windows one product reads over all its chunks (host arrays)."""
    return int((np.asarray(windows) & _PASSES_MASK).sum())


def _parts(P: Array, p8: int) -> Array:
    """``Pᵀ`` as the forward kernel reads it: ``[part rows, D_pad]``
    bfloat16, part ``q`` (``a + b + c == x`` bit for bit) of latent column
    ``k`` at row ``q * p8 + k``; rows past ``3 * p8`` and items past ``D``
    hold 0."""
    dim, p = P.shape
    x = jnp.pad(P.astype(jnp.float32).T,
                ((0, p8 - p), (0, _round_up(dim, LANE) - dim)))
    a = _truncate_to_bfloat16(x)
    b = _truncate_to_bfloat16(x - a)
    parts = jnp.concatenate([a, b, (x - a) - b])
    return jnp.pad(parts, ((0, _part_rows(p8) - 3 * p8), (0, 0))).astype(
        jnp.bfloat16)


def _times_kernel(windows_ref, col_ref, val_ref, bt_ref, parts_ref, out_ref,
                  *, p8: int):
    """``ROWS_PER_STEP`` chunks a grid step: a chunk's windows looked up one
    after another into ``P[m_n]`` (``[p8, CHUNK]``), then one ``[1, CHUNK]``
    store of its margins."""
    from jax.experimental import pallas as pl

    step = pl.program_id(0)
    item_of = jax.lax.broadcasted_iota(jnp.int32, (LANE, CHUNK), 0)

    def chunk(c, carry):
        col = col_ref[pl.ds(c, 1), :]                           # [1, CHUNK]
        packed = windows_ref[step * ROWS_PER_STEP + c]
        first = (packed >> _FIRST_SHIFT) * LANE

        def one_pass(i, rows):
            base = pl.multiple_of(first + i * LANE, LANE)
            onehot = (item_of == col - base).astype(jnp.bfloat16)
            got = jnp.dot(parts_ref[:, pl.ds(base, LANE)], onehot,
                          preferred_element_type=jnp.float32)
            # The three parts of a row's item, or 0 where it is in another
            # window: each sum is exact.
            return rows + (got[:p8] + got[p8:2 * p8] + got[2 * p8:3 * p8])

        rows = jax.lax.fori_loop(0, packed & _PASSES_MASK, one_pass,
                                 jnp.zeros((p8, CHUNK), jnp.float32))
        bt = bt_ref[:, pl.ds(pl.multiple_of(c * CHUNK, CHUNK), CHUNK)]
        out_ref[pl.ds(c, 1), :] = jnp.sum(
            rows * bt, axis=0, keepdims=True) * val_ref[pl.ds(c, 1), :]
        return carry

    jax.lax.fori_loop(0, ROWS_PER_STEP, chunk, 0)


def _scatter_kernel(windows_ref, col_ref, val_ref, r_ref, bt_ref, out_ref,
                    comp_ref, *, p8: int):
    """``ROWS_PER_STEP`` chunks a grid step into the gradient resident in
    ``out_ref`` (``[blocks * p8, 128]``: row ``p8 * j + k``, lane ``l`` holds
    latent column ``k`` of item ``128 j + l``), compensated in
    ``comp_ref``."""
    from jax.experimental import pallas as pl

    step = pl.program_id(0)
    item_of = jax.lax.broadcasted_iota(jnp.int32, (LANE, CHUNK), 0)
    pad = _part_rows(p8) - 3 * p8

    @pl.when(step == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)
        comp_ref[...] = jnp.zeros_like(comp_ref)

    def chunk(c, carry):
        col = col_ref[pl.ds(c, 1), :]                           # [1, CHUNK]
        bt = bt_ref[:, pl.ds(pl.multiple_of(c * CHUNK, CHUNK), CHUNK)]
        x = bt * (r_ref[pl.ds(c, 1), :] * val_ref[pl.ds(c, 1), :])
        a = _truncate_to_bfloat16(x)
        b = _truncate_to_bfloat16(x - a)
        parts = [a, b, (x - a) - b]
        if pad:
            parts.append(jnp.zeros((pad, CHUNK), jnp.float32))
        parts = jnp.concatenate(parts).astype(jnp.bfloat16)    # [rows, CHUNK]
        packed = windows_ref[step * ROWS_PER_STEP + c]
        first = packed >> _FIRST_SHIFT

        def one_pass(i, carry):
            block = first + i
            onehot = (item_of == col - block * LANE).astype(jnp.bfloat16)
            got = jax.lax.dot_general(
                parts, onehot, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)             # [rows, 128]
            g = got[:p8] + (got[p8:2 * p8] + got[2 * p8:3 * p8])
            at = pl.ds(pl.multiple_of(block * p8, 8), p8)
            # Two-sum: ``acc + g`` exactly, its error kept aside.
            acc = out_ref[at, :]
            total = acc + g
            back = total - acc
            comp_ref[at, :] += (acc - (total - back)) + (g - back)
            out_ref[at, :] = total
            return carry

        return jax.lax.fori_loop(0, packed & _PASSES_MASK, one_pass, carry)

    jax.lax.fori_loop(0, ROWS_PER_STEP, chunk, 0)

    @pl.when(step == pl.num_programs(0) - 1)
    def _():
        out_ref[...] += comp_ref[...]


def sorted_times(windows: Array, col: Array, val: Array, P: Array,
                 bt: Array) -> Array:
    """``z[n] = val[n] · P[col[n]] · bt[:, n]`` over rows sorted by item:
    ``[C * CHUNK]`` float32, 0 in a ghost or padding row.

    ``windows`` (``[C]``, ``item_windows``), ``col`` and ``val`` (``[C,
    CHUNK]``) are the sorted plan; ``bt`` is ``[latent_rows(p), C * CHUNK]``
    float32, the rows' latent vectors laid column by column (rows past
    ``p``: 0). The lookup returns ``P``'s float32 bits; the products and
    the sum over ``p`` are float32.
    """
    return _sorted_times(windows, col, val, P, bt, fast_sparse._interpret())


# A program of its own inside the program that calls it: a solve holds the
# forward product at three places (its first margins, a direction's, the
# refresh) and the transpose at two, and the places share one lowering.
@functools.partial(jax.jit, static_argnames="interpret")
def _sorted_times(windows, col, val, P, bt, interpret: bool) -> Array:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    chunks = col.shape[0]
    p8 = bt.shape[0]
    parts = _parts(P, p8)
    rows = pl.BlockSpec((ROWS_PER_STEP, CHUNK), lambda i, w: (i, 0))
    out = pl.pallas_call(
        functools.partial(_times_kernel, p8=p8),
        out_shape=jax.ShapeDtypeStruct((chunks, CHUNK), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(chunks // ROWS_PER_STEP,),
            in_specs=[rows, rows,
                      pl.BlockSpec((p8, STEP_ROWS), lambda i, w: (0, i)),
                      pl.BlockSpec(memory_space=pltpu.VMEM)],
            out_specs=rows,
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=(16 << 20) + 2 * parts.size
            + 2 * 4 * p8 * STEP_ROWS),
        name="factored_sorted_times",
        interpret=interpret,
    )(windows, col, val.astype(jnp.float32), bt, parts)
    return out.reshape(-1)


def sorted_scatter(windows: Array, col: Array, val: Array, r: Array,
                   bt: Array, shape: tuple) -> Array:
    """``G[m] = Σ_{n: col[n] = m} r[n] · val[n] · bt[:, n]`` over rows
    sorted by item: ``shape`` (``(D, p)``) float32.

    The plan and ``bt`` as ``sorted_times`` takes them; ``r`` is the rows'
    ``[C * CHUNK]`` factors in the same order. Every product on the MXU is
    a bfloat16 part of a float32 contribution times 0 or 1, so exact; every
    sum is float32, and the sum over chunks is compensated.
    """
    return _sorted_scatter(windows, col, val, r, bt, tuple(shape),
                           fast_sparse._interpret())


@functools.partial(jax.jit, static_argnames=("shape", "interpret"))
def _sorted_scatter(windows, col, val, r, bt, shape: tuple,
                    interpret: bool) -> Array:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    dim, p = shape
    chunks = col.shape[0]
    p8 = bt.shape[0]
    blocks = -(-dim // LANE)
    grad = (blocks * p8, LANE)
    rows = pl.BlockSpec((ROWS_PER_STEP, CHUNK), lambda i, w: (i, 0))
    out = pl.pallas_call(
        functools.partial(_scatter_kernel, p8=p8),
        out_shape=jax.ShapeDtypeStruct(grad, jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(chunks // ROWS_PER_STEP,),
            in_specs=[rows, rows, rows,
                      pl.BlockSpec((p8, STEP_ROWS), lambda i, w: (0, i))],
            out_specs=pl.BlockSpec(grad, lambda i, w: (0, 0)),
            scratch_shapes=[pltpu.VMEM(grad, jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=(16 << 20) + 3 * 4 * grad[0] * LANE
            + 2 * 4 * p8 * STEP_ROWS),
        name="factored_sorted_scatter",
        interpret=interpret,
    )(windows, col, val.astype(jnp.float32),
      r.astype(jnp.float32).reshape(chunks, CHUNK), bt)
    # Row p8 j + k, lane l: latent column k of item 128 j + l.
    g = out.reshape(blocks, p8, LANE).transpose(0, 2, 1)
    return g.reshape(blocks * LANE, p8)[:dim, :p]
