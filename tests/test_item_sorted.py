"""The factored projection step's products over rows sorted by item
(``ops/item_sorted.py``), in the Pallas interpreter, held to the flat
products (``game/factored_random_effect.py`` ``_times_dot``,
``_transpose_dot``) on rows of one entry each: a head item of more rows
than a chunk, single-row items that spread one chunk over several windows,
ghost entries, rows of no entity and padding rows."""
import numpy as np
import pytest

import jax.numpy as jnp

from photon_tpu.game import factored_random_effect as f
from photon_tpu.ops import item_sorted
from photon_tpu.ops.fast_sparse import CHUNK

DIM, SLOTS, PADDING = 1200, 40, 96


def _rows(seed: int, unit_values: bool = False) -> f.FlatRows:
    rng = np.random.default_rng(seed)
    items = np.concatenate([
        np.full(CHUNK + 476, 3),           # a head item: more than a chunk
        np.arange(200, 1100),              # single-row items: a wide chunk
        rng.integers(0, DIM, 600),
        np.full(50, DIM)])                 # ghost entries
    rng.shuffle(items)
    n = len(items)
    live = items < DIM
    idx = np.full((n + PADDING, 1), DIM, np.int32)
    idx[:n, 0] = items
    val = np.zeros((n + PADDING, 1), np.float32)
    val[:n, 0] = np.where(live, 1.0 if unit_values else rng.normal(size=n), 0)
    slot = np.full(n + PADDING, SLOTS, np.int32)
    slot[:n] = rng.integers(0, SLOTS + 1, n)   # SLOTS: a row of no entity
    weights = np.zeros(n + PADDING, np.float32)
    weights[:n] = live
    return f.FlatRows(idx=jnp.asarray(idx), val=jnp.asarray(val),
                      slot=jnp.asarray(slot), weights=jnp.asarray(weights),
                      labels=jnp.zeros(n + PADDING, jnp.float32), n_rows=n,
                      n_slots=SLOTS)


def _in_plan_order(plan, flat: np.ndarray) -> np.ndarray:
    perm = np.asarray(plan.perm)
    return np.where(perm < len(flat), flat[np.minimum(perm, len(flat) - 1)],
                    0)


@pytest.fixture(scope="module")
def rows():
    return _rows(0)


def test_the_rows_hold_a_head_item_and_chunks_over_several_windows(rows):
    plan = f.sorted_rows(rows, jnp.zeros((DIM, 8), jnp.float32))
    passes = np.asarray(plan.windows) & 0xFFFF
    assert passes.max() > 1 and 1 in passes and 0 in passes
    assert plan.window_passes == passes.sum()
    col = np.asarray(plan.col).ravel()
    assert np.all(np.diff(col) >= 0) and col[-PADDING:].min() == DIM


@pytest.mark.parametrize("p", [8, 32])
def test_the_sorted_forward_is_the_flat_one(rows, p):
    rng = np.random.default_rng(p)
    P = jnp.asarray(rng.normal(size=(DIM, p)), jnp.float32)
    beta = jnp.asarray(rng.normal(size=(SLOTS, p)), jnp.float32)
    plan = f.sorted_rows(rows, P)
    bt = f._latent_columns(beta, plan.slot, item_sorted.latent_rows(p))
    got = np.asarray(item_sorted.sorted_times(plan.windows, plan.col,
                                              plan.val, P, bt))
    want = np.asarray(f._times_dot(P, beta, rows.idx, rows.val, rows.slot))
    np.testing.assert_allclose(got, _in_plan_order(plan, want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("p", [8, 32])
def test_the_sorted_transpose_is_the_flat_one(rows, p):
    rng = np.random.default_rng(p + 1)
    r = rng.normal(size=rows.idx.shape[0]).astype(np.float32)
    beta = jnp.asarray(rng.normal(size=(SLOTS, p)), jnp.float32)
    plan = f.sorted_rows(rows, jnp.zeros((DIM, p), jnp.float32))
    bt = f._latent_columns(beta, plan.slot, item_sorted.latent_rows(p))
    got = np.asarray(item_sorted.sorted_scatter(
        plan.windows, plan.col, plan.val, jnp.asarray(_in_plan_order(plan, r)),
        bt, (DIM, p)), np.float64)
    want = np.asarray(f._transpose_dot(jnp.asarray(r), beta, rows.idx,
                                       rows.val, rows.slot, DIM), np.float64)
    # float32 rounding: the head item sums 1,500 rows, in another order
    assert np.linalg.norm(got - want) < 1e-6 * np.linalg.norm(want)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("p", [8, 32])
def test_the_forward_looks_up_the_float32_bits_of_P(p):
    """With value 1 and every slot's latent vector a unit vector, a row's
    margin is one element of ``P``: bit for bit."""
    rows = _rows(1, unit_values=True)
    rng = np.random.default_rng(p + 2)
    # Elements over 50 decades, with all 24 bits of mantissa (under about
    # 1e-31 a third part would lie below float32's normal numbers).
    P = (rng.normal(size=(DIM, p)) * 10.0 ** rng.integers(-25, 25, (DIM, p))
         ).astype(np.float32)
    beta = np.eye(p, dtype=np.float32)[np.arange(SLOTS) % p]
    plan = f.sorted_rows(rows, jnp.asarray(P))
    bt = f._latent_columns(jnp.asarray(beta), plan.slot,
                           item_sorted.latent_rows(p))
    got = np.asarray(item_sorted.sorted_times(
        plan.windows, plan.col, plan.val, jnp.asarray(P), bt))
    col = np.asarray(plan.col).ravel()
    slot = np.asarray(plan.slot)
    looked_up = (col < DIM) & (slot < SLOTS)
    want = P[col[looked_up], slot[looked_up] % p]
    assert looked_up.sum() > 2500
    assert np.array_equal(got[looked_up].view(np.int32), want.view(np.int32))
    assert not got[~looked_up].any()
