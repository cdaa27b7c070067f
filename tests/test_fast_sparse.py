"""Correctness of the table formulations of the sparse pass
(ops/fast_sparse.py: the Pallas kernel over its two arrangements, the sorted
``window`` table and ``X.w``'s ``planes``, here in the interpreter, and the
``fast`` row-slice tables) and the incremental-score L-BFGS variant, vs the
generic implementations."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.data.batch import LabeledBatch, SparseFeatures, ell_from_rows
from photon_tpu.functions.objective import GLMObjective
from photon_tpu.functions.problem import GLMOptimizationProblem
from photon_tpu.ops import fast_sparse
from photon_tpu.ops.fast_sparse import (
    FastSparseAux,
    PlaneTable,
    RowSliceXtr,
    RowSliceXw,
    WindowTable,
    build_fast_aux,
    gather_reduce,
    matvec_fast,
    plane_lookup,
    rmatvec_fast,
)
from photon_tpu.ops.losses import loss_for_task
from photon_tpu.optim import (LBFGS, OptimizerConfig, OptimizerType,
                              RegularizationContext, RegularizationType)
from photon_tpu.types import TaskType


FORMULATIONS = ["window", "planes", "fast"]
# The kernel's two arrangements of ``X.w``.
ARRANGEMENTS = ["window", "planes"]
XW_TABLE = {"window": WindowTable, "planes": PlaneTable, "fast": RowSliceXw}
# What ``X^T.r`` runs beside each: it has no ``planes``.
XTR_OF = {"window": "window", "planes": "window", "fast": "fast"}
FORCED = {
    "window": {"WINDOW_BREAK_EVEN_PASSES": float("inf"), "PLANES_MARGIN": 0.0},
    "planes": {"WINDOW_BREAK_EVEN_PASSES": float("inf"),
               "PLANES_MARGIN": float("inf")},
    "fast": {"WINDOW_BREAK_EVEN_PASSES": -1.0},
}


@pytest.fixture
def force(monkeypatch):
    """Make ``build_fast_aux`` give every op the named table formulation
    (``X^T.r`` under ``planes``: ``window``), by the constants it chooses
    by."""
    def _force(formulation):
        for name, value in FORCED[formulation].items():
            monkeypatch.setattr(fast_sparse, name, value)
    return _force


def _random_sparse(n, dim, k, seed=0, skew=False):
    rng = np.random.default_rng(seed)
    rows = []
    for i in range(n):
        nnz = rng.integers(1, k + 1)
        if skew and i % 3 == 0:
            cols = np.unique(np.concatenate([
                rng.integers(0, 8, size=nnz),       # hot columns
                rng.integers(0, dim, size=2),
            ]))
        else:
            cols = np.unique(rng.integers(0, dim, size=nnz))
        vals = rng.normal(size=len(cols))
        rows.append((cols.tolist(), vals.tolist()))
    return ell_from_rows(rows, dim=dim)


@pytest.mark.parametrize("formulation", FORMULATIONS)
@pytest.mark.parametrize("n,dim,k,skew", [
    (300, 517, 9, False), (300, 517, 9, True),
    # rows and columns off the 128 and 1,024 grids
    (300, 200, 4, False), (1000, 700, 6, False), (257, 129, 3, False)])
def test_matvec_rmatvec_match_generic(force, formulation, n, dim, k, skew):
    force(formulation)
    sf = _random_sparse(n, dim, k, seed=1, skew=skew)
    aux = build_fast_aux(np.asarray(sf.idx), np.asarray(sf.val), dim,
                         q_capacity=64)
    assert aux.formulation("matvec") == formulation
    assert aux.formulation("rmatvec") == XTR_OF[formulation]
    rng = np.random.default_rng(2)
    w = jnp.asarray(rng.normal(size=dim).astype(np.float32))
    v = jnp.asarray(rng.normal(size=n).astype(np.float32))

    np.testing.assert_allclose(
        np.asarray(matvec_fast(aux, sf.val, w, dim)),
        np.asarray(sf.matvec(w)), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(rmatvec_fast(aux, v, dim)),
        np.asarray(sf.rmatvec(v)), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(rmatvec_fast(aux, v, dim, square_vals=True)),
        np.asarray(sf.sq_rmatvec(v)), rtol=1e-5, atol=1e-5)


def test_with_fast_path_dispatch():
    n, dim, k = 200, 300, 7
    sf = _random_sparse(n, dim, k, seed=3)
    fast = sf.with_fast_path(q_capacity=128)
    assert fast.fast is not None
    rng = np.random.default_rng(4)
    w = jnp.asarray(rng.normal(size=dim).astype(np.float32))
    v = jnp.asarray(rng.normal(size=n).astype(np.float32))
    np.testing.assert_allclose(np.asarray(fast.matvec(w)),
                               np.asarray(sf.matvec(w)), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(fast.rmatvec(v)),
                               np.asarray(sf.rmatvec(v)), rtol=1e-5, atol=1e-5)
    assert fast.without_fast_path().fast is None


def test_fast_path_under_jit_and_objective():
    n, dim, k = 256, 384, 8
    sf = _random_sparse(n, dim, k, seed=5).with_fast_path(q_capacity=256)
    rng = np.random.default_rng(6)
    labels = (rng.random(n) < 0.5).astype(np.float32)
    batch = LabeledBatch(
        features=sf,
        labels=jnp.asarray(labels),
        offsets=jnp.zeros((n,), jnp.float32),
        weights=jnp.ones((n,), jnp.float32),
    )
    slow_batch = LabeledBatch(
        features=sf.without_fast_path(),
        labels=batch.labels, offsets=batch.offsets, weights=batch.weights,
    )
    obj = GLMObjective(loss=loss_for_task(TaskType.LOGISTIC_REGRESSION),
                       l2_weight=0.5)
    w = jnp.asarray(rng.normal(size=dim).astype(np.float32) * 0.1)
    vf, gf = jax.jit(obj.value_and_grad)(w, batch)
    vs, gs = jax.jit(obj.value_and_grad)(w, slow_batch)
    np.testing.assert_allclose(np.asarray(vf), np.asarray(vs), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gf), np.asarray(gs),
                               rtol=1e-4, atol=1e-4)


def test_scored_lbfgs_matches_plain():
    """optimize_scored reaches the same optimum as optimize on a logistic
    problem (same math; different per-probe rounding)."""
    n, dim, k = 400, 64, 6
    sf = _random_sparse(n, dim, k, seed=7)
    rng = np.random.default_rng(8)
    w_true = rng.normal(size=dim)
    z = np.asarray(sf.matvec(jnp.asarray(w_true, jnp.float32)))
    labels = (rng.random(n) < 1 / (1 + np.exp(-z))).astype(np.float32)
    batch = LabeledBatch(
        features=sf, labels=jnp.asarray(labels),
        offsets=jnp.zeros((n,), jnp.float32),
        weights=jnp.ones((n,), jnp.float32),
    )
    obj = GLMObjective(loss=loss_for_task(TaskType.LOGISTIC_REGRESSION),
                       l2_weight=1.0)
    cfg = OptimizerConfig(max_iterations=60, tolerance=1e-9)
    w0 = jnp.zeros((dim,), jnp.float32)
    r_plain = LBFGS(cfg).optimize(obj.bind(batch), w0)
    r_scored = LBFGS(cfg).optimize_scored(obj.score_space(batch), w0)
    # f32 line searches stall at slightly different near-optimal points;
    # assert mutual near-optimality rather than bitwise trajectory equality.
    assert float(r_scored.value) == pytest.approx(float(r_plain.value),
                                                  rel=5e-3)
    np.testing.assert_allclose(np.asarray(r_scored.x), np.asarray(r_plain.x),
                               rtol=0.05, atol=0.05)


def test_problem_run_uses_scored_path_and_matches():
    """GLMOptimizationProblem.run (LBFGS, no normalization) reaches the same
    optimum with and without the fast feature path attached."""
    n, dim, k = 300, 200, 8
    sf = _random_sparse(n, dim, k, seed=9)
    rng = np.random.default_rng(10)
    labels = (rng.random(n) < 0.4).astype(np.float32)

    def make_batch(features):
        return LabeledBatch(
            features=features, labels=jnp.asarray(labels),
            offsets=jnp.zeros((n,), jnp.float32),
            weights=jnp.ones((n,), jnp.float32),
        )

    problem = GLMOptimizationProblem(
        task=TaskType.LOGISTIC_REGRESSION,
        optimizer_type=OptimizerType.LBFGS,
        optimizer_config=OptimizerConfig(max_iterations=200, tolerance=1e-10),
        regularization=RegularizationContext(RegularizationType.L2),
        reg_weight=1.0,
    )
    w0 = jnp.zeros((dim,), jnp.float32)
    m_slow, r_slow = problem.run(make_batch(sf), w0)
    m_fast, r_fast = problem.run(make_batch(sf.with_fast_path(q_capacity=256)), w0)
    assert float(r_fast.value) == pytest.approx(float(r_slow.value), rel=5e-3)
    np.testing.assert_allclose(
        np.asarray(m_fast.coefficients.means),
        np.asarray(m_slow.coefficients.means), rtol=0.05, atol=0.05)


def _table_values(aux):
    """The arrays of the attached tables that hold feature values."""
    return [t.cs_val if isinstance(t, RowSliceXtr) else t.val
            for t in (aux.xw, aux.xtr) if not isinstance(t, RowSliceXw)]


@pytest.mark.parametrize("formulation", FORMULATIONS)
def test_value_dtype_bfloat16_exact_for_binary_features(force, formulation):
    """One-hot/binary values are exactly representable in bfloat16, so the
    narrowed storage (with_value_dtype) must reproduce f32 results bit-for-
    bit on matvec/rmatvec/sq_rmatvec."""
    force(formulation)
    n, dim = 200, 300
    rng = np.random.default_rng(11)
    rows = [(np.unique(rng.integers(0, dim, size=5)).tolist(), None)
            for _ in range(n)]
    rows = [(cols, [1.0] * len(cols)) for cols, _ in rows]
    sf = ell_from_rows(rows, dim=dim).with_fast_path(q_capacity=128)
    nf = sf.with_value_dtype(jnp.bfloat16)
    assert nf.val.dtype == jnp.bfloat16
    assert [v.dtype for v in _table_values(nf.fast)] == (
        [jnp.bfloat16] * (1 if formulation == "fast" else 2))

    w = jnp.asarray(rng.normal(size=dim).astype(np.float32))
    v = jnp.asarray(rng.normal(size=n).astype(np.float32))
    for op in ("matvec", "rmatvec", "sq_rmatvec"):
        a = getattr(sf, op)(w if op == "matvec" else v)
        b = getattr(nf, op)(w if op == "matvec" else v)
        assert b.dtype == jnp.float32  # accumulation stays in f32
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("formulation", FORMULATIONS)
def test_value_dtype_bfloat16_close_for_continuous_features(force,
                                                            formulation):
    """Continuous values round to 8 mantissa bits; results must stay within
    bf16 quantization error of the f32 path, including the square path
    (which must upcast BEFORE squaring)."""
    force(formulation)
    n, dim, k = 300, 517, 9
    sf = _random_sparse(n, dim, k, seed=12).with_fast_path(q_capacity=64)
    nf = sf.with_value_dtype(jnp.bfloat16)
    rng = np.random.default_rng(13)
    w = jnp.asarray(rng.normal(size=dim).astype(np.float32))
    v = jnp.asarray(rng.normal(size=n).astype(np.float32))
    np.testing.assert_allclose(np.asarray(nf.matvec(w)),
                               np.asarray(sf.matvec(w)),
                               rtol=0.03, atol=0.03)
    np.testing.assert_allclose(np.asarray(nf.rmatvec(v)),
                               np.asarray(sf.rmatvec(v)),
                               rtol=0.03, atol=0.03)
    np.testing.assert_allclose(np.asarray(nf.sq_rmatvec(v)),
                               np.asarray(sf.sq_rmatvec(v)),
                               rtol=0.05, atol=0.05)


def test_value_dtype_is_idempotent():
    sf = _random_sparse(50, 64, 4, seed=14).with_fast_path(q_capacity=32)
    nf = sf.with_value_dtype(jnp.bfloat16)
    assert nf.with_value_dtype(jnp.bfloat16) is nf  # no-op when already cast
    assert sf.with_value_dtype(jnp.float32) is sf


def test_glm_fit_with_bfloat16_values_converges_close():
    """End-to-end: an L2 logistic fit on bf16-stored values reaches an
    optimum close to the f32 fit (solver math itself stays f32)."""
    n, dim, k = 300, 200, 8
    sf = _random_sparse(n, dim, k, seed=15)
    rng = np.random.default_rng(16)
    labels = (rng.random(n) < 0.4).astype(np.float32)

    def make_batch(features):
        return LabeledBatch(
            features=features, labels=jnp.asarray(labels),
            offsets=jnp.zeros((n,), jnp.float32),
            weights=jnp.ones((n,), jnp.float32),
        )

    problem = GLMOptimizationProblem(
        task=TaskType.LOGISTIC_REGRESSION,
        optimizer_type=OptimizerType.LBFGS,
        optimizer_config=OptimizerConfig(max_iterations=200, tolerance=1e-10),
        regularization=RegularizationContext(RegularizationType.L2),
        reg_weight=1.0,
    )
    w0 = jnp.zeros((dim,), jnp.float32)
    m32, r32 = problem.run(make_batch(sf.with_fast_path(q_capacity=256)), w0)
    m16, r16 = problem.run(
        make_batch(sf.with_fast_path(q_capacity=256)
                   .with_value_dtype(jnp.bfloat16)), w0)
    assert float(r16.value) == pytest.approx(float(r32.value), rel=2e-2)
    np.testing.assert_allclose(
        np.asarray(m16.coefficients.means),
        np.asarray(m32.coefficients.means), rtol=0.1, atol=0.1)


@pytest.mark.parametrize("formulation", FORMULATIONS)
def test_value_dtype_then_fast_path_casts_the_tables(force, formulation):
    """Attach order must not matter: narrowing BEFORE with_fast_path still
    yields bf16 values in the tables (the builder emits f32)."""
    force(formulation)
    sf = _random_sparse(80, 96, 5, seed=17)
    nf = sf.with_value_dtype(jnp.bfloat16).with_fast_path(q_capacity=32)
    assert nf.val.dtype == jnp.bfloat16
    assert all(v.dtype == jnp.bfloat16 for v in _table_values(nf.fast))
    rng = np.random.default_rng(18)
    w = jnp.asarray(rng.normal(size=96).astype(np.float32))
    # Same result as narrowing after attach.
    other = sf.with_fast_path(q_capacity=32).with_value_dtype(jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(nf.matvec(w)),
                                  np.asarray(other.matvec(w)))


def test_digit_dtype_narrows_and_results_match(force):
    """Small spaces store >>7 digits as int16 (pure-HBM-stream halving);
    the threshold leaves room for the ghost block, and results are
    unchanged vs the generic path (covered by the match tests, which now
    exercise the int16 branch at their shapes)."""
    from photon_tpu.ops.fast_sparse import _digit_dtype

    assert _digit_dtype(100) == np.int16
    assert _digit_dtype(np.iinfo(np.int16).max - 1) == np.int16  # +ghost fits
    assert _digit_dtype(np.iinfo(np.int16).max) == np.int32      # would clip
    assert _digit_dtype(1 << 20) == np.int32

    force("fast")
    sf = _random_sparse(300, 517, 9, seed=19)
    aux = build_fast_aux(np.asarray(sf.idx), np.asarray(sf.val), 517,
                         q_capacity=64)
    assert aux.xw.hi.dtype == jnp.int16
    assert aux.xtr.cs_rhi.dtype == jnp.int16


@pytest.mark.parametrize("formulation", FORMULATIONS)
@pytest.mark.parametrize("value_dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("nnz", [5, 8, 52, 75, 76])
def test_matvec_fast_matches_float64_gather_at_row_width(
        force, formulation, nnz, value_dtype):
    """X.w (the kernel's row-range table, its planes, and the flat lane
    select) against the plain gather in float64, at row widths on and off a
    multiple of 8,
    an odd row count (72,309's kind), ghost entries in some rows and values
    stored narrow."""
    force(formulation)
    n, dim = 389, 3 * 128 + 77
    rng = np.random.default_rng(nnz)
    idx = np.full((n, nnz), dim, np.int32)
    val = np.zeros((n, nnz), np.float32)
    for i, count in enumerate(rng.integers(1, nnz + 1, size=n)):
        count = nnz if i % 7 == 0 else count      # full rows and short ones
        idx[i, :count] = rng.choice(dim, size=count, replace=False)
        val[i, :count] = rng.normal(size=count)
    assert (idx == dim).any()
    sf = SparseFeatures(idx=jnp.asarray(idx), val=jnp.asarray(val),
                        dim=dim).with_value_dtype(value_dtype)
    aux = sf.with_fast_path(q_capacity=64).fast
    assert type(aux.xw) is XW_TABLE[formulation]
    if formulation == "fast":
        assert aux.xw.hi.ndim == aux.xw.lo.ndim == 1
    else:
        assert aux.xw.val.dtype == value_dtype
    w = np.random.default_rng(nnz + 1).normal(size=dim).astype(np.float32)
    stored = np.asarray(sf.val.astype(jnp.float32), np.float64)
    want = np.sum(stored * np.append(w.astype(np.float64), 0.0)[idx], axis=1)
    got = matvec_fast(aux, sf.val, jnp.asarray(w), dim)
    assert got.shape == (n,) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


def test_matvec_fast_lowers_with_no_rank3_row_slices(force):
    """``fast`` at glm_fit's shape: the gather writes ``[rows*nnz, 128]`` and
    the lane select reads it as written: no ``[rows, nnz, 128]`` value (a
    physical copy on the TPU, whose tiles pad 76 to 80) is in the program."""
    force("fast")
    n, k, dim = 65536, 76, 47237
    aux = build_fast_aux(np.full((8, k), dim), np.zeros((8, k), np.float32),
                         dim, q_capacity=8)
    aux = dataclasses.replace(aux, xw=RowSliceXw(
        hi=jax.ShapeDtypeStruct((n * k,), jnp.int16),
        lo=jax.ShapeDtypeStruct((n * k,), jnp.int8)))
    text = matvec_fast.lower(
        aux, jax.ShapeDtypeStruct((n, k), jnp.float32),
        jax.ShapeDtypeStruct((dim,), jnp.float32), dim).as_text()
    assert f"tensor<{n * k}x128xf32>" in text
    assert f"tensor<{n}x{k}x128x" not in text


def _ell(rng, n, d, k, ghost_frac=0.2):
    """Raw ELL arrays with ghost entries anywhere in a row and column ids
    free to repeat within one."""
    idx = rng.integers(0, d, size=(n, k)).astype(np.int32)
    idx = np.where(rng.random((n, k)) < ghost_frac, d, idx)
    val = np.where(idx < d, rng.normal(size=(n, k)), 0.0).astype(np.float32)
    return idx, val


def _scatter64(idx, val, dz, d, square=False):
    """X^T.dz (or (X∘X)^T.dz) by a float64 scatter-add."""
    v = val.astype(np.float64)
    out = np.zeros(d + 1, np.float64)
    np.add.at(out, idx.ravel(),
              (dz.astype(np.float64)[:, None] * (v * v if square else v))
              .ravel())
    return out[:d]


@pytest.mark.parametrize("formulation", FORMULATIONS)
@pytest.mark.parametrize("case", ["duplicate_in_row", "column_in_every_row"])
def test_fast_ops_match_float64_on_duplicate_and_hot_columns(
        force, formulation, case):
    """A column id twice in one row: its entries add up. One column in
    every row (as the intercept is in every cell): its 128-column range
    holds more entries than ``q_capacity`` and spills over table rows."""
    force(formulation)
    rng = np.random.default_rng(0)
    n, d, k, q = 400, 100, 5, 64
    idx, val = _ell(rng, n, d, k, ghost_frac=0.0)
    if case == "duplicate_in_row":
        idx[:, 1] = idx[:, 2]
    else:
        idx[:, 0] = 7
    sf = SparseFeatures(jnp.asarray(idx), jnp.asarray(val), d).with_fast_path(
        q_capacity=q)
    # d = 100 is one 128-column range; its 2,000 entries fill 32 table rows
    # of 64 (``fast``) or 4 of the kernel's narrowest, one chunk.
    if formulation == "fast":
        assert int((np.asarray(sf.fast.xtr.cs_range) == 0).sum()) == (
            -(-n * k // q))
    else:
        assert sf.fast.xtr.word.shape[1] == fast_sparse.CHUNK
        assert int((np.asarray(sf.fast.xtr.range) == 0).sum()) == (
            -(-n * k // fast_sparse.CHUNK))
    w = rng.normal(size=d).astype(np.float32)
    dz = rng.normal(size=n).astype(np.float32)
    z64 = np.sum(val.astype(np.float64)
                 * np.append(w.astype(np.float64), 0.0)[idx], axis=1)
    np.testing.assert_allclose(
        np.asarray(sf.matvec(jnp.asarray(w))), z64, rtol=0, atol=5e-5)
    np.testing.assert_allclose(
        np.asarray(sf.rmatvec(jnp.asarray(dz))),
        _scatter64(idx, val, dz, d), rtol=0, atol=5e-5)
    np.testing.assert_allclose(
        np.asarray(sf.sq_rmatvec(jnp.asarray(dz))),
        _scatter64(idx, val, dz, d, square=True), rtol=0, atol=5e-5)


@pytest.mark.parametrize("formulation", ["window", "fast"])
@pytest.mark.parametrize("square_vals", [False, True])
@pytest.mark.parametrize("n", [257, 1000, 1025])
def test_rmatvec_fast_matches_float64_scatter_off_the_row_grids(
        force, formulation, n, square_vals):
    """X^T.r against a float64 scatter at row counts off the 128-row blocks
    of the ``dz`` table and off ``ROW_PAD`` (one row past it at 1,025), ghost
    entries in some rows: the mirror of the X.w test above."""
    dim, k = 3 * 128 + 77, 6
    rng = np.random.default_rng(n)
    idx, val = _ell(rng, n, dim, k)
    assert (idx == dim).any()
    force(formulation)
    aux = build_fast_aux(idx, val, dim, q_capacity=64)
    if formulation == "fast":
        assert aux.xtr.n_row_blocks == -(-n // 128)
    else:
        assert aux.xtr.n_ranges == -(-dim // 128)
    dz = rng.normal(size=n).astype(np.float32)
    got = rmatvec_fast(aux, jnp.asarray(dz), dim, square_vals=square_vals)
    assert got.shape == (dim,) and got.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(got), _scatter64(idx, val, dz, dim, square=square_vals),
        rtol=1e-5, atol=5e-5)


def test_estimator_attaches_accelerator_paths(monkeypatch):
    """On an accelerator backend the estimator attaches the ``fast`` tables
    to fixed-effect batches by itself (drivers need no layout knowledge),
    and nothing else; the fit matches the ``plain`` fit."""
    from photon_tpu.estimators.config import (
        FixedEffectDataConfig,
        GLMOptimizationConfiguration,
    )
    from photon_tpu.estimators.game_estimator import GameEstimator
    from photon_tpu.io.data_reader import GameDataBundle

    rng = np.random.default_rng(9)
    n, d, k = 400, 200, 6
    idx, val = _ell(rng, n, d, k)
    bundle = GameDataBundle(
        features={"global": SparseFeatures(jnp.asarray(idx), jnp.asarray(val), d)},
        labels=(rng.random(n) < 0.5).astype(np.float64),
        offsets=np.zeros(n),
        weights=np.ones(n),
        uids=np.arange(n).astype(object),
        id_tags={},
    )
    est = GameEstimator(
        task=TaskType.LOGISTIC_REGRESSION,
        coordinate_data_configs={"fixed": FixedEffectDataConfig("global")},
        n_sweeps=1,
    )
    cfg = [{"fixed": GLMOptimizationConfiguration(
        regularization=RegularizationContext(RegularizationType.L2),
        reg_weight=1.0, max_iterations=10)}]

    ref = est.fit(bundle, None, cfg)
    w_plain = np.asarray(ref[0].model["fixed"].model.coefficients.means)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    attached = []
    orig = SparseFeatures.with_accelerator_paths

    def spy(self):
        out = orig(self)
        attached.append({f.name: getattr(out, f.name) is not None
                         for f in dataclasses.fields(out)})
        return out

    monkeypatch.setattr(SparseFeatures, "with_accelerator_paths", spy)
    got = est.fit(bundle, None, cfg)
    w_acc = np.asarray(got[0].model["fixed"].model.coefficients.means)

    assert attached == [
        {"idx": True, "val": True, "dim": True, "fast": True}]
    np.testing.assert_allclose(w_acc, w_plain, rtol=0, atol=2e-3)


# ------------------------------------------- the ``window`` kernel (PR 31)


def _cell_like(seed, n, k, dim, head, ghost_frac=0.05):
    """ELL arrays in the benchmark generator's pattern: half of a row's
    named entries in a popular head, half anywhere, the last column an
    intercept in every row; some entries ghosts."""
    rng = np.random.default_rng(seed)
    named = k - 1
    idx = np.concatenate([
        rng.integers(0, head, size=(n, named // 2)),
        rng.integers(0, dim - 1, size=(n, named - named // 2)),
        np.full((n, 1), dim - 1)], axis=1).astype(np.int32)
    val = rng.normal(size=(n, k)).astype(np.float32) / np.sqrt(k)
    ghost = rng.random((n, k)) < ghost_frac
    ghost[:, -1] = False
    return (np.where(ghost, dim, idx).astype(np.int32),
            np.where(ghost, 0, val).astype(np.float32))


# Scaled-down shapes of the five cells and of the smoke: the row widths of
# each (76, 52, 8, 4, 3, 32), a row count off every grid (72,309's kind),
# vectors over more than one 5,376-element window on the side each cell has
# them. ``game_fit_ragged``'s planes are typed as the cell's: a head plane
# (window 0), two over all 26,764 named columns (windows 0 to 4), the
# intercept (window 4); ``game_fit_crossed``'s three read one window.
CELL_SHAPES = {
    "glm_fit": (565, 76, 6001, 1024),
    "glm_fit_tron": (723, 52, 2100, 1024),
    "game_fit": (6003, 8, 377, 64),
    "game_fit_ragged": (3003, 4, 26765, 20),
    "game_fit_crossed": (3003, 3, 21, 20),
    "smoke": (1024, 32, 12000, 1024),
}
# Every op the kernel runs: ``X^T.r`` and its squared twin have no planes.
KERNEL_OPS = [(a, op) for a in ARRANGEMENTS
              for op in ("matvec", "rmatvec", "sq_rmatvec")
              if a == "window" or op == "matvec"]


@pytest.mark.parametrize("arrangement,op", KERNEL_OPS)
@pytest.mark.parametrize("shape", list(CELL_SHAPES))
def test_window_ops_match_plain_at_the_cells_shapes(force, shape,
                                                    arrangement, op):
    force(arrangement)
    n, k, dim, head = CELL_SHAPES[shape]
    idx, val = _cell_like(31, n, k, dim, head)
    assert (idx == dim).any()
    plain = SparseFeatures(jnp.asarray(idx), jnp.asarray(val), dim)
    feats = plain.with_fast_path()
    assert feats.fast.formulation(op) == arrangement
    rng = np.random.default_rng(32)
    x = jnp.asarray(rng.normal(size=dim if op == "matvec" else n)
                    .astype(np.float32))
    want = np.asarray(getattr(plain, op)(x))
    got = getattr(feats, op)(x)
    assert got.shape == want.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                               atol=1e-5 * np.abs(want).max())


@pytest.mark.parametrize("arrangement", ARRANGEMENTS)
@pytest.mark.parametrize("where", ["jit", "while_loop", "nested_while_loops"])
def test_window_ops_under_jit_and_inside_a_while_loop(force, where,
                                                      arrangement):
    """The kernel traced into a larger program, as ``_fit_jitted`` holds it
    (L-BFGS's loop is a ``lax.while_loop``, TRON's CG loop one inside
    another)."""
    force(arrangement)
    n, k, dim, head = CELL_SHAPES["glm_fit_tron"]
    idx, val = _cell_like(33, n, k, dim, head)
    plain = SparseFeatures(jnp.asarray(idx), jnp.asarray(val), dim)
    feats = plain.with_fast_path()
    assert feats.fast.formulation("matvec") == arrangement
    w0 = jnp.asarray(np.random.default_rng(34).normal(size=dim)
                     .astype(np.float32))

    def step(f, w):
        return w - 0.01 * f.rmatvec(jnp.tanh(f.matvec(w)))

    def thrice(body):
        return lambda f, w: jax.lax.while_loop(
            lambda c: c[0] < 3, lambda c: (c[0] + 1, body(f, c[1])),
            (0, w))[1]

    run = jax.jit({"jit": step, "while_loop": thrice(step),
                   "nested_while_loops": thrice(thrice(step))}[where])
    np.testing.assert_allclose(np.asarray(run(feats, w0)),
                               np.asarray(run(plain, w0)),
                               rtol=1e-5, atol=1e-5)


def _one_entry_a_row(n, dim, seed):
    """Features whose ``X.w`` is a pure lookup: row i reads ``w[idx[i]]``
    with value 1. Sorted, so that 128 rows read a narrow span."""
    rng = np.random.default_rng(seed)
    idx = np.sort(rng.integers(0, dim, size=n)).astype(np.int32)[:, None]
    return idx, SparseFeatures(jnp.asarray(idx), jnp.ones((n, 1), jnp.float32),
                               dim)


@pytest.mark.parametrize("arrangement", ARRANGEMENTS)
@pytest.mark.parametrize("values", [
    "1e-30_to_1e30", "negatives", "zeros_between", "integers", "ulps_of_one"])
def test_window_select_returns_the_float32_bits(force, values, arrangement):
    """The lookup alone: three exact bfloat16 parts through a one-hot
    product give back the operand's float32, bit for bit, by either
    arrangement of the slots."""
    force(arrangement)
    n, dim = 700, 12000          # w over three windows
    rng = np.random.default_rng(35)
    x = rng.normal(size=dim) * 10.0 ** rng.uniform(-30, 30, size=dim)
    if values == "negatives":
        x = -np.abs(x)
    elif values == "zeros_between":
        x[rng.random(dim) < 0.5] = 0.0
    elif values == "integers":
        x = rng.integers(-2 ** 24, 2 ** 24, size=dim)
    elif values == "ulps_of_one":
        x = 1.0 + rng.integers(-64, 64, size=dim) * 2.0 ** -23
    x = x.astype(np.float32)
    idx, feats = _one_entry_a_row(n, dim, 36)
    feats = feats.with_fast_path()
    assert feats.fast.formulation("matvec") == arrangement
    got = np.asarray(feats.matvec(jnp.asarray(x)))
    np.testing.assert_array_equal(got.view(np.uint32),
                                  x[idx[:, 0]].view(np.uint32))


def _typed_planes(rng, n, dim, head=20):
    """``game_fit_ragged``'s row: a head entry, two anywhere, the intercept."""
    return np.concatenate([
        rng.integers(0, head, size=(n, 1)), rng.integers(0, dim - 1, (n, 2)),
        np.full((n, 1), dim - 1)], axis=1).astype(np.int32)


def _matrix(shape):
    """``(idx, dim)`` of the matrices the choice is read on."""
    rng = np.random.default_rng(37)
    windows = fast_sparse.WINDOW_BLOCKS * 128
    if shape == "tall_typed":           # five windows of w, four planes
        return _typed_planes(rng, 4096, 26765), 26765
    if shape == "tall_one_window":      # game_fit_crossed's, game_fit's kind
        return rng.integers(0, 21, size=(4096, 3)).astype(np.int32), 21
    if shape == "glm_fit":
        # 76 entries a row, half of them anywhere in nine windows: a sorted
        # chunk reads a window or two, a plane-chunk of the tail all nine.
        n, k, dim, head = 2048, 76, 47237, 1024
        return _cell_like(37, n, k, dim, head, ghost_frac=0.0)[0], dim
    if shape == "near_level":
        # Eight head planes and eight over four windows: a 128-row range is
        # a sorted chunk of one pass and one of four (0.48 + 1.5 us by the
        # model), 1,024 rows by planes 16 plane-chunks of 8 + 32 passes
        # (12.56 us against 15.84): cheaper by planes, and not by the margin.
        dim = 4 * windows
        return np.concatenate([
            rng.integers(0, 1000, size=(2048, 8)),
            rng.integers(0, dim, size=(2048, 8))], axis=1).astype(np.int32), dim
    if shape == "wide":                 # columns anywhere in forty windows
        return (rng.integers(0, 40 * windows, size=(1024, 8))
                .astype(np.int32), 40 * windows)
    raise ValueError(shape)


@pytest.mark.parametrize("shape,chosen,sorts", [
    ("tall_typed", "planes", False), ("tall_one_window", "planes", False),
    ("glm_fit", "window", True), ("near_level", "window", True),
    ("wide", "fast", True)])
def test_build_counts_both_arrangements_and_chooses_by_them(
        monkeypatch, shape, chosen, sorts):
    """``build_fast_aux`` counts what X.w would run by planes and by the
    sorted table and weighs the two by the kernel's measured costs: a tall
    typed matrix goes by planes (outright: no sort is made), ``glm_fit``'s
    shape keeps the sorted table, and so does a matrix on which the planes
    are cheaper by less than the margin; columns anywhere in forty windows
    read twenty of them a sorted chunk and forty a plane-chunk, and the op
    keeps the row-slice table. The table not chosen is not on the pytree."""
    idx, dim = _matrix(shape)
    n, k = idx.shape
    val = np.random.default_rng(38).normal(size=(n, k)).astype(np.float32)

    count = fast_sparse._count_planes(idx, dim)
    forced = fast_sparse._window_table(
        *fast_sparse._sorted_by_row_block(idx, val, dim), n, dim, 2048)
    sorted_us = fast_sparse._kernel_us(forced.passes & 255,
                                       fast_sparse.WINDOW_CHUNK_US)
    if shape == "wide":
        assert count is None and forced.passes_per_slot() > 10
    else:
        # The floor is one: no sorted table of the matrix goes under it.
        assert count.sorted_floor_us <= sorted_us + 1e-9
        assert (count.us < fast_sparse.PLANES_MARGIN * sorted_us) == (
            chosen == "planes")
    if shape == "near_level":
        assert count.us < sorted_us
    if shape == "tall_typed":
        n_pass = (count.passes & 255).reshape(-1, k)
        assert (n_pass[: n // 1024] == [1, 5, 5, 1]).all()
        assert not n_pass[n // 1024:].any()         # the padding chunks
        assert forced.passes_per_slot() == 5.0      # 512 entries a chunk
        assert (count.passes >> 8).reshape(-1, k)[0].tolist() == [0, 0, 0, 4]

    sorted_calls = []
    sort = fast_sparse._sorted_by_row_block
    monkeypatch.setattr(
        fast_sparse, "_sorted_by_row_block",
        lambda *a: sorted_calls.append(1) or sort(*a))
    aux = build_fast_aux(idx, val, dim)
    assert bool(sorted_calls) == sorts
    assert aux.formulation("matvec") == chosen
    assert type(aux.xw) is XW_TABLE[chosen]
    leaves = jax.tree_util.tree_leaves(aux.xw)
    assert len(leaves) == {"planes": 3, "window": 4, "fast": 2}[chosen]
    assert all(isinstance(leaf, jax.Array) for leaf in leaves)
    # X^T.r gathers by row: these rows are one window, whatever the columns.
    assert aux.formulation("rmatvec") == "window"
    assert aux.xtr.passes_per_slot() == 1.0
    args = aux.span_arguments()
    assert args["formulation_matvec"] == chosen
    assert args["formulation_rmatvec"] == "window"
    assert ("passes_per_slot_matvec" in args) == (chosen != "fast")
    if chosen == "planes":
        assert args["passes_per_slot_matvec"] == {
            "tall_typed": 3.0, "tall_one_window": 1.0}[shape]
    assert args["passes_per_slot_rmatvec"] == 1.0
    w = jnp.asarray(np.random.default_rng(39).normal(size=dim)
                    .astype(np.float32))
    plain = SparseFeatures(jnp.asarray(idx), jnp.asarray(val), dim)
    np.testing.assert_allclose(
        np.asarray(matvec_fast(aux, plain.val, w, dim)),
        np.asarray(plain.matvec(w)), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("square_vals", [False, True])
@pytest.mark.parametrize("rows_a_step", [1, 4, 7, 64])
def test_a_row_slice_table_is_reduced_a_block_at_a_time(
        force, monkeypatch, rows_a_step, square_vals):
    """``rmatvec_fast`` walks its table ``ROW_SLICE_STEP_BYTES`` of row
    slices at a time. Whatever the step (one that divides the 30 table
    rows, ones that do not, one that holds them all) the sums are those of
    the whole table in one step, to float32's last bits (1e-6 relative:
    the blocks change no order of summation inside a table row, XLA's
    fusion may), and those of a float64 scatter to the tolerance the test
    above holds the table to."""
    dim, k, n, q = 3 * 128 + 77, 6, 1000, 64
    rng = np.random.default_rng(rows_a_step)
    idx, val = _ell(rng, n, dim, k)
    force("fast")
    aux = build_fast_aux(idx, val, dim, q_capacity=q)
    b = aux.xtr.cs_rhi.shape[0]
    assert b > rows_a_step or rows_a_step == 64
    dz = jnp.asarray(rng.normal(size=n).astype(np.float32))
    assert b * 4 * 128 * q <= fast_sparse.ROW_SLICE_STEP_BYTES
    whole = np.asarray(rmatvec_fast(aux, dz, dim, square_vals=square_vals))
    monkeypatch.setattr(fast_sparse, "ROW_SLICE_STEP_BYTES",
                        rows_a_step * 4 * 128 * q)
    blocks = np.asarray(rmatvec_fast(aux, dz, dim, square_vals=square_vals))
    np.testing.assert_allclose(blocks, whole, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(
        blocks, _scatter64(idx, val, np.asarray(dz), dim, square=square_vals),
        rtol=1e-5, atol=5e-5)


def test_a_vector_too_long_for_vmem_keeps_the_row_slice_table(monkeypatch):
    monkeypatch.setattr(fast_sparse, "WINDOW_VMEM_VECTOR_BYTES", 6 * 5376)
    idx, val = _cell_like(38, 300, 6, 6000, 64)
    aux = build_fast_aux(idx, val, 6000)
    assert isinstance(aux.xw, RowSliceXw)          # w: two windows
    assert isinstance(aux.xtr, WindowTable)        # dz: one


@pytest.mark.parametrize("arrangement,op", [
    ("window", "matvec"), ("window", "rmatvec"), ("planes", "matvec")])
def test_padding_slots_contribute_nothing(force, arrangement, op):
    """A table is mostly padding at this size (3 entries a range in rows of
    512 slots; by planes 3 live entries among the ghosts of 300 rows and
    the rows that pad them to whole grid steps): the padding's value is 0
    and its lookup lands on element 0 of the vector, which may be as large
    as float32 goes."""
    force(arrangement)
    n, dim = 300, 400
    idx = np.full((n, 2), dim, np.int32)
    val = np.zeros((n, 2), np.float32)
    idx[1::100, 0], val[1::100, 0] = [5, 140, 399], [1.0, 2.0, 3.0]
    feats = SparseFeatures(jnp.asarray(idx), jnp.asarray(val),
                           dim).with_fast_path()
    table = feats.fast.xw if op == "matvec" else feats.fast.xtr
    live = np.asarray(table.val) != 0
    assert live.sum() == 3 and live.size >= 3 * fast_sparse.CHUNK
    assert not np.asarray(table.word)[~live].any()
    x = np.ones(dim if op == "matvec" else n, np.float32)
    x[0] = 3e38
    got = np.asarray(getattr(feats, op)(jnp.asarray(x)))
    want = np.zeros_like(got)
    if op == "matvec":
        want[1::100] = [1.0, 2.0, 3.0]
    else:
        want[[5, 140, 399]] = [1.0, 2.0, 3.0]
    np.testing.assert_array_equal(got, want)


def test_gather_reduce_is_the_one_kernel_of_both_ops(force):
    """``matvec`` and ``rmatvec`` are ``gather_reduce`` on mirrored tables."""
    force("window")
    n, k, dim, head = CELL_SHAPES["glm_fit_tron"]
    idx, val = _cell_like(39, n, k, dim, head)
    aux = build_fast_aux(idx, val, dim)
    rng = np.random.default_rng(40)
    w = jnp.asarray(rng.normal(size=dim).astype(np.float32))
    dz = jnp.asarray(rng.normal(size=n).astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(gather_reduce(aux.xw, w))[:n],
        np.asarray(matvec_fast(aux, jnp.asarray(val), w, dim)))
    np.testing.assert_array_equal(
        np.asarray(gather_reduce(aux.xtr, dz, square_vals=True))[:dim],
        np.asarray(rmatvec_fast(aux, dz, dim, square_vals=True)))
    # Mirrors: the same live entries, each table sorted for its own side.
    assert (np.asarray(aux.xw.val) != 0).sum() == (
        np.asarray(aux.xtr.val) != 0).sum() == (val != 0).sum()


def test_plane_lookup_is_x_w_over_the_ell_block_as_it_lies(force):
    """``matvec`` by planes is ``plane_lookup``: the table holds the ELL
    block's live entries plane by plane, in the rows' own order (no sort),
    and the rows that pad it to whole grid steps read 0."""
    force("planes")
    n, k, dim, head = CELL_SHAPES["game_fit_ragged"]
    idx, val = _cell_like(39, n, k, dim, head)
    table = build_fast_aux(idx, val, dim).xw
    w = jnp.asarray(np.random.default_rng(40).normal(size=dim)
                    .astype(np.float32))
    out = np.asarray(plane_lookup(table, w))
    steps = -(-n // (fast_sparse.ROWS_PER_STEP * fast_sparse.CHUNK))
    assert out.shape == (steps * fast_sparse.ROWS_PER_STEP
                         * fast_sparse.CHUNK,)
    assert not out[n:].any()
    np.testing.assert_array_equal(
        out[:n], np.asarray(matvec_fast(
            FastSparseAux(xw=table, xtr=None), jnp.asarray(val), w, dim)))
    by_plane = np.asarray(table.val).reshape(-1, k, fast_sparse.CHUNK)
    np.testing.assert_array_equal(
        by_plane.transpose(0, 2, 1).reshape(-1, k)[:n], val)
    assert table.n_planes == k and table.n_windows == 5


@pytest.mark.parametrize("arrangement", ARRANGEMENTS)
def test_window_table_takes_plain_for_an_operand_that_is_not_float32(
        force, arrangement):
    """The kernel is float32; a float64 operand (x64 runs off the chip)
    runs the op's ``plain`` arm in its own precision, and is counted so."""
    from photon_tpu.obs.metrics import REGISTRY

    force(arrangement)
    idx, val = _cell_like(41, 200, 6, 300, 32)
    feats = SparseFeatures(jnp.asarray(idx), jnp.asarray(val),
                           300).with_fast_path()
    counter = REGISTRY.counter("sparse_op_traces_total", "")
    before = {kind: counter.value(op="matvec", formulation=kind)
              for kind in (arrangement, "plain")}
    w64 = jnp.asarray(np.random.default_rng(42).normal(size=300), jnp.float64)
    z64 = feats.matvec(w64)
    assert z64.dtype == jnp.float64
    feats.matvec(w64.astype(jnp.float32))
    assert counter.value(op="matvec", formulation="plain") == before["plain"] + 1
    assert counter.value(op="matvec", formulation=arrangement) == (
        before[arrangement] + 1)
    want = (np.append(np.asarray(w64), 0.0)[idx] * val.astype(np.float64)).sum(1)
    np.testing.assert_allclose(np.asarray(z64), want, rtol=1e-12, atol=1e-12)


def _column_table_by_loop(idx, val, dim, q_capacity):
    """The row-slice X^T.r table as a Python loop over column ranges built
    it until PR 31: the reference the vectorised build is held to."""
    n, k = idx.shape
    n_col_blocks = -(-dim // 128)
    flat_col = idx.ravel()
    keep = flat_col < dim
    cols = flat_col[keep].astype(np.int64)
    rows = np.repeat(np.arange(n, dtype=np.int64), k)[keep]
    vals = val.ravel()[keep]
    order = np.argsort(cols >> 7, kind="stable")
    cols, rows, vals = cols[order], rows[order], vals[order]
    counts = np.bincount(cols >> 7, minlength=n_col_blocks)
    rows_per_range = np.maximum(1, -(-counts // q_capacity))
    b_pad = -(-int(rows_per_range.sum()) // 8) * 8
    out = {name: np.zeros((b_pad, q_capacity), dt) for name, dt in (
        ("cs_rhi", np.int64), ("cs_rlo", np.int64), ("cs_clo", np.int64),
        ("cs_val", np.float32))}
    cs_range = np.full((b_pad,), n_col_blocks, np.int32)
    starts = np.concatenate([[0], np.cumsum(counts)])
    b = 0
    for r in range(n_col_blocks):
        lo_e, hi_e = int(starts[r]), int(starts[r + 1])
        for off in range(lo_e, max(hi_e, lo_e + 1), q_capacity):
            sl = slice(off, min(off + q_capacity, hi_e))
            m = sl.stop - sl.start
            out["cs_rhi"][b, :m] = rows[sl] >> 7
            out["cs_rlo"][b, :m] = rows[sl] & 127
            out["cs_clo"][b, :m] = cols[sl] & 127
            out["cs_val"][b, :m] = vals[sl]
            cs_range[b] = r
            b += 1
    return out, cs_range


@pytest.mark.parametrize("n,k,dim,q", [(400, 5, 300, 64), (1000, 6, 700, 32),
                                       (257, 3, 129, 2048)])
def test_vectorised_table_build_equals_the_loop_it_replaced(force, n, k, dim,
                                                            q):
    force("fast")
    idx, val = _ell(np.random.default_rng(n), n, dim, k)
    idx[:, 0] = 7                      # a column in every row: many table rows
    val[:, 0] = 1.0
    want, want_range = _column_table_by_loop(idx, val, dim, q)
    got = build_fast_aux(idx, val, dim, q_capacity=q).xtr
    np.testing.assert_array_equal(np.asarray(got.cs_range), want_range)
    for name, table in want.items():
        np.testing.assert_array_equal(np.asarray(getattr(got, name)), table)
