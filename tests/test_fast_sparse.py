"""Correctness of the MXU-friendly sparse fast paths (ops/fast_sparse.py)
and the incremental-score L-BFGS variant, vs the generic implementations."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.data.batch import LabeledBatch, SparseFeatures, ell_from_rows
from photon_tpu.functions.objective import GLMObjective
from photon_tpu.functions.problem import GLMOptimizationProblem
from photon_tpu.ops.fast_sparse import build_fast_aux, matvec_fast, rmatvec_fast
from photon_tpu.ops.losses import loss_for_task
from photon_tpu.optim import (LBFGS, OptimizerConfig, OptimizerType,
                              RegularizationContext, RegularizationType)
from photon_tpu.types import TaskType


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


@pytest.mark.parametrize("n,dim,k,skew", [
    (300, 517, 9, False), (300, 517, 9, True),
    # rows and columns off the 128 and 1,024 grids
    (300, 200, 4, False), (1000, 700, 6, False), (257, 129, 3, False)])
def test_matvec_rmatvec_match_generic(n, dim, k, skew):
    sf = _random_sparse(n, dim, k, seed=1, skew=skew)
    aux = build_fast_aux(np.asarray(sf.idx), np.asarray(sf.val), dim,
                         q_capacity=64)
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


def test_value_dtype_bfloat16_exact_for_binary_features():
    """One-hot/binary values are exactly representable in bfloat16, so the
    narrowed storage (with_value_dtype) must reproduce f32 results bit-for-
    bit on matvec/rmatvec/sq_rmatvec."""
    n, dim = 200, 300
    rng = np.random.default_rng(11)
    rows = [(np.unique(rng.integers(0, dim, size=5)).tolist(), None)
            for _ in range(n)]
    rows = [(cols, [1.0] * len(cols)) for cols, _ in rows]
    sf = ell_from_rows(rows, dim=dim).with_fast_path(q_capacity=128)
    nf = sf.with_value_dtype(jnp.bfloat16)
    assert nf.val.dtype == jnp.bfloat16
    assert nf.fast.cs_val.dtype == jnp.bfloat16

    w = jnp.asarray(rng.normal(size=dim).astype(np.float32))
    v = jnp.asarray(rng.normal(size=n).astype(np.float32))
    for op in ("matvec", "rmatvec", "sq_rmatvec"):
        a = getattr(sf, op)(w if op == "matvec" else v)
        b = getattr(nf, op)(w if op == "matvec" else v)
        assert b.dtype == jnp.float32  # accumulation stays in f32
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_value_dtype_bfloat16_close_for_continuous_features():
    """Continuous values round to 8 mantissa bits; results must stay within
    bf16 quantization error of the f32 path, including the square path
    (which must upcast BEFORE squaring)."""
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


def test_value_dtype_then_fast_path_casts_column_table():
    """Attach order must not matter: narrowing BEFORE with_fast_path still
    yields a bf16 column-sorted table (the builder emits f32)."""
    sf = _random_sparse(80, 96, 5, seed=17)
    nf = sf.with_value_dtype(jnp.bfloat16).with_fast_path(q_capacity=32)
    assert nf.val.dtype == jnp.bfloat16
    assert nf.fast.cs_val.dtype == jnp.bfloat16
    rng = np.random.default_rng(18)
    w = jnp.asarray(rng.normal(size=96).astype(np.float32))
    # Same result as narrowing after attach.
    other = sf.with_fast_path(q_capacity=32).with_value_dtype(jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(nf.matvec(w)),
                                  np.asarray(other.matvec(w)))


def test_digit_dtype_narrows_and_results_match():
    """Small spaces store >>7 digits as int16 (pure-HBM-stream halving);
    the threshold leaves room for the ghost block, and results are
    unchanged vs the generic path (covered by the match tests, which now
    exercise the int16 branch at their shapes)."""
    from photon_tpu.ops.fast_sparse import _digit_dtype

    assert _digit_dtype(100) == np.int16
    assert _digit_dtype(np.iinfo(np.int16).max - 1) == np.int16  # +ghost fits
    assert _digit_dtype(np.iinfo(np.int16).max) == np.int32      # would clip
    assert _digit_dtype(1 << 20) == np.int32

    sf = _random_sparse(300, 517, 9, seed=19)
    aux = build_fast_aux(np.asarray(sf.idx), np.asarray(sf.val), 517,
                         q_capacity=64)
    assert aux.hi.dtype == jnp.int16
    assert aux.cs_rhi.dtype == jnp.int16


@pytest.mark.parametrize("value_dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("nnz", [5, 8, 52, 75, 76])
def test_matvec_fast_matches_float64_gather_at_row_width(nnz, value_dtype):
    """The flat lane select against the plain gather in float64, at row
    widths on and off a multiple of 8, an odd row count (72,309's kind),
    ghost entries in some rows and values stored narrow."""
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
    aux = build_fast_aux(idx, np.asarray(sf.val), dim, q_capacity=64)
    assert aux.hi.ndim == aux.lo.ndim == 1
    w = np.random.default_rng(nnz + 1).normal(size=dim).astype(np.float32)
    stored = np.asarray(sf.val.astype(jnp.float32), np.float64)
    want = np.sum(stored * np.append(w.astype(np.float64), 0.0)[idx], axis=1)
    got = matvec_fast(aux, sf.val, jnp.asarray(w), dim)
    assert got.shape == (n,) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


def test_matvec_fast_lowers_with_no_rank3_row_slices():
    """At glm_fit's shape the gather writes ``[rows*nnz, 128]`` and the
    lane select reads it as written: no ``[rows, nnz, 128]`` value (a
    physical copy on the TPU, whose tiles pad 76 to 80) is in the program."""
    n, k, dim = 65536, 76, 47237
    aux = build_fast_aux(np.full((8, k), dim), np.zeros((8, k), np.float32),
                         dim, q_capacity=8)
    flat = jax.ShapeDtypeStruct((n * k,), jnp.int16)
    aux = dataclasses.replace(
        aux, hi=flat, lo=jax.ShapeDtypeStruct((n * k,), jnp.int8))
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


@pytest.mark.parametrize("case", ["duplicate_in_row", "column_in_every_row"])
def test_fast_ops_match_float64_on_duplicate_and_hot_columns(case):
    """A column id twice in one row: its entries add up. One column in
    every row (as the intercept is in every cell): its 128-column range
    holds more entries than ``q_capacity`` and spills over table rows."""
    rng = np.random.default_rng(0)
    n, d, k, q = 400, 100, 5, 64
    idx, val = _ell(rng, n, d, k, ghost_frac=0.0)
    if case == "duplicate_in_row":
        idx[:, 1] = idx[:, 2]
    else:
        idx[:, 0] = 7
    sf = SparseFeatures(jnp.asarray(idx), jnp.asarray(val), d).with_fast_path(
        q_capacity=q)
    # d = 100 is one 128-column range; its 2,000 entries fill 32 table rows.
    assert int((np.asarray(sf.fast.cs_range) == 0).sum()) == -(-n * k // q)
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


@pytest.mark.parametrize("square_vals", [False, True])
@pytest.mark.parametrize("n", [257, 1000, 1025])
def test_rmatvec_fast_matches_float64_scatter_off_the_row_grids(
        n, square_vals):
    """X^T.r against a float64 scatter at row counts off the 128-row blocks
    of the ``dz`` table and off ``ROW_PAD`` (one row past it at 1,025), ghost
    entries in some rows: the mirror of the X.w test above."""
    dim, k = 3 * 128 + 77, 6
    rng = np.random.default_rng(n)
    idx, val = _ell(rng, n, dim, k)
    assert (idx == dim).any()
    aux = build_fast_aux(idx, val, dim, q_capacity=64)
    assert aux.n_row_blocks == -(-n // 128)
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
