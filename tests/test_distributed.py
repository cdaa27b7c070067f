"""Distributed data-parallel tests on the virtual 8-device CPU mesh —
the rebuild's equivalent of the reference's Spark `local[*]` integration tier
(SURVEY.md §4): the REAL psum/shard_map/GSPMD code paths execute here.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.data.batch import make_dense_batch, LabeledBatch, ell_from_rows
from photon_tpu.functions.objective import GLMObjective, intercept_reg_mask
from photon_tpu.functions.problem import GLMOptimizationProblem
from photon_tpu.optim import L2RegularizationContext, OptimizerConfig, OptimizerType
from photon_tpu.parallel import (
    fit_data_parallel,
    make_mesh,
    spmd_value_and_grad,
)
from photon_tpu.ops.losses import LogisticLoss
from photon_tpu.types import TaskType


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) == 8, "conftest must provide 8 CPU devices"
    return make_mesh({"data": 8})


def _make_problem():
    return GLMOptimizationProblem(
        task=TaskType.LOGISTIC_REGRESSION,
        optimizer_type=OptimizerType.LBFGS,
        optimizer_config=OptimizerConfig(max_iterations=100),
        regularization=L2RegularizationContext,
        reg_weight=0.5,
        reg_mask=intercept_reg_mask(9, 0),
    )


def _data(rng, n=320, d=8):
    x = np.concatenate([np.ones((n, 1)), rng.normal(size=(n, d))], axis=1)
    w = rng.normal(size=d + 1) * 0.5
    y = (1 / (1 + np.exp(-(x @ w))) > rng.uniform(size=n)).astype(float)
    return make_dense_batch(x, y, dtype=jnp.float64)


def test_spmd_value_and_grad_matches_local(rng, mesh):
    batch = _data(rng)
    obj = GLMObjective(loss=LogisticLoss, l2_weight=0.5,
                       reg_mask=intercept_reg_mask(9, 0))
    w = jnp.asarray(rng.normal(size=9))
    v_local, g_local = obj.value_and_grad(w, batch)
    vg = spmd_value_and_grad(obj, batch, mesh)
    v_spmd, g_spmd = vg(w)
    np.testing.assert_allclose(v_spmd, v_local, rtol=1e-10)
    np.testing.assert_allclose(g_spmd, g_local, rtol=1e-9)


def test_gspmd_fit_matches_single_device(rng, mesh):
    batch = _data(rng)
    prob = _make_problem()
    w0 = jnp.zeros(9, jnp.float64)
    model_1, res_1 = prob.run(batch, w0)
    model_8, res_8 = fit_data_parallel(prob, batch, w0, mesh)
    np.testing.assert_allclose(model_8.coefficients.means,
                               model_1.coefficients.means, atol=1e-8)
    assert int(res_8.converged_reason) == int(res_1.converged_reason)


def test_optimizer_over_spmd_objective(rng, mesh):
    """Optimizer loop outside, shard_map objective inside — collectives ride
    inside the jitted while_loop (the explicit variant of the north star)."""
    from photon_tpu.optim import LBFGS

    batch = _data(rng)
    obj = GLMObjective(loss=LogisticLoss, l2_weight=0.5,
                       reg_mask=intercept_reg_mask(9, 0))
    vg = spmd_value_and_grad(obj, batch, mesh)
    res_spmd = jax.jit(
        lambda w0: LBFGS(OptimizerConfig(max_iterations=100)).optimize(vg, w0)
    )(jnp.zeros(9, jnp.float64))
    res_local = LBFGS(OptimizerConfig(max_iterations=100)).optimize(
        obj.bind(batch), jnp.zeros(9, jnp.float64)
    )
    np.testing.assert_allclose(res_spmd.x, res_local.x, atol=1e-8)


def test_sparse_batch_data_parallel(rng, mesh):
    n, d = 160, 20
    dense = rng.normal(size=(n, d)) * (rng.uniform(size=(n, d)) < 0.25)
    rows = [(np.nonzero(dense[i])[0], dense[i][np.nonzero(dense[i])[0]])
            for i in range(n)]
    y = rng.integers(0, 2, n).astype(float)
    sb = LabeledBatch(
        features=ell_from_rows(rows, dim=d, dtype=jnp.float64),
        labels=jnp.asarray(y), offsets=jnp.zeros(n), weights=jnp.ones(n),
    )
    prob = GLMOptimizationProblem(
        task=TaskType.LOGISTIC_REGRESSION,
        regularization=L2RegularizationContext, reg_weight=0.3,
    )
    w0 = jnp.zeros(d, jnp.float64)
    m1, _ = prob.run(sb, w0)
    m8, _ = fit_data_parallel(prob, sb, w0, mesh)
    np.testing.assert_allclose(m8.coefficients.means, m1.coefficients.means,
                               atol=1e-8)


def test_uneven_rows_reject_or_pad(rng, mesh):
    # 321 rows don't divide 8; pad_rows_to_multiple zero-fills, which already
    # leaves padded rows at weight 0 — the padded fit must equal the exact one.
    from photon_tpu.parallel.mesh import pad_rows_to_multiple

    batch = _data(rng, n=321)
    padded = pad_rows_to_multiple(batch, 8)
    assert padded.n_rows == 328
    np.testing.assert_array_equal(np.asarray(padded.weights)[321:], 0.0)
    prob = _make_problem()
    m_pad, _ = fit_data_parallel(prob, padded, jnp.zeros(9, jnp.float64), mesh)
    m_ref, _ = prob.run(batch, jnp.zeros(9, jnp.float64))
    np.testing.assert_allclose(m_pad.coefficients.means,
                               m_ref.coefficients.means, atol=1e-8)


@pytest.mark.parametrize("holder", ["labeled_batch", "sparse", "dense"])
def test_strip_unshardable_aux(rng, holder):
    """Before rows are distributed the ``fast`` tables come off (the column
    table does not shard by rows) and nothing else changes: a LabeledBatch
    keeps its other leaves, bare sparse features their ELL arrays, and what
    holds no tables comes back as it went in."""
    from photon_tpu.parallel.mesh import strip_unshardable_aux

    if holder == "dense":
        batch = _data(rng, n=16)
        assert strip_unshardable_aux(batch) is batch
        assert strip_unshardable_aux(batch.features) is batch.features
        return
    rows = [(rng.choice(12, 3, replace=False), rng.normal(size=3))
            for _ in range(16)]
    bare = ell_from_rows(rows, dim=12)
    assert strip_unshardable_aux(bare) is bare
    fast = bare.with_fast_path(q_capacity=8)
    if holder == "sparse":
        out = strip_unshardable_aux(fast)
        assert out.fast is None and out.idx is fast.idx and out.val is fast.val
        return
    batch = LabeledBatch(features=fast, labels=jnp.zeros(16),
                         offsets=jnp.zeros(16), weights=jnp.ones(16))
    out = strip_unshardable_aux(batch)
    assert out.features.fast is None and out.features.idx is fast.idx
    assert out.labels is batch.labels and out.weights is batch.weights
    assert strip_unshardable_aux(out) is out


class TestMultiSliceDCN:
    """2-level dcn x ici meshes (SURVEY.md §5.8): the 8 virtual devices play
    2 slices x 4 chips; psums over ("dcn", "data") lower hierarchically on
    real multi-slice topologies and must be numerically identical to the
    single-axis path here."""

    @pytest.fixture(scope="class")
    def mesh2(self):
        from photon_tpu.parallel.mesh import make_multislice_mesh

        return make_multislice_mesh(n_slices=2, axis_sizes={"data": 4})

    def test_mesh_shape_and_axis_order(self, mesh2):
        assert mesh2.axis_names == ("dcn", "data")
        assert mesh2.shape["dcn"] == 2 and mesh2.shape["data"] == 4

    def test_spmd_value_and_grad_hierarchical(self, rng, mesh2):
        batch = _data(rng)
        obj = GLMObjective(loss=LogisticLoss, l2_weight=0.5,
                           reg_mask=intercept_reg_mask(9, 0))
        w = jnp.asarray(rng.normal(size=9))
        v_local, g_local = obj.value_and_grad(w, batch)
        vg = spmd_value_and_grad(obj, batch, mesh2, data_axis=("dcn", "data"))
        v, g = vg(w)
        np.testing.assert_allclose(v, v_local, rtol=1e-10)
        np.testing.assert_allclose(g, g_local, rtol=1e-9)

    def test_fit_matches_single_slice(self, rng, mesh2):
        batch = _data(rng)
        problem = _make_problem()
        w0 = jnp.zeros(9, jnp.float64)
        m_single, r_single = jax.jit(problem.run)(batch, w0)
        m_dcn, r_dcn = fit_data_parallel(
            problem, batch, w0, mesh2, data_axis=("dcn", "data")
        )
        np.testing.assert_allclose(
            np.asarray(m_dcn.coefficients.means),
            np.asarray(m_single.coefficients.means), atol=1e-7,
        )
        np.testing.assert_allclose(
            float(r_dcn.value), float(r_single.value), rtol=1e-9
        )

    def test_uneven_rows_padded_over_both_axes(self, rng, mesh2):
        batch = _data(rng, n=301)   # 301 % 8 != 0 -> weight-0 padding
        problem = _make_problem()
        w0 = jnp.zeros(9, jnp.float64)
        m_single, _ = jax.jit(problem.run)(batch, w0)
        m_dcn, _ = fit_data_parallel(
            problem, batch, w0, mesh2, data_axis=("dcn", "data")
        )
        np.testing.assert_allclose(
            np.asarray(m_dcn.coefficients.means),
            np.asarray(m_single.coefficients.means), atol=1e-7,
        )

    def test_model_parallel_on_dcn_mesh(self, rng):
        from photon_tpu.parallel.mesh import make_multislice_mesh
        from photon_tpu.parallel.model_parallel import fit_model_parallel

        mesh3 = make_multislice_mesh(
            n_slices=2, axis_sizes={"data": 2, "model": 2}
        )
        assert mesh3.axis_names == ("dcn", "data", "model")
        batch = _data(rng, n=320)
        problem = _make_problem()
        w0 = jnp.zeros(9, jnp.float64)
        m_single, _ = jax.jit(problem.run)(batch, w0)
        m_mp, _ = fit_model_parallel(
            problem, batch, w0, mesh3, data_axis=("dcn", "data")
        )
        np.testing.assert_allclose(
            np.asarray(m_mp.coefficients.means),
            np.asarray(m_single.coefficients.means), atol=2e-5,
        )


class TestMultiHostPrimitives:
    """parallel/distributed.py: single-process no-op semantics + the
    process-local -> global assembly primitive (SURVEY.md §5.8)."""

    def test_initialize_is_noop_single_process(self):
        from photon_tpu.parallel.distributed import initialize_distributed

        assert initialize_distributed() is False   # no coordinator spun up
        assert jax.process_count() == 1

    def test_process_file_shard(self):
        from photon_tpu.parallel.distributed import process_file_shard

        i, n = process_file_shard()
        assert (i, n) == (0, 1)

    def test_global_batch_from_local_matches_device_put(self, mesh):
        from photon_tpu.parallel.distributed import global_batch_from_local
        from photon_tpu.parallel.mesh import shard_batch_pytree

        rng = np.random.default_rng(0)
        batch = {
            "x": rng.normal(size=(64, 5)).astype(np.float32),
            "y": rng.normal(size=(64,)).astype(np.float32),
        }
        g = global_batch_from_local(batch, mesh)
        ref = shard_batch_pytree(
            {k: jnp.asarray(v) for k, v in batch.items()}, mesh
        )
        for k in batch:
            assert g[k].shape == batch[k].shape
            assert g[k].sharding == ref[k].sharding
            np.testing.assert_array_equal(np.asarray(g[k]), batch[k])

    def test_benign_init_phrases_pinned_to_installed_jax(self):
        """ADVICE r3: ensure_initialized classifies double-init as benign by
        matching exact jax error text; a jax upgrade that rewords those
        messages would silently turn a benign double-init into a hard
        failure. Pin the matched phrases against the installed jax source so
        the upgrade trips THIS test instead of breaking single-host flows."""
        import inspect

        import jax._src.distributed as jdist

        src = inspect.getsource(jdist).lower()
        # Phrases matched in photon_tpu/parallel/distributed.py (benign set).
        for phrase in ("only be called once", "must be called before"):
            assert phrase in src, (
                f"jax {jax.__version__} no longer raises {phrase!r}: update "
                "the benign-error classification in parallel/distributed.py"
            )
