"""Out-of-core fixed-effect training (optim/out_of_core.py): host-resident
row chunks streamed per pass must reproduce the in-core solve."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.data.batch import LabeledBatch, SparseFeatures
from photon_tpu.functions.problem import GLMOptimizationProblem
from photon_tpu.optim import (OptimizerConfig, OptimizerType,
                              RegularizationContext, RegularizationType)
from photon_tpu.optim.base import (FUNCTION_VALUES_CONVERGED,
                                   GRADIENT_CONVERGED)
from photon_tpu.optim.out_of_core import (ChunkedGLMData, OutOfCoreLBFGS,
                                          run_out_of_core)
from photon_tpu.ops.losses import loss_for_task
from photon_tpu.types import TaskType


def _data(n=700, dim=150, k=8, seed=0, task=TaskType.LOGISTIC_REGRESSION):
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, dim, size=(n, k)).astype(np.int32)
    val = (rng.normal(size=(n, k)) / np.sqrt(k)).astype(np.float32)
    w_true = rng.normal(size=dim).astype(np.float32)
    z = (val * w_true[idx]).sum(1)
    if task == TaskType.LOGISTIC_REGRESSION:
        labels = (rng.random(n) < 1 / (1 + np.exp(-z))).astype(np.float32)
    elif task == TaskType.POISSON_REGRESSION:
        labels = rng.poisson(np.exp(np.clip(z, None, 3))).astype(np.float32)
    else:
        labels = (z + 0.1 * rng.normal(size=n)).astype(np.float32)
    return idx, val, labels


def _problem(task=TaskType.LOGISTIC_REGRESSION, max_iter=120):
    return GLMOptimizationProblem(
        task=task,
        optimizer_type=OptimizerType.LBFGS,
        optimizer_config=OptimizerConfig(max_iterations=max_iter,
                                         tolerance=1e-9),
        regularization=RegularizationContext(RegularizationType.L2),
        reg_weight=1.0,
    )


@pytest.mark.parametrize("task", [TaskType.LOGISTIC_REGRESSION,
                                  TaskType.LINEAR_REGRESSION,
                                  TaskType.POISSON_REGRESSION])
def test_out_of_core_matches_in_core(task):
    idx, val, labels = _data(task=task)
    dim = 150
    problem = _problem(task)

    batch = LabeledBatch(
        features=SparseFeatures(idx=jnp.asarray(idx), val=jnp.asarray(val),
                                dim=dim),
        labels=jnp.asarray(labels),
        offsets=jnp.zeros((len(labels),), jnp.float32),
        weights=jnp.ones((len(labels),), jnp.float32),
    )
    m_in, r_in = problem.run(batch, jnp.zeros((dim,), jnp.float32))

    data = ChunkedGLMData.from_arrays(idx, val, labels, dim, chunk_rows=256)
    assert data.n_chunks == 3  # 700 rows / 256 -> padded chunking exercised
    m_out, r_out = run_out_of_core(problem, data)

    assert int(r_out.converged_reason) in (FUNCTION_VALUES_CONVERGED,
                                           GRADIENT_CONVERGED)
    assert float(r_out.value) == pytest.approx(float(r_in.value), rel=1e-4)
    np.testing.assert_allclose(np.asarray(m_out.coefficients.means),
                               np.asarray(m_in.coefficients.means),
                               rtol=1e-2, atol=1e-2)


def test_out_of_core_weights_and_offsets():
    """Non-trivial offsets and zero-weight rows (the padding convention)
    must match an in-core solve on the same effective data."""
    idx, val, labels = _data(n=500, seed=3)
    dim = 150
    rng = np.random.default_rng(4)
    offsets = rng.normal(size=500).astype(np.float32) * 0.3
    weights = (rng.random(500) > 0.2).astype(np.float32)
    problem = _problem()

    batch = LabeledBatch(
        features=SparseFeatures(idx=jnp.asarray(idx), val=jnp.asarray(val),
                                dim=dim),
        labels=jnp.asarray(labels), offsets=jnp.asarray(offsets),
        weights=jnp.asarray(weights),
    )
    m_in, r_in = problem.run(batch, jnp.zeros((dim,), jnp.float32))
    data = ChunkedGLMData.from_arrays(idx, val, labels, dim, offsets=offsets,
                                      weights=weights, chunk_rows=128)
    m_out, r_out = run_out_of_core(problem, data)
    assert float(r_out.value) == pytest.approx(float(r_in.value), rel=1e-4)
    np.testing.assert_allclose(np.asarray(m_out.coefficients.means),
                               np.asarray(m_in.coefficients.means),
                               rtol=1e-2, atol=1e-2)


def test_out_of_core_pass_count_is_two_per_iteration():
    """Resident-margin line search: probes cost no data pass, so
    passes == 2 (init) + 2 per iteration."""
    idx, val, labels = _data(n=400, seed=5)
    data = ChunkedGLMData.from_arrays(idx, val, labels, 150, chunk_rows=200)
    solver = OutOfCoreLBFGS(
        loss=loss_for_task(TaskType.LOGISTIC_REGRESSION), l2_weight=1.0,
        config=OptimizerConfig(max_iterations=40, tolerance=1e-9),
    )
    res = solver.optimize(data, jnp.zeros((150,), jnp.float32))
    assert int(res.data_passes) == 2 + 2 * int(res.iterations)


def test_out_of_core_value_dtype_and_budget_helpers():
    idx, val, labels = _data(n=300, seed=6)
    data = ChunkedGLMData.from_arrays(idx, val, labels, 150, chunk_rows=128,
                                      value_dtype=jnp.bfloat16)
    assert data.chunks[0].val.dtype == jnp.bfloat16
    # 3 chunks x 128 rows x 8 nnz x (4B idx + 2B val)
    assert data.streamed_bytes_per_pass() == 3 * 128 * 8 * 6
    problem = _problem()
    m, r = run_out_of_core(problem, data)
    assert np.isfinite(float(r.value))


def test_out_of_core_rejects_tron():
    idx, val, labels = _data(n=100, seed=7)
    data = ChunkedGLMData.from_arrays(idx, val, labels, 150)
    problem = GLMOptimizationProblem(
        task=TaskType.LOGISTIC_REGRESSION,
        optimizer_type=OptimizerType.TRON,
        optimizer_config=OptimizerConfig(max_iterations=10),
        regularization=RegularizationContext(RegularizationType.L2),
        reg_weight=1.0,
    )
    with pytest.raises(NotImplementedError):
        run_out_of_core(problem, data)


def test_glm_driver_out_of_core_matches_in_core(tmp_path):
    """--row-chunk-rows routes the single-GLM driver through the streamed
    path; the selected model must score like the in-core fit, and the saved
    model loads through the standard scoring driver."""
    from tests.test_drivers import _write_game_avro
    from photon_tpu.cli import game_scoring_driver, glm_training_driver

    d = tmp_path / "data"
    d.mkdir()
    _write_game_avro(d / "train.avro", seed=11, n_users=6, rows_per_user=40)

    out_ic = tmp_path / "in_core"
    s_ic = glm_training_driver.run([
        "--train-data", str(d / "train.avro"),
        "--output-dir", str(out_ic),
        "--task", "LOGISTIC_REGRESSION",
        "--reg-weights", "1.0",
        "--max-iterations", "60",
        "--normalization", "NONE", "--variance", "NONE",
        "--no-report", "--row-chunk-rows", "0",
    ])
    out_oc = tmp_path / "out_of_core"
    s_oc = glm_training_driver.run([
        "--train-data", str(d / "train.avro"),
        "--output-dir", str(out_oc),
        "--task", "LOGISTIC_REGRESSION",
        "--reg-weights", "1.0",
        "--max-iterations", "60",
        "--normalization", "NONE", "--variance", "NONE",
        "--no-report", "--row-chunk-rows", "64",
    ])
    assert s_oc["mode"] == "out_of_core"
    assert s_oc["n_chunks"] == 4  # 240 rows / 64 -> padded final chunk
    assert s_oc["evaluation"]["AUC"] == pytest.approx(
        s_ic["evaluation"]["AUC"], abs=0.02
    )
    # Saved artifact is a standard GAME model: scores via the normal path.
    ssum = game_scoring_driver.run([
        "--data", str(d / "train.avro"),
        "--model-dir", str(out_oc / "best"),
        "--output-dir", str(tmp_path / "scores"),
        "--evaluators", "AUC",
    ])
    assert ssum["evaluation"]["AUC"] == pytest.approx(
        s_oc["evaluation"]["AUC"], abs=0.02
    )


def test_glm_driver_out_of_core_guards(tmp_path):
    from tests.test_drivers import _write_game_avro
    from photon_tpu.cli import glm_training_driver

    d = tmp_path / "data"
    d.mkdir()
    _write_game_avro(d / "train.avro", seed=12, n_users=4, rows_per_user=10)
    with pytest.raises(ValueError, match="out-of-core training supports"):
        glm_training_driver.run([
            "--train-data", str(d / "train.avro"),
            "--output-dir", str(tmp_path / "o"),
            "--task", "LOGISTIC_REGRESSION",
            "--normalization", "STANDARDIZATION",
            "--row-chunk-rows", "32",
        ])


def test_out_of_core_rejects_l1_component():
    from photon_tpu.optim.regularization import elastic_net_context

    idx, val, labels = _data(n=100, seed=8)
    data = ChunkedGLMData.from_arrays(idx, val, labels, 150)
    problem = GLMOptimizationProblem(
        task=TaskType.LOGISTIC_REGRESSION,
        optimizer_type=OptimizerType.LBFGS,
        optimizer_config=OptimizerConfig(max_iterations=10),
        regularization=elastic_net_context(0.5),
        reg_weight=1.0,
    )
    with pytest.raises(NotImplementedError, match="L1 component"):
        run_out_of_core(problem, data)


def test_from_stream_regrows_on_wider_chunks():
    """A stream whose ELL width grows mid-way must ghost-pad earlier chunks
    out to the final width (incremental assembly never sees the full K up
    front)."""
    class _Chunk:
        def __init__(self, idx, val, dim):
            n = idx.shape[0]
            self.features = {"s": SparseFeatures(idx=idx, val=val, dim=dim)}
            self.labels = np.zeros(n, np.float32)
            self.offsets = np.zeros(n, np.float32)
            self.weights = np.ones(n, np.float32)
            self.n_rows = n

    dim = 40
    rng = np.random.default_rng(20)
    a = _Chunk(rng.integers(0, dim, (30, 2)).astype(np.int32),
               rng.normal(size=(30, 2)).astype(np.float32), dim)
    b = _Chunk(rng.integers(0, dim, (30, 5)).astype(np.int32),
               rng.normal(size=(30, 5)).astype(np.float32), dim)
    data = ChunkedGLMData.from_stream(iter([a, b]), "s", dim, chunk_rows=25)
    assert all(c.idx.shape[1] == 5 for c in data.chunks)
    assert data.n_rows == 60
    # Ghost-padded columns of the regrown first chunk: idx == dim, val == 0.
    assert (data.chunks[0].idx[:, 2:] == dim).all()
    assert (data.chunks[0].val[:, 2:] == 0).all()


def test_glm_driver_out_of_core_validates_chunks(tmp_path):
    """--data-validation applies per streamed chunk: NaN labels must raise,
    not train a garbage model."""
    import jax.numpy as jnp_  # noqa: F401 - ensure jax configured by conftest
    from photon_tpu.io.avro import write_container
    from tests.test_drivers import RECORD_SCHEMA
    from photon_tpu.cli import glm_training_driver

    d = tmp_path / "data"
    d.mkdir()
    recs = [{
        "uid": str(i),
        "response": float("nan") if i == 7 else float(i % 2),
        "offset": None, "weight": None,
        "features": [{"name": "g", "term": "0", "value": 1.0}],
        "metadataMap": {},
    } for i in range(20)]
    write_container(str(d / "train.avro"), RECORD_SCHEMA, recs)
    with pytest.raises(ValueError, match="label|response|finite|NaN|nan"):
        glm_training_driver.run([
            "--train-data", str(d / "train.avro"),
            "--output-dir", str(tmp_path / "o"),
            "--task", "LOGISTIC_REGRESSION",
            "--normalization", "NONE", "--variance", "NONE",
            "--no-report", "--row-chunk-rows", "8",
        ])


def test_from_stream_on_chunk_fails_fast():
    """``on_chunk`` fires as each chunk is assembled, so a validation error
    in early data aborts the stream without consuming (or decoding) the
    rest — the fail-fast contract the OOC driver's --data-validation relies
    on at 100M-row scale."""
    class _Chunk:
        def __init__(self, idx, val, dim):
            n = idx.shape[0]
            self.features = {"s": SparseFeatures(idx=idx, val=val, dim=dim)}
            self.labels = np.zeros(n, np.float32)
            self.offsets = np.zeros(n, np.float32)
            self.weights = np.ones(n, np.float32)
            self.n_rows = n

    dim = 16
    rng = np.random.default_rng(7)

    def mk():
        return _Chunk(rng.integers(0, dim, (10, 2)).astype(np.int32),
                      rng.normal(size=(10, 2)).astype(np.float32), dim)

    consumed = []

    def stream():
        for i in range(10):
            consumed.append(i)
            yield mk()

    seen = []

    def on_chunk(i, c, lab, off, wgt):
        seen.append(i)
        assert c.idx.shape == (10, 2)
        if i == 1:
            raise ValueError("bad chunk")

    with pytest.raises(ValueError, match="bad chunk"):
        ChunkedGLMData.from_stream(stream(), "s", dim, chunk_rows=10,
                                   on_chunk=on_chunk)
    assert seen == [0, 1]
    # The stream stopped at the failing chunk; the tail was never decoded.
    assert len(consumed) <= 3


def test_checkpoint_resume_matches_uninterrupted(tmp_path):
    """A solve killed after iteration k and resumed from its checkpoint
    reaches the same optimum as an uninterrupted run (a config-5 solve
    runs for hours, and machines do not always last that long; VERDICT r3
    ask #6)."""
    from photon_tpu.ops.losses import loss_for_task
    from photon_tpu.optim.out_of_core import OutOfCoreLBFGS

    idx, val, labels = _data(n=400, seed=11)
    data = ChunkedGLMData.from_arrays(idx, val, labels, 150, chunk_rows=128)
    ck = str(tmp_path / "ck.npz")

    def solver(path=None, max_it=30):
        return OutOfCoreLBFGS(
            loss=loss_for_task(TaskType.LOGISTIC_REGRESSION),
            l2_weight=0.5,
            config=OptimizerConfig(max_iterations=max_it, tolerance=1e-7),
            checkpoint_path=path,
            checkpoint_min_interval_s=0.0,  # every iteration (test speed)
        )

    w0 = jnp.zeros((150,), jnp.float32)
    ref = solver().optimize(data, w0)

    # "Killed" run: stop after 3 iterations by raising from progress.
    class _Stop(Exception):
        pass

    s1 = solver(ck)

    def bomb(it, f, gn, p):
        if it >= 3:
            raise _Stop

    s1 = dataclasses.replace(s1, progress=bomb)
    with pytest.raises(_Stop):
        s1.optimize(data, w0)
    import numpy as _np
    st = _np.load(ck, allow_pickle=False)
    assert int(st["it"]) == 3  # checkpoint BEFORE the kill point survived

    # Resume completes and matches the uninterrupted optimum.
    res = solver(ck).optimize(data, w0)
    assert int(res.converged_reason) == int(ref.converged_reason)
    _np.testing.assert_allclose(
        _np.asarray(res.x), _np.asarray(ref.x), rtol=2e-4, atol=2e-5
    )
    assert abs(float(res.value) - float(ref.value)) < 1e-3

    # A different problem (other λ) must NOT resume from this file: its
    # result must match a FRESH λ=2 solve, not the stale λ=0.5 optimum.
    fresh2 = dataclasses.replace(solver(), l2_weight=2.0).optimize(data, w0)
    res2 = dataclasses.replace(solver(ck), l2_weight=2.0).optimize(data, w0)
    _np.testing.assert_allclose(
        _np.asarray(res2.x), _np.asarray(fresh2.x), rtol=2e-4, atol=2e-5
    )
    assert abs(float(res2.value) - float(ref.value)) > 1e-2  # not λ=0.5's


def test_mesh_streaming_matches_single_device():
    """P1 x out-of-core: row-sharded chunk streaming over an 8-device mesh
    produces the same solve as single-device OOC (GSPMD inserts the
    value/grad all-reduces; SURVEY.md §2.6 P1, §2.2 distributed objective)."""
    from photon_tpu.ops.losses import loss_for_task
    from photon_tpu.parallel.mesh import make_mesh

    idx, val, labels = _data(n=512, seed=21)
    data = ChunkedGLMData.from_arrays(idx, val, labels, 150, chunk_rows=128)
    cfg = OptimizerConfig(max_iterations=25, tolerance=1e-7)

    def solve(mesh=None):
        return OutOfCoreLBFGS(
            loss=loss_for_task(TaskType.LOGISTIC_REGRESSION),
            l2_weight=0.3, config=cfg, mesh=mesh,
        ).optimize(data, jnp.zeros((150,), jnp.float32))

    ref = solve()
    res = solve(make_mesh({"data": 8}))
    # The 8-way all-reduce reassociates float32 sums, so iteration-exact
    # equality is not guaranteed across versions — compare the optimum and
    # allow the step count a ±1 drift.
    assert abs(int(res.iterations) - int(ref.iterations)) <= 1
    assert float(res.value) == pytest.approx(float(ref.value), rel=1e-5)
    # rtol 1e-3: the reassociated f32 sums shift an Armijo boundary on some
    # jax versions, leaving one late-step coefficient ~8e-4 relative off
    # while value/iterations still agree (observed on jax 0.4.37).
    np.testing.assert_allclose(
        np.asarray(res.x), np.asarray(ref.x), rtol=1e-3, atol=5e-5
    )

    # chunk_rows that don't divide the mesh axis fail loudly, not wrongly
    bad = ChunkedGLMData.from_arrays(idx, val, labels, 150, chunk_rows=100)
    with pytest.raises(ValueError, match="divide evenly"):
        OutOfCoreLBFGS(
            loss=loss_for_task(TaskType.LOGISTIC_REGRESSION),
            config=cfg, mesh=make_mesh({"data": 8}),
        ).optimize(bad, jnp.zeros((150,), jnp.float32))


def test_mesh_streaming_checkpoint_resume(tmp_path):
    """A killed MESH solve resumes under the same mesh: restored state is
    re-replicated, so the resumed run matches the uninterrupted one."""
    from photon_tpu.ops.losses import loss_for_task
    from photon_tpu.parallel.mesh import make_mesh

    idx, val, labels = _data(n=512, seed=22)
    data = ChunkedGLMData.from_arrays(idx, val, labels, 150, chunk_rows=128)
    mesh = make_mesh({"data": 8})
    ck = str(tmp_path / "ck.npz")

    def solver(path=None):
        return OutOfCoreLBFGS(
            loss=loss_for_task(TaskType.LOGISTIC_REGRESSION), l2_weight=0.3,
            config=OptimizerConfig(max_iterations=25, tolerance=1e-7),
            checkpoint_path=path, checkpoint_min_interval_s=0.0, mesh=mesh,
        )

    w0 = jnp.zeros((150,), jnp.float32)
    ref = solver().optimize(data, w0)

    class _Stop(Exception):
        pass

    def bomb(it, f, gn, p):
        if it >= 3:
            raise _Stop

    with pytest.raises(_Stop):
        dataclasses.replace(solver(ck), progress=bomb).optimize(data, w0)
    res = solver(ck).optimize(data, w0)
    # The resumed trajectory re-derives scores from w and the 8-way
    # all-reduce reassociates sums, so line-search decisions can differ;
    # both runs reach the same optimum (value to 1e-5) but coefficients in
    # the flat tail may drift ~1e-3 — compare at convergence tolerance.
    assert float(res.value) == pytest.approx(float(ref.value), rel=1e-5)
    np.testing.assert_allclose(
        np.asarray(res.x), np.asarray(ref.x), rtol=2e-2, atol=5e-3
    )


# -- OWL-QN out-of-core (L1/elastic-net at beyond-HBM scale) ----------------


def _owlqn_problem(task, reg, reg_weight=0.05, max_iter=150, alpha=0.5):
    from photon_tpu.optim.regularization import elastic_net_context

    if reg == RegularizationType.ELASTIC_NET:
        ctx = elastic_net_context(alpha)
    else:
        ctx = RegularizationContext(reg)
    return GLMOptimizationProblem(
        task=task,
        optimizer_type=OptimizerType.OWLQN,
        optimizer_config=OptimizerConfig(max_iterations=max_iter,
                                         tolerance=1e-9),
        regularization=ctx,
        reg_weight=reg_weight,
    )


@pytest.mark.parametrize("task", [
    TaskType.LOGISTIC_REGRESSION,
    TaskType.LINEAR_REGRESSION,
    TaskType.POISSON_REGRESSION,
    TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM,
])
def test_owlqn_out_of_core_matches_in_core(task):
    """OOC OWL-QN reproduces the in-core orthant-wise solve on all four
    losses: same pseudo-gradient/alignment/projection semantics, the only
    difference is streamed (value-only) line-search probes.

    The hinge case runs under ELASTIC_NET and binary labels: with L1 only,
    the piecewise-quadratic hinge objective has near-flat directions, so
    two float-reassociated trajectories legitimately reach value-equal but
    coefficient-different optima — the L2 component pins the optimum."""
    svm = task == TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM
    idx, val, labels = _data(
        n=600, task=TaskType.LOGISTIC_REGRESSION if svm else task, seed=31
    )
    dim = 150
    problem = _owlqn_problem(
        task,
        RegularizationType.ELASTIC_NET if svm else RegularizationType.L1,
    )

    batch = LabeledBatch(
        features=SparseFeatures(idx=jnp.asarray(idx), val=jnp.asarray(val),
                                dim=dim),
        labels=jnp.asarray(labels),
        offsets=jnp.zeros((len(labels),), jnp.float32),
        weights=jnp.ones((len(labels),), jnp.float32),
    )
    m_in, r_in = problem.run(batch, jnp.zeros((dim,), jnp.float32))
    data = ChunkedGLMData.from_arrays(idx, val, labels, dim, chunk_rows=256)
    m_out, r_out = run_out_of_core(problem, data)

    # rel 5e-4, not 1e-4: the streamed per-chunk reduction reassociates
    # float32 sums, and on a NON-smooth objective a 1-ulp line-search
    # difference can flip a coordinate's orthant and legitimately land on a
    # near-tied endpoint (observed: OOC ~1e-4 BELOW in-core on poisson and
    # hinge). The zero-set agreement below is the real semantic check.
    assert float(r_out.value) == pytest.approx(float(r_in.value), rel=5e-4)
    np.testing.assert_allclose(np.asarray(m_out.coefficients.means),
                               np.asarray(m_in.coefficients.means),
                               rtol=1e-2, atol=1e-2)
    # Both paths must agree on WHICH coefficients die (the orthant
    # machinery's signature). λ=0.05 sparsifies the logistic/linear fits
    # (asserted — a regression that stops zeroing coordinates must fail);
    # the poisson/hinge gradients are larger and keep every coordinate
    # alive at this λ, so only the agreement check binds there.
    z_in = np.asarray(m_in.coefficients.means) == 0.0
    z_out = np.asarray(m_out.coefficients.means) == 0.0
    if task in (TaskType.LOGISTIC_REGRESSION, TaskType.LINEAR_REGRESSION):
        assert z_in.sum() > 0
    assert (z_in == z_out).mean() > 0.95


def test_owlqn_out_of_core_elastic_net_and_mask():
    """Elastic net splits λ into L1/L2 parts; a reg mask exempts column 0
    from BOTH penalties (the intercept convention)."""
    from photon_tpu.optim.out_of_core import OutOfCoreOWLQN

    idx, val, labels = _data(n=500, seed=32)
    dim = 150
    problem = _owlqn_problem(
        TaskType.LOGISTIC_REGRESSION, RegularizationType.ELASTIC_NET,
        reg_weight=0.1,
    )
    mask = jnp.ones((dim,), jnp.float32).at[0].set(0.0)
    batch = LabeledBatch(
        features=SparseFeatures(idx=jnp.asarray(idx), val=jnp.asarray(val),
                                dim=dim),
        labels=jnp.asarray(labels),
        offsets=jnp.zeros((len(labels),), jnp.float32),
        weights=jnp.ones((len(labels),), jnp.float32),
    )
    m_in, r_in = problem.run(batch, jnp.zeros((dim,), jnp.float32),
                             reg_mask=mask)
    data = ChunkedGLMData.from_arrays(idx, val, labels, dim, chunk_rows=128)
    m_out, r_out = run_out_of_core(problem, data, reg_mask=mask)
    assert float(r_out.value) == pytest.approx(float(r_in.value), rel=1e-4)
    np.testing.assert_allclose(np.asarray(m_out.coefficients.means),
                               np.asarray(m_in.coefficients.means),
                               rtol=1e-2, atol=1e-2)
    # The solver facade agrees with the problem-level entry.
    from photon_tpu.ops.losses import loss_for_task

    direct = OutOfCoreOWLQN(
        loss=loss_for_task(TaskType.LOGISTIC_REGRESSION),
        l2_weight=0.05, l1_weight=0.05, reg_mask=mask,
        config=OptimizerConfig(max_iterations=150, tolerance=1e-9),
    ).optimize(data, jnp.zeros((dim,), jnp.float32))
    assert float(direct.value) == pytest.approx(float(r_out.value), rel=1e-6)


def test_owlqn_out_of_core_checkpoint_resume(tmp_path):
    """A killed OOC OWL-QN solve resumes at iteration k and reaches the
    uninterrupted optimum; a different λ never cross-resumes."""
    from photon_tpu.ops.losses import loss_for_task
    from photon_tpu.optim.out_of_core import OutOfCoreOWLQN

    idx, val, labels = _data(n=400, seed=33)
    data = ChunkedGLMData.from_arrays(idx, val, labels, 150, chunk_rows=128)
    ck = str(tmp_path / "ck.npz")

    def solver(path=None, l1=0.05):
        return OutOfCoreOWLQN(
            loss=loss_for_task(TaskType.LOGISTIC_REGRESSION),
            l2_weight=0.1, l1_weight=l1,
            config=OptimizerConfig(max_iterations=80, tolerance=1e-9),
            checkpoint_path=path, checkpoint_min_interval_s=0.0,
        )

    w0 = jnp.zeros((150,), jnp.float32)
    ref = solver().optimize(data, w0)

    class _Stop(Exception):
        pass

    def bomb(it, f, gn, p):
        if it >= 3:
            raise _Stop

    with pytest.raises(_Stop):
        dataclasses.replace(solver(ck), progress=bomb).optimize(data, w0)
    st = np.load(ck, allow_pickle=False)
    assert int(st["it"]) == 3
    res = solver(ck).optimize(data, w0)
    assert float(res.value) == pytest.approx(float(ref.value), rel=1e-5)
    np.testing.assert_allclose(np.asarray(res.x), np.asarray(ref.x),
                               rtol=2e-3, atol=1e-4)
    # Different l1 weight: fresh solve, not a stale resume.
    other = solver(ck, l1=0.5).optimize(data, w0)
    fresh = solver(l1=0.5).optimize(data, w0)
    np.testing.assert_allclose(np.asarray(other.x), np.asarray(fresh.x),
                               rtol=2e-3, atol=1e-4)


def test_owlqn_out_of_core_mesh_matches_single_device():
    """OWL-QN streams row-sharded over a data mesh exactly like the smooth
    solver (orthant machinery is replicated coefficient-space math)."""
    from photon_tpu.ops.losses import loss_for_task
    from photon_tpu.optim.out_of_core import OutOfCoreOWLQN
    from photon_tpu.parallel.mesh import make_mesh

    idx, val, labels = _data(n=512, seed=34)
    data = ChunkedGLMData.from_arrays(idx, val, labels, 150, chunk_rows=128)

    def solve(mesh=None):
        return OutOfCoreOWLQN(
            loss=loss_for_task(TaskType.LOGISTIC_REGRESSION),
            l2_weight=0.1, l1_weight=0.05,
            config=OptimizerConfig(max_iterations=40, tolerance=1e-7),
            mesh=mesh,
        ).optimize(data, jnp.zeros((150,), jnp.float32))

    ref = solve()
    res = solve(make_mesh({"data": 8}))
    assert float(res.value) == pytest.approx(float(ref.value), rel=1e-5)
    np.testing.assert_allclose(np.asarray(res.x), np.asarray(ref.x),
                               rtol=2e-2, atol=5e-3)


def test_glm_driver_out_of_core_owlqn(tmp_path):
    """--optimizer OWLQN --regularization L1 routes through the OOC path
    (auto-router accepts the pairing) and trains a model that scores."""
    from tests.test_drivers import _write_game_avro
    from photon_tpu.cli import glm_training_driver

    d = tmp_path / "data"
    d.mkdir()
    _write_game_avro(d / "train.avro", seed=35, n_users=6, rows_per_user=40)
    s = glm_training_driver.run([
        "--train-data", str(d / "train.avro"),
        "--output-dir", str(tmp_path / "out"),
        "--task", "LOGISTIC_REGRESSION",
        "--optimizer", "OWLQN", "--regularization", "L1",
        "--reg-weights", "0.1",
        "--max-iterations", "60",
        "--normalization", "NONE", "--variance", "NONE",
        "--no-report", "--row-chunk-rows", "64",
    ])
    assert s["mode"] == "out_of_core"
    assert s["evaluation"]["AUC"] > 0.5
