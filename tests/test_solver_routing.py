"""Entity-sub-batched Newton solves + measured cost-model solver routing.

Covers the round-6 contracts: chunked-vs-full solver agreement across all
four losses and both dtypes, inert padding lanes, the static chunked tiers
engaging where the budget gate refuses full buckets, the calibration race
(one-time, persisted, winner-respected, vmapped fallback when every Newton
variant is refused), the compile/solve timing split, and retrace-sentinel
silence across a multi-sweep fit (the chunk ladder is a closed set).
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.data.random_effect import build_random_effect_dataset
from photon_tpu.functions.problem import GLMOptimizationProblem
from photon_tpu.game import newton_re, solver_routing, train_random_effects
from photon_tpu.game import random_effect as re_mod
from photon_tpu.obs import retrace
from photon_tpu.optim import (
    OptimizerConfig,
    OptimizerType,
    RegularizationContext,
    RegularizationType,
)
from photon_tpu.types import TaskType
from tests.test_random_effect import _make_entity_data

L2 = RegularizationContext(RegularizationType.L2)
L1 = RegularizationContext(RegularizationType.L1)


# A float32 lane that has converged takes a last Newton step of about 1e-4
# whose line search float32 cannot resolve (it changes the objective by less
# than one rounding), so the last bit of the direction decides it, and on
# the CPU that bit moves with the batch's size: LLVM contracts the lane
# Cholesky's ``a - l * l`` into a fused multiply-add at some widths of the
# entity axis and not at others. The float32 re-batching cases stop before
# that step (no solve changes a decision there, and the answers agree to
# rounding); the float64 cases run to the default tolerance.
F32_REBATCH_TOL = 1e-4


def _problem(task=TaskType.LOGISTIC_REGRESSION, reg=L2,
             optimizer=OptimizerType.LBFGS, reg_weight=0.5, max_iter=60,
             tolerance=OptimizerConfig().tolerance):
    return GLMOptimizationProblem(
        task=task,
        optimizer_config=OptimizerConfig(max_iterations=max_iter,
                                         tolerance=tolerance),
        optimizer_type=optimizer,
        regularization=reg,
        reg_weight=reg_weight,
    )


def _bucket_setup(rng, dtype=np.float32, **data_kw):
    """One smallish dataset + the per-bucket solver inputs for bucket 0."""
    idx, val, labels, keys = _make_entity_data(rng, **data_kw)
    ds = build_random_effect_dataset(
        "userId", keys, idx, val, labels, global_dim=50, dtype=dtype)
    b = max(ds.buckets, key=lambda bb: bb.n_entities)
    offsets = jnp.zeros((ds.n_rows,), dtype)
    batches = b.local_batches(offsets)
    e, p = b.n_entities, b.local_dim
    w0 = jnp.zeros((e, p), b.val.dtype)
    mask = jnp.ones((e, p), b.val.dtype)
    return ds, b, batches, w0, mask


@pytest.mark.parametrize("task", list(TaskType))
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_chunked_matches_full_primal_all_losses(rng, task, dtype):
    """Sub-batched primal Newton must agree with the full-bucket solve to
    solver tolerance for every loss family and both dtypes — chunking only
    re-batches the entity axis, it must not move any optimum."""
    problem = (_problem(task=task) if dtype == np.float64 else
               _problem(task=task, tolerance=F32_REBATCH_TOL))
    _, b, batches, w0, mask = _bucket_setup(rng, dtype=dtype)
    full_m, full_r = newton_re.fit_bucket_newton(problem, batches, w0, mask,
                                                 None)

    def fit_one(bb, w, m, pr):
        return newton_re.fit_bucket_newton(problem, bb, w, m, pr)

    # chunk=4 does not divide most entity counts -> padded tail exercised.
    ch_m, ch_r = newton_re.fit_bucket_in_chunks(fit_one, 4, batches, w0,
                                                mask, None)
    tol = 1e-10 if dtype == np.float64 else 2e-5
    np.testing.assert_allclose(np.asarray(ch_m.coefficients.means),
                               np.asarray(full_m.coefficients.means),
                               atol=tol)
    np.testing.assert_allclose(np.asarray(ch_r.value),
                               np.asarray(full_r.value), atol=tol)


def test_rebatched_float32_fits_are_each_at_the_optimum(rng):
    """The float32 path at the default tolerance, where the last line search
    is rounding's (``F32_REBATCH_TOL``): the whole and the chunked solve are
    each held by their own float64 gradient. A lane's objective is
    ``reg_weight``-strongly convex, so a point lies within ``|grad| /
    reg_weight`` of its optimum; the two solutions lie within the sum of
    those of each other (1.6e-4 apart in one lane of 3 here, bounds 5.3e-4
    and 7.7e-7)."""
    problem = _problem()
    _, b, batches, w0, mask = _bucket_setup(rng)

    def fit_one(bb, w, m, pr):
        return newton_re.fit_bucket_newton(problem, bb, w, m, pr)

    f = batches.features
    e, s, _ = f.idx.shape
    x = np.zeros((e, s, f.dim + 1))                  # ghost column last
    np.add.at(x, (np.arange(e)[:, None, None], np.arange(s)[None, :, None],
                  np.asarray(f.idx)), np.asarray(f.val, np.float64))
    x = x[..., :f.dim]
    y, wt, off = (np.asarray(a, np.float64) for a in (
        batches.labels, batches.weights, batches.offsets))

    def bound(w):
        w = np.asarray(w, np.float64)
        z = off + np.einsum("esp,ep->es", x, w)
        g = np.einsum("es,esp->ep", wt * (1 / (1 + np.exp(-z)) - y), x)
        return np.linalg.norm(g + problem.reg_weight * w, axis=1) \
            / problem.reg_weight

    whole = fit_one(batches, w0, mask, None)[0].coefficients.means
    chunked = newton_re.fit_bucket_in_chunks(
        fit_one, 4, batches, w0, mask, None)[0].coefficients.means
    assert bound(whole).max() < 1e-3 and bound(chunked).max() < 1e-3
    apart = np.linalg.norm(np.asarray(whole, np.float64)
                           - np.asarray(chunked, np.float64), axis=1)
    assert np.all(apart <= bound(whole) + bound(chunked))


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_chunked_matches_full_dual(rng, dtype):
    problem = _problem()
    _, b, batches, w0, mask = _bucket_setup(
        rng, dtype=dtype, max_rows=5, min_support=8)
    u_max = newton_re.u_max_for(
        newton_re.penalty_terms(problem, mask, None)[3])

    def fit_one(bb, w, m, pr):
        return newton_re.fit_bucket_newton_dual(problem, bb, w, m, pr, u_max)

    full_m, _ = fit_one(batches, w0, mask, None)
    ch_m, _ = newton_re.fit_bucket_in_chunks(fit_one, 4, batches, w0, mask,
                                             None)
    tol = 1e-9 if dtype == np.float64 else 5e-5
    np.testing.assert_allclose(np.asarray(ch_m.coefficients.means),
                               np.asarray(full_m.coefficients.means),
                               atol=tol)


@pytest.mark.parametrize("dtype, tolerance, atol", [
    (np.float32, F32_REBATCH_TOL, 2e-5),
    (np.float64, OptimizerConfig().tolerance, 1e-10)])
def test_chunk_padding_lanes_inert(rng, dtype, tolerance, atol):
    """A chunk larger than the bucket (one fully padded chunk) and a
    non-dividing chunk must both reproduce the full solve exactly for the
    REAL lanes — padded lanes may not scatter anything into the restack."""
    problem = _problem(tolerance=tolerance)
    _, b, batches, w0, mask = _bucket_setup(rng, dtype=dtype)

    def fit_one(bb, w, m, pr):
        return newton_re.fit_bucket_newton(problem, bb, w, m, pr)

    full_m, full_r = fit_one(batches, w0, mask, None)
    e = w0.shape[0]
    for chunk in (e + 7, max(2, e - 1)):
        ch_m, ch_r = newton_re.fit_bucket_in_chunks(
            fit_one, chunk, batches, w0, mask, None)
        assert ch_m.coefficients.means.shape == full_m.coefficients.means.shape
        np.testing.assert_allclose(np.asarray(ch_m.coefficients.means),
                                   np.asarray(full_m.coefficients.means),
                                   atol=atol)
        # per-lane diagnostics restack to the true entity count too
        assert ch_r.value.shape == full_r.value.shape


def _train(problem, ds, init=None):
    offsets = jnp.zeros((ds.n_rows,), jnp.float32)
    model, results = train_random_effects(problem, ds, offsets,
                                          init_coefs=init)
    return model, results


def test_static_chunked_tier_engages_under_budget(rng, monkeypatch):
    """A bucket the FULL-bucket budget gate refuses must route to chunked
    Newton (not surrender to vmapped), and match the unconstrained solve."""
    problem = _problem()
    idx, val, labels, keys = _make_entity_data(rng, n_entities=12)
    ds = build_random_effect_dataset("userId", keys, idx, val, labels,
                                     global_dim=50, dtype=np.float32)
    ref_model, _ = _train(problem, ds)
    ref_solvers = {t["solver"] for t in re_mod.LAST_BUCKET_TIMINGS}
    assert ref_solvers == {"newton_primal"}

    monkeypatch.setenv("PHOTON_RE_CHUNK_LADDER", "2,4")
    # Tight budget: full buckets refused, 4-entity chunks fit.
    monkeypatch.setenv("PHOTON_RE_NEWTON_BUDGET_MB", "0.02")
    ch_model, _ = _train(problem, ds)
    rec = re_mod.LAST_BUCKET_TIMINGS
    assert all(t["solver"].startswith("newton") for t in rec), rec
    assert any(t["chunk"] is not None for t in rec), rec
    for a, b in zip(ch_model.bucket_coefs, ref_model.bucket_coefs):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_compile_seconds_split(rng, monkeypatch):
    """First solve of a fresh shape reports compile_seconds > 0; an
    identical re-solve reports 0 (executable cache hit) — the split the
    bench stamps into artifacts."""
    problem = _problem(max_iter=59)  # unique static config -> fresh compile
    idx, val, labels, keys = _make_entity_data(rng, n_entities=7,
                                               global_dim=53)
    ds = build_random_effect_dataset("userId", keys, idx, val, labels,
                                     global_dim=53, dtype=np.float32)
    _train(problem, ds)
    first = [t["compile_seconds"] for t in re_mod.LAST_BUCKET_TIMINGS]
    assert any(c > 0 for c in first), first
    _train(problem, ds)
    second = [t["compile_seconds"] for t in re_mod.LAST_BUCKET_TIMINGS]
    assert all(c == 0 for c in second), second


@pytest.fixture
def measured(monkeypatch, tmp_path):
    table_path = str(tmp_path / "solver_costs.json")
    monkeypatch.setenv("PHOTON_RE_ROUTING", "measured")
    monkeypatch.setenv("PHOTON_RE_COST_TABLE", table_path)
    monkeypatch.setenv("PHOTON_RE_CHUNK_LADDER", "4,8")
    solver_routing.reset_process_table()
    yield table_path
    solver_routing.reset_process_table()


@pytest.mark.slow
def test_measured_routing_calibrates_once_then_persists(rng, measured,
                                                        monkeypatch):
    problem = _problem()
    idx, val, labels, keys = _make_entity_data(rng, n_entities=10)
    ds = build_random_effect_dataset("userId", keys, idx, val, labels,
                                     global_dim=50, dtype=np.float32)
    model, _ = _train(problem, ds)
    rec = re_mod.LAST_BUCKET_TIMINGS
    assert all(t["routing"] == "measured" for t in rec)
    assert any(t["calibrated"] for t in rec), rec
    assert all(t["calibration_seconds"] >= 0 for t in rec)
    # same optimum regardless of which candidate won the race
    with monkeypatch.context() as m:
        m.setenv("PHOTON_RE_ROUTING", "static")
        ref_model, _ = _train(problem, ds)
    for a, b in zip(model.bucket_coefs, ref_model.bucket_coefs):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)

    # Second sweep of the same shapes: the table routes, nobody races.
    _train(problem, ds)
    assert not any(t["calibrated"] for t in re_mod.LAST_BUCKET_TIMINGS)

    # The race persisted; a fresh process (table reset + reload from the
    # env path) skips calibration entirely — the warm-restart contract.
    assert os.path.exists(measured)
    payload = json.load(open(measured))
    assert payload["version"] == 1 and payload["entries"]
    solver_routing.reset_process_table()
    _train(problem, ds)
    assert not any(t["calibrated"] for t in re_mod.LAST_BUCKET_TIMINGS)


def test_measured_routing_falls_back_without_newton(rng, measured):
    """When calibration refuses every Newton variant (L1 objective here),
    routing must hand the whole bucket to vmapped L-BFGS unchunked."""
    problem = _problem(reg=L1, optimizer=OptimizerType.OWLQN)
    idx, val, labels, keys = _make_entity_data(rng, n_entities=8)
    ds = build_random_effect_dataset("userId", keys, idx, val, labels,
                                     global_dim=50, dtype=np.float32)
    _train(problem, ds)
    rec = re_mod.LAST_BUCKET_TIMINGS
    assert {t["solver"] for t in rec} == {"vmapped_lbfgs"}, rec
    assert all(t["chunk"] is None for t in rec)
    assert not any(t["calibrated"] for t in rec)


def test_measured_routing_respects_seeded_winner(rng, measured):
    """A pre-seeded cost table IS the routing decision: absurdly expensive
    Newton entries force the vmapped baseline with no race run."""
    problem = _problem()
    idx, val, labels, keys = _make_entity_data(rng, n_entities=10)
    ds = build_random_effect_dataset("userId", keys, idx, val, labels,
                                     global_dim=50, dtype=np.float32)
    table = solver_routing.process_table()
    for b in ds.buckets:
        mask = jnp.ones((b.n_entities, b.local_dim), b.val.dtype)
        u_max = newton_re.u_max_for(
            newton_re.penalty_terms(problem, mask, None)[3])
        cands = solver_routing.candidates_for(problem, b, None, u_max)
        assert any(c.solver.startswith("newton") for c in cands)
        key = solver_routing.shape_class(b)
        for c in cands:
            cost = 1e-9 if c.solver == "vmapped_lbfgs" else 1e9
            table.record(key, c, cost)
    _train(problem, ds)
    rec = re_mod.LAST_BUCKET_TIMINGS
    assert {t["solver"] for t in rec} == {"vmapped_lbfgs"}, rec
    assert all(t["chunk"] is not None for t in rec)  # chunked baseline
    assert not any(t["calibrated"] for t in rec)


def test_cost_table_roundtrip(tmp_path):
    t = solver_routing.SolverCostTable()
    c1 = solver_routing.Candidate("newton_dual", 4096)
    c2 = solver_routing.Candidate("vmapped_lbfgs", 4096)
    t.record("s16k6p32:float32", c1, 1.5e-5)
    t.record("s16k6p32:float32", c2, 9.0e-5)
    assert t.winner("s16k6p32:float32", [c1, c2]) == c1
    assert t.winner("s16k6p32:float32", [c2]) == c2       # feasibility-aware
    assert t.winner("other", [c1, c2]) is None
    # A feasible candidate with NO recorded cost forces a (partial) race:
    # a table persisted under a smaller budget must not permanently pin
    # routing to the only solver it happened to measure.
    c3 = solver_routing.Candidate("newton_primal", 4096)
    assert t.winner("s16k6p32:float32", [c1, c2, c3]) is None
    path = str(tmp_path / "costs.json")
    t.save(path)
    t2 = solver_routing.SolverCostTable()
    t2.load(path)
    assert t2.costs("s16k6p32:float32") == t.costs("s16k6p32:float32")
    with pytest.raises(ValueError):
        t2.load_json({"version": 99})


def test_chunk_ladder_env(monkeypatch):
    monkeypatch.setenv("PHOTON_RE_CHUNK_LADDER", "64, 8,512")
    assert newton_re.chunk_ladder() == (8, 64, 512)
    monkeypatch.setenv("PHOTON_RE_CHUNK_LADDER", "0,8")
    with pytest.raises(ValueError):
        newton_re.chunk_ladder()
    monkeypatch.delenv("PHOTON_RE_CHUNK_LADDER")
    assert newton_re.chunk_ladder() == newton_re._DEFAULT_CHUNK_LADDER


def test_routing_mode_validation(monkeypatch):
    monkeypatch.setenv("PHOTON_RE_ROUTING", "sometimes")
    with pytest.raises(ValueError):
        solver_routing.routing_mode()
    monkeypatch.setenv("PHOTON_RE_ROUTING", "measured")
    assert solver_routing.routing_mode() == "measured"
    monkeypatch.delenv("PHOTON_RE_ROUTING")
    assert solver_routing.routing_mode() == "static"


@pytest.mark.slow
def test_retrace_quiet_across_sweeps_with_chunking(rng, monkeypatch):
    """Acceptance check: across a 3-sweep descent with chunked Newton
    solves, the retrace sentinel must count ZERO retraces-after-warmup for
    the bucket kernels — the chunk ladder is closed, so sweep 1 compiles
    everything sweeps 2-3 need."""
    from photon_tpu.estimators.config import (
        FixedEffectDataConfig,
        GLMOptimizationConfiguration,
        RandomEffectDataConfig,
    )
    from photon_tpu.estimators.game_estimator import GameEstimator
    from tests.test_checkpoint import _bundle

    def estimator(n_sweeps):
        return GameEstimator(
            task=TaskType.LOGISTIC_REGRESSION,
            coordinate_data_configs={
                "fixed": FixedEffectDataConfig("g"),
                "perUser": RandomEffectDataConfig(re_type="userId",
                                                  feature_shard="g"),
            },
            n_sweeps=n_sweeps,
        )

    cfg = {
        "fixed": GLMOptimizationConfiguration(
            regularization=L2, reg_weight=1.0, max_iterations=8),
        "perUser": GLMOptimizationConfiguration(
            regularization=L2, reg_weight=1.0, max_iterations=8),
    }
    bundle = _bundle(n_users=24, rows_per_user=8)
    # Scout pass: learn the bucket shapes so the budget below is computed,
    # not guessed — full buckets must be refused while 8-entity chunks fit.
    estimator(1).fit(bundle, None, [cfg])
    shapes = [(t["row_slots"] // t["entities"], t["local_dim"], t["entities"])
              for t in re_mod.LAST_BUCKET_TIMINGS]
    assert any(e > 8 for _, _, e in shapes), shapes
    budget_b = 1.5 * max(
        newton_re._primal_need_bytes(8, s, p, 4.0) for s, p, _ in shapes)
    monkeypatch.setenv("PHOTON_RE_CHUNK_LADDER", "4,8")
    monkeypatch.setenv("PHOTON_RE_NEWTON_BUDGET_MB", str(budget_b / 1e6))

    retrace.reset()
    estimator(3).fit(bundle, None, [cfg])
    assert any(t["chunk"] is not None for t in re_mod.LAST_BUCKET_TIMINGS)
    compiled = sum(retrace.traces(k) for k in retrace.RE_SOLVER_KERNELS)
    assert compiled > 0  # the solves really went through watched kernels
    for k in retrace.RE_SOLVER_KERNELS:
        assert retrace.retraces_after_warmup(k) == 0, k
