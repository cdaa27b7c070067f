"""What a TRON solve says of itself: the three counters of its loop state
(Hessian-vector products, CG steps, refused trial steps) against a Python
loop over the same objective, ``data_passes`` against its formula and
against the feature operations' own count, the counters on the
``descent.step`` span and in the tracker's record (and on no L-BFGS step),
and the kept fast-path tables serving a TRON coordinate."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.data import batch as batch_mod
from photon_tpu.data.batch import LabeledBatch, SparseFeatures, ell_from_rows
from photon_tpu.estimators import (
    FixedEffectDataConfig,
    GLMOptimizationConfiguration,
    GameEstimator,
)
from photon_tpu.functions.objective import GLMObjective
from photon_tpu.io.data_reader import GameDataBundle
from photon_tpu.obs.trace import recent_trees
from photon_tpu.ops import fast_sparse, pass_counter
from photon_tpu.ops.losses import LogisticLoss
from photon_tpu.optim import (
    TRON,
    OptimizerConfig,
    OptimizerType,
    RegularizationContext,
    RegularizationType,
)
from photon_tpu.types import TaskType

NAME, ARGS = 0, 5
COUNTERS = ("hvp", "cg_steps", "rejected")
MAX_CG = 6


def _batch(seed, n=384, d=48, k=5, scale=1.0):
    r = np.random.default_rng(seed)
    idx = r.integers(0, d, size=(n, k)).astype(np.int32)
    val = scale * r.normal(size=(n, k)) / np.sqrt(k)
    w = r.normal(size=d)
    z = (val * w[idx]).sum(1)
    y = (r.random(n) < 1.0 / (1.0 + np.exp(-z))).astype(np.float64)
    return LabeledBatch(
        SparseFeatures(jnp.asarray(idx), jnp.asarray(val), d),
        jnp.asarray(y), jnp.zeros(n), jnp.ones(n))


def _recount(obj, batch, w0, iterations):
    """TRON as a Python loop over the program's own objective: the CG steps
    of every trust-region iteration and whether its trial step was refused."""
    vg, hvp_at = obj.bind(batch), obj.bind_hvp_at(batch)
    x = w0
    f, g = vg(x)
    delta = jnp.linalg.norm(g)
    cg, refused = [], []
    for _ in range(iterations):
        hv, tol = hvp_at(x), 0.1 * jnp.linalg.norm(g)
        p, hp, r = jnp.zeros_like(g), jnp.zeros_like(g), -g
        d, rr, steps, out = r, r @ r, 0, False
        while not out and steps < MAX_CG and jnp.sqrt(rr) > tol:
            hd = hv(d)
            dhd = d @ hd
            alpha = rr / dhd
            out = bool(jnp.linalg.norm(p + alpha * d) >= delta)
            if out:
                dd, pd, pp = d @ d, p @ d, p @ p
                alpha = (-pd + jnp.sqrt(pd * pd + dd * (delta ** 2 - pp))) / dd
            p, hp, r = p + alpha * d, hp + alpha * hd, r - alpha * hd
            d, rr = r + (r @ r) / rr * d, r @ r
            steps += 1
        pred = -(g @ p + 0.5 * (p @ hp))
        f_try, g_try = vg(x + p)
        rho = (f - f_try) / pred
        pn = jnp.linalg.norm(p)
        if rho < 0.25:
            new_delta = 0.25 * jnp.minimum(pn, delta)
        elif rho < 0.75:
            new_delta = 0.5 * delta
        else:
            new_delta = jnp.clip(4.0 * pn, delta, 4.0 * delta)
        if rho > 1e-4:
            x, f, g = x + p, f_try, g_try
        cg.append(steps)
        refused.append(not rho > 1e-4)
        delta = new_delta
    return cg, refused


# A well-conditioned start from zero (every step taken), and two starts far
# from the optimum on weakly regularized data (refused steps, short CG
# solves that end at the boundary).
# ``iterations`` stops the first short of the optimum, where rounding
# decides every test and a Python loop need not agree with a compiled one.
CASES = {"from_zero": dict(seed=1, l2=1.0, scale=1.0, w0=0.0, iterations=4),
         "far_start": dict(seed=2, l2=1e-3, scale=8.0, w0=2.0, iterations=8),
         "far_start_2": dict(seed=5, l2=1e-2, scale=6.0, w0=3.0,
                             iterations=8)}


def _solve(c: dict) -> dict:
    batch = _batch(c["seed"], scale=c["scale"])
    obj = GLMObjective(loss=LogisticLoss, l2_weight=c["l2"])
    w0 = jnp.asarray(np.random.default_rng(c["seed"]).normal(size=batch.dim)
                     * c["w0"])
    cfg = OptimizerConfig(max_iterations=c["iterations"], tolerance=0.0,
                          max_cg_iterations=MAX_CG)

    def solve(batch, w0):
        return TRON(cfg).optimize(obj.bind(batch), w0, obj.bind_hvp_at(batch))

    with pass_counter.counting() as counts:
        res = jax.jit(solve)(batch, w0)
        jax.block_until_ready(res.value)
    return {"iterations": c["iterations"], "res": res,
            "touched": dict(counts),
            "recount": _recount(obj, batch, w0, c["iterations"])}


@pytest.fixture(scope="module")
def cases():
    return {name: _solve(c) for name, c in CASES.items()}


@pytest.fixture(params=sorted(CASES))
def solved(request, cases):
    return cases[request.param]


def test_the_counters_equal_a_python_loop_recount(solved):
    res, (cg, refused) = solved["res"], solved["recount"]
    assert int(res.iterations) == solved["iterations"]
    assert int(res.cg_steps) == sum(cg)
    assert int(res.hvp) == sum(cg)           # one product a CG step
    assert int(res.rejected) == sum(refused)


def test_data_passes_follow_the_counters_and_the_operations(solved):
    res, (cg, _) = solved["res"], solved["recount"]
    # 2 for the first value and gradient; an iteration: 1 (the margins the
    # products hoist) + 2 a product + 2 (the trial's value and gradient).
    assert int(res.data_passes) == 2 + sum(1 + 2 * steps + 2 for steps in cg)
    touched = solved["touched"]
    assert int(res.data_passes) == touched["matvec"] + touched["rmatvec"]
    assert touched["rmatvec"] == 1 + int(res.iterations) + int(res.hvp)


def test_the_cases_refuse_steps_and_not(cases):
    """What the cases above are for, so that a counter stuck at zero would
    be seen."""
    refused = {name: int(c["res"].rejected) for name, c in cases.items()}
    assert refused["from_zero"] == 0
    assert refused["far_start"] > 0 or refused["far_start_2"] > 0


# ------------------------------------------- through the estimator's fit


def _bundle(seed, n=96, d=6):
    r = np.random.default_rng(seed)
    rows = [(np.arange(d), r.normal(size=d)) for _ in range(n)]
    return GameDataBundle(
        features={"global": ell_from_rows(rows, d)},
        labels=(r.random(n) < 0.5).astype(np.float64),
        offsets=np.zeros(n), weights=np.ones(n),
        uids=np.asarray([str(i) for i in range(n)], object), id_tags={})


def _fit(optimizer: str, estimator=None, bundle=None):
    """(result, the fit's kept span tree, the estimator)."""
    estimator = estimator or GameEstimator(
        task=TaskType.LOGISTIC_REGRESSION,
        coordinate_data_configs={
            "fixed": FixedEffectDataConfig(feature_shard="global")})
    config = {"fixed": GLMOptimizationConfiguration(
        optimizer_type=OptimizerType[optimizer], max_iterations=4,
        regularization=RegularizationContext(RegularizationType.L2),
        reg_weight=1.0)}
    result = estimator.fit(bundle or _bundle(3), None, [config])[0]
    return result, recent_trees("estimator.fit", 1)[0], estimator


def _step_args(tree):
    return [s[ARGS] for s in tree if s[NAME] == "descent.step"]


def test_a_tron_step_carries_its_counters_on_span_and_tracker():
    result, tree, _ = _fit("TRON")
    (args,) = _step_args(tree)
    record = result.tracker[0].convergence
    for name in COUNTERS:
        assert isinstance(args[name], int) and args[name] == record[name]
    assert args["hvp"] == args["cg_steps"] >= record["iterations"] >= 1
    assert record["data_passes"] == (
        2 + 3 * record["iterations"] + 2 * record["hvp"])


def test_an_lbfgs_step_carries_none_of_them():
    result, tree, _ = _fit("LBFGS")
    (args,) = _step_args(tree)
    record = result.tracker[0].convergence
    assert not set(COUNTERS) & set(args)
    assert not set(COUNTERS) & set(record)
    assert {"iterations", "data_passes", "reasons"} <= set(record)


def test_kept_tables_serve_a_tron_coordinate(monkeypatch):
    """The second fit on a prepared bundle attaches the tables the first
    built (``GameEstimator._with_tables``), whatever the optimizer; the
    tables are a TPU default, so the CPU is named an accelerator here."""
    calls = []
    original = fast_sparse.build_fast_aux
    monkeypatch.setattr(fast_sparse, "build_fast_aux",
                        lambda *a, **k: calls.append(1) or original(*a, **k))
    monkeypatch.setattr(batch_mod, "REAL_ACCELERATOR_BACKENDS", ("cpu",))
    bundle = _bundle(4)
    first, tree1, estimator = _fit("TRON", bundle=bundle)
    second, tree2, _ = _fit("TRON", estimator, bundle)
    assert len(calls) == 1

    def tables(tree):
        (args,) = [s[ARGS] for s in tree
                   if s[NAME] == "estimator.build_coordinates"]
        return args["tables_reused"], args["tables_built"]

    assert tables(tree1) == (0, 1) and tables(tree2) == (1, 0)
    assert [s[NAME] for s in tree2].count("data.accel_tables") == 0
    np.testing.assert_array_equal(
        np.asarray(first.model.models["fixed"].model.coefficients.means),
        np.asarray(second.model.models["fixed"].model.coefficients.means))
    assert _step_args(tree2)[0]["hvp"] == _step_args(tree1)[0]["hvp"]


# ------------------------------------------------- the names on the device


@pytest.fixture(scope="module")
def tron_program_text():
    from photon_tpu.functions.problem import (
        GLMOptimizationProblem,
        _fit_jitted,
    )

    problem = GLMOptimizationProblem(
        task=TaskType.LOGISTIC_REGRESSION, optimizer_type=OptimizerType.TRON,
        optimizer_config=OptimizerConfig(max_iterations=3),
        regularization=RegularizationContext(RegularizationType.L2),
        reg_weight=1.0)
    batch = _batch(0)
    return _fit_jitted.lower(
        problem, batch, jnp.zeros(batch.dim), None, None, None,
        jnp.asarray(1.0)).as_text(debug_info=True)


@pytest.mark.parametrize("scope", [
    "tron.cg", "tron.hvp", "tron.trial", "tron.radius",
    # the margins are taken once a CG solve, outside its loop; a product
    # is the sparse pass inside it
    "while/body/tron.cg/sparse.matvec",
    "tron.cg/while/body/tron.hvp/sparse.matvec",
    "tron.cg/while/body/tron.hvp/sparse.rmatvec",
    "tron.trial/sparse.rmatvec"])
def test_tron_program_names_its_scopes(tron_program_text, scope):
    assert scope in tron_program_text
