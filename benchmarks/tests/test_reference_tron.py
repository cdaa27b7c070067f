"""The plain TRON (``references/optimizer_tron.py``) against the program's
(``optim/tron.py``) on seeded small problems: in float64 the two take the
same path to the last digit of what is compared (objective, gradient norm,
CG steps of every trust-region iteration), in float32 as far as the
reference calls its own path ``sure``; the cases between them refuse a
step, end a CG solve at the boundary and at its cap; and ``sure`` falls
where a near-tie is planted."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import datagen, reference
from benchmarks.references import optimizer_tron
from benchmarks.tests import faults_tron

ITERATIONS = 8


@pytest.fixture(autouse=True, scope="module")
def float64():
    """The float64 comparisons need JAX's x64 mode; the float32 ones name
    their dtype."""
    was = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", was)

# A start from zero on the generator's own data (every step taken, the
# trust region never met), and starts away from zero on weakly regularized,
# scaled-up data (refused steps, CG solves that end at the boundary); the
# last with CG capped at 3 steps.
# ``float32`` is how closely a float32 solve's objectives follow: a start
# far from the optimum is ill-conditioned (saturated rows have no
# curvature), and float32 CG then ends on a residual a fifth off float64's
# with every decision the same.
CASES = {
    "from_zero": dict(seed=3, l2=1.0, scale=1.0, w0=0.0, max_cg=20,
                      float32=2e-6),
    "far_start": dict(seed=3, l2=1.0, scale=1.0, w0=3.0, max_cg=20,
                      float32=1e-2),
    "weak_l2_far_start": dict(seed=4, l2=1e-3, scale=10.0, w0=2.0, max_cg=20,
                              float32=1e-2),
    "capped_cg": dict(seed=6, l2=1e-2, scale=6.0, w0=0.0, max_cg=3,
                      float32=2e-6),
}
# The cases' margins of ``sure``: the CPU's float32 objective at this size
# is good to 1e-6 of itself, and a CG solve's residual to 3% (far starts).
MARGINS = dict(f_noise=1e-6, cg_noise=3e-2)


def _data(case: dict):
    ds = datagen.generate(dict(rows=2048, named_features=255, named_nnz=7,
                               head_features=32, validation=dict(rows=8)),
                          case["seed"])
    tr = ds.train
    val = tr.gv.copy()
    val[:, :-1] *= case["scale"]
    w0 = (np.random.default_rng(case["seed"]).normal(size=ds.global_dim)
          * case["w0"])
    return ds, tr.gi, val, tr.y, w0


def _reference(case: dict, **margins) -> dict:
    margins = margins or MARGINS
    ds, idx, val, y, w0 = _data(case)
    p = reference.SparseLogistic(
        idx=idx, val=val, y=y, offsets=np.zeros(len(y)), dim=ds.global_dim,
        l2=case["l2"], intercept=ds.global_dim - 1)
    return optimizer_tron.tron(p, ITERATIONS, w0, max_cg=case["max_cg"],
                               **margins)


def _program(case: dict, dtype, iterations=ITERATIONS):
    """The program's solve of the case, cut to ``iterations``."""
    from photon_tpu.data.batch import LabeledBatch, SparseFeatures
    from photon_tpu.functions.objective import GLMObjective, intercept_reg_mask
    from photon_tpu.ops.losses import LogisticLoss
    from photon_tpu.optim import TRON, OptimizerConfig

    ds, idx, val, y, w0 = _data(case)
    n = len(y)
    batch = LabeledBatch(
        SparseFeatures(jnp.asarray(idx, jnp.int32), jnp.asarray(val, dtype),
                       ds.global_dim),
        jnp.asarray(y, dtype), jnp.zeros(n, dtype), jnp.ones(n, dtype))
    obj = GLMObjective(
        loss=LogisticLoss, l2_weight=case["l2"],
        reg_mask=intercept_reg_mask(ds.global_dim, ds.global_dim - 1))
    cfg = OptimizerConfig(max_iterations=iterations, tolerance=0.0,
                          max_cg_iterations=case["max_cg"])
    return jax.jit(lambda b, w: TRON(cfg).optimize(
        obj.bind(b), w, obj.bind_hvp_at(b)))(batch, jnp.asarray(w0, dtype))


def _gaps(res, ref, upto):
    got = np.asarray(res.values, np.float64)[:upto + 1]
    got_g = np.asarray(res.grad_norms, np.float64)[:upto + 1]
    want = np.asarray(ref["values"])[:upto + 1]
    want_g = np.asarray(ref["grad_norms"])[:upto + 1]
    return (np.abs(got - want) / np.abs(want)).max(), \
        (np.abs(got_g - want_g) / want_g).max()


@pytest.mark.parametrize("name", sorted(CASES))
def test_float64_program_and_reference_take_one_path(name):
    case = CASES[name]
    ref, res = _reference(case), _program(case, jnp.float64)
    assert int(res.iterations) == ITERATIONS
    loss_gap, grad_gap = _gaps(res, ref, ITERATIONS)
    assert loss_gap < 1e-9 and grad_gap < 1e-6, (loss_gap, grad_gap)
    assert int(res.cg_steps) == sum(ref["cg_steps"])
    assert int(res.rejected) == sum(ref["rejected"])
    # iteration by iteration: the program cut to k iterations has made the
    # reference's first k CG solves
    for k in (1, 2, 3):
        assert int(_program(case, jnp.float64, k).cg_steps) == sum(
            ref["cg_steps"][:k])
    np.testing.assert_allclose(np.asarray(res.x), ref["w"], rtol=0, atol=1e-9)


@pytest.mark.parametrize("name", sorted(CASES))
def test_float32_program_follows_the_reference_while_it_is_sure(name):
    case = CASES[name]
    ref = _reference(case)
    sure = ref["sure"]
    assert sure >= 2, ref
    res = _program(case, jnp.float32, sure)
    loss_gap, _ = _gaps(res, ref, sure)
    assert loss_gap < case["float32"], loss_gap
    assert int(res.cg_steps) == sum(ref["cg_steps"][:sure])
    assert int(res.rejected) == sum(ref["rejected"][:sure])


def test_the_cases_refuse_a_step_meet_the_boundary_and_the_cap():
    seen = {name: _reference(case) for name, case in CASES.items()}
    # (a start from zero refuses nothing until it has converged and
    # rounding decides, here after its fifth iteration)
    assert not any(seen["from_zero"]["rejected"][:5])
    assert set(seen["from_zero"]["ended"][:5]) == {"residual"}
    assert any(seen["weak_l2_far_start"]["rejected"])
    assert "boundary" in seen["weak_l2_far_start"]["ended"]
    assert "boundary" in seen["far_start"]["ended"]
    assert "cap" in seen["capped_cg"]["ended"]
    assert max(seen["capped_cg"]["cg_steps"]) == 3
    for out in seen.values():
        v = out["values"]
        assert len(v) == ITERATIONS + 1 == len(out["grad_norms"])
        assert all(b <= a for a, b in zip(v, v[1:]))
        # a refused step repeats the objective
        assert all((v[k + 1] == v[k]) == out["rejected"][k]
                   for k in range(ITERATIONS))


def test_sure_falls_where_a_near_tie_sits():
    """The margins decide ``sure`` and nothing else. With none, every
    iteration is sure. A CG margin of one half plants a near-tie in the
    first iteration (its solve ends on a residual between half and once
    its tolerance), and ``sure`` is 0. With the objective's margin alone,
    ``sure`` falls at the first iteration whose ``rho`` lies within
    ``2 f_noise |f| / pred`` of one of its three thresholds, which is in
    the middle of the path: there the predicted decrease has shrunk to a
    few millionths of the objective."""
    case = CASES["from_zero"]
    loose = _reference(case, f_noise=0.0, cg_noise=0.0)
    assert loose["sure"] == ITERATIONS
    tight_cg = _reference(case, f_noise=0.0, cg_noise=0.5)
    assert tight_cg["sure"] == 0
    # the objective's margin alone: sure up to the first iteration whose
    # predicted decrease is within 2 * f_noise of the objective
    f_noise = 1e-6
    by_f = _reference(case, f_noise=f_noise, cg_noise=0.0)
    slack = [2 * f_noise * abs(v) / p
             for v, p in zip(by_f["values"], by_f["pred"])]
    first = next(k for k, (s, r) in enumerate(zip(slack, by_f["rho"]))
                 if min(abs(r - t) for t in (1e-4, 0.25, 0.75)) <= s)
    assert by_f["sure"] == first and 0 < first < ITERATIONS
    # and the same paths whatever the margins: they decide nothing but sure
    assert by_f["values"] == loose["values"] == tight_cg["values"]


def test_a_task_without_a_stated_curvature_is_refused():
    class Other:
        dim = 3

    with pytest.raises(ValueError, match="curvature"):
        optimizer_tron.tron(Other(), 1)


def test_the_reference_is_found_by_the_name_a_configuration_gives():
    assert reference.optimizer("TRON") is optimizer_tron.STATED
    import ast
    import inspect

    tree = ast.parse(inspect.getsource(optimizer_tron))
    imported = {a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
                for a in n.names} | {
        n.module for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)}
    assert not any(m and m.startswith(("photon_tpu", "jax")) for m in imported)


@pytest.mark.parametrize("fault", sorted(faults_tron.FAULTS))
def test_a_planted_tron_fault_leaves_the_reference_path(fault):
    """Each of TRON's own faults on the case that refuses steps and meets
    the boundary: the program's objectives part from the reference's, and
    the fault leaves no traced program of its own behind."""
    case = CASES["weak_l2_far_start"]
    ref = _reference(case)
    sound, _ = _gaps(_coordinate_step(case), ref, ITERATIONS)
    with faults_tron.FAULTS[fault]():
        parted, _ = _gaps(_coordinate_step(case), ref, ITERATIONS)
    assert sound < 1e-9 < 1e-4 < parted, (sound, parted)
    again, _ = _gaps(_coordinate_step(case), ref, ITERATIONS)
    assert again == sound


def _coordinate_step(case: dict):
    """The solve as a coordinate step runs it (``FixedEffectCoordinate.train``,
    the jitted program the faults are planted under), float64."""
    from photon_tpu.data.batch import LabeledBatch, SparseFeatures
    from photon_tpu.functions.objective import intercept_reg_mask
    from photon_tpu.functions.problem import GLMOptimizationProblem
    from photon_tpu.game.coordinates import FixedEffectCoordinate, FixedEffectModel
    from photon_tpu.models.coefficients import Coefficients
    from photon_tpu.models.glm import GeneralizedLinearModel
    from photon_tpu.optim import (
        OptimizerConfig,
        OptimizerType,
        RegularizationContext,
        RegularizationType,
    )
    from photon_tpu.types import TaskType

    ds, idx, val, y, w0 = _data(case)
    n = len(y)
    batch = LabeledBatch(
        SparseFeatures(jnp.asarray(idx, jnp.int32), jnp.asarray(val),
                       ds.global_dim),
        jnp.asarray(y), jnp.zeros(n), jnp.ones(n))
    task = TaskType.LOGISTIC_REGRESSION
    problem = GLMOptimizationProblem(
        task=task, optimizer_type=OptimizerType.TRON,
        optimizer_config=OptimizerConfig(
            max_iterations=ITERATIONS, tolerance=0.0,
            max_cg_iterations=case["max_cg"]),
        regularization=RegularizationContext(RegularizationType.L2),
        reg_weight=case["l2"],
        reg_mask=intercept_reg_mask(ds.global_dim, ds.global_dim - 1))
    start = FixedEffectModel(GeneralizedLinearModel(
        Coefficients(means=jnp.asarray(w0)), task), "global")
    coordinate = FixedEffectCoordinate(batch=batch, problem=problem,
                                       feature_shard="global")
    return coordinate.train(jnp.zeros(n), start)[1]
