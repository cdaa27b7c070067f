"""The per-entity Newton systems' factorization with the entity on the lane
axis (``game/newton_re.py`` ``_lane_cholesky_solve``): the same Cholesky the
library call makes, held to NumPy float64 and to the library form (kept
here as the reference), and the driver's contract around it (a lane that is
not positive definite comes back NaN alone and steps by steepest descent; no
width a gate admits traces the library call; whole fits return what they
returned).
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.data.random_effect import build_random_effect_dataset
from photon_tpu.functions.problem import GLMOptimizationProblem
from photon_tpu.game import newton_re
from photon_tpu.optim import (
    OptimizerConfig,
    RegularizationContext,
    RegularizationType,
)
from photon_tpu.types import TaskType
from tests.test_random_effect import _make_entity_data


def _library_cholesky_solve(h, b):
    """``h @ x = b`` a lane by the batched library calls on [E,T,T]: what
    ``_newton_loop`` called before the lane form."""
    chol = jnp.linalg.cholesky(jnp.asarray(h))
    return jax.scipy.linalg.cho_solve(
        (chol, True), jnp.asarray(b)[..., None])[..., 0]


def _systems(rng, e, t, real=None):
    """[E,T,T] float32 Hessians of ``real`` columns, identity on the padded
    ones, and a right-hand side that is zero there."""
    real = t if real is None else real
    x = rng.standard_normal((e, 3 * t, real)).astype(np.float32)
    h = np.zeros((e, t, t), np.float32)
    h[:, :real, :real] = 0.2 * np.swapaxes(x, 1, 2) @ x
    h = 0.5 * (h + np.swapaxes(h, 1, 2)) + np.eye(t, dtype=np.float32)
    b = np.zeros((e, t), np.float32)
    b[:, :real] = rng.standard_normal((e, real)).astype(np.float32)
    return h, b


def _gap(got, want):
    return float(np.max(np.abs(np.asarray(got, np.float64) - want))
                 / np.max(np.abs(want)))


@pytest.mark.parametrize("against", ["float64", "library"])
@pytest.mark.parametrize("e", [1, 3, 130, 1000])
@pytest.mark.parametrize("t", [17, 32])
def test_lane_solve_matches(rng, t, e, against):
    h, b = _systems(rng, e, t)
    got = jax.jit(newton_re._lane_cholesky_solve)(h, b)
    assert got.shape == (e, t) and got.dtype == jnp.float32
    if against == "float64":
        want = np.linalg.solve(h.astype(np.float64),
                               b.astype(np.float64)[..., None])[..., 0]
    else:
        want = np.asarray(_library_cholesky_solve(h, b), np.float64)
    assert _gap(got, want) < 1e-5


def test_lane_solve_keeps_the_dtype_it_is_given(rng):
    """A float64 dataset solves in float64 (the module's dtype contract)."""
    h, b = _systems(rng, 5, 17)
    h, b = h.astype(np.float64), b.astype(np.float64)
    got = newton_re._lane_cholesky_solve(jnp.asarray(h), jnp.asarray(b))
    assert got.dtype == jnp.float64
    assert _gap(got, np.linalg.solve(h, b[..., None])[..., 0]) < 1e-12


def test_lane_solve_takes_the_mean_of_the_two_triangles(rng):
    """A Hessian from a float32 GEMM is symmetric only to rounding:
    ``jnp.linalg.cholesky`` averages the triangles, and so does this."""
    h, b = _systems(rng, 6, 17)
    h[:, 3, 9] *= 1.01
    got = jax.jit(newton_re._lane_cholesky_solve)(h, b)
    sym = 0.5 * (h + np.swapaxes(h, 1, 2)).astype(np.float64)
    want = np.linalg.solve(sym, b.astype(np.float64)[..., None])[..., 0]
    assert _gap(got, want) < 1e-5
    assert _gap(_library_cholesky_solve(h, b), want) < 1e-5


def test_identity_padded_columns_solve_to_zero(rng):
    """21 real columns of 32, as the GAME cells pad a user's coefficients:
    the padded coordinates of the step are exactly zero."""
    h, b = _systems(rng, 130, 32, real=21)
    got = np.asarray(jax.jit(newton_re._lane_cholesky_solve)(h, b))
    assert np.all(got[:, 21:] == 0.0)
    want = np.linalg.solve(h.astype(np.float64),
                           b.astype(np.float64)[..., None])[..., 0]
    assert _gap(got, want) < 1e-5


def test_a_lane_that_is_not_positive_definite_is_nan_alone(rng):
    h, b = _systems(rng, 130, 32)
    h[7] = -h[7]
    h[129, 5, 5] = -3.0
    got = np.asarray(jax.jit(newton_re._lane_cholesky_solve)(h, b))
    bad = np.isnan(got).any(axis=1)
    assert np.flatnonzero(bad).tolist() == [7, 129]
    assert np.isnan(got[[7, 129]]).all()  # the whole lane, as the library's
    lib = np.asarray(_library_cholesky_solve(h, b))
    assert np.flatnonzero(np.isnan(lib).any(axis=1)).tolist() == [7, 129]


def _library_calls(traced):
    """The factorization and substitution primitives in a jaxpr's text."""
    return set(re.findall(r"= (lu|cholesky|triangular_solve)\b", traced))


def _quadratic_loop(a, b, hess, max_iterations=1):
    """``_newton_loop`` on ``f(x) = x'Ax/2 - b'x`` a lane, from zero, told
    that the Hessian is ``hess``."""
    a, b, hess = jnp.asarray(a), jnp.asarray(b), jnp.asarray(hess)
    e, t = b.shape

    def value_at(x, z):
        return 0.5 * jnp.einsum("ep,epq,eq->e", x, a, x) - jnp.sum(b * x, 1)

    def probe_values(x, z, d, zd, ts):
        xt = x[None] + ts[:, None, None] * d[None]
        return (0.5 * jnp.einsum("lep,epq,leq->le", xt, a, xt)
                - jnp.sum(b[None] * xt, 2))

    out = newton_re._newton_loop(
        jnp.zeros((e, t), b.dtype), jnp.zeros((e, 1), b.dtype),
        OptimizerConfig(max_iterations=max_iterations), value_at,
        lambda x, z: jnp.einsum("epq,eq->ep", a, x) - b,
        lambda x, z: hess, lambda d: jnp.zeros((e, 1), b.dtype),
        probe_values, ridge=1e-8)
    return out[0]


def test_newton_loop_steps_a_failed_lane_by_steepest_descent(rng):
    """The lane whose Hessian lost positive definiteness takes a step along
    its negative gradient; its neighbours take the full Newton step."""
    a, b = _systems(rng, 9, 17)
    hess = a.copy()
    hess[4] = -hess[4]
    x = np.asarray(_quadratic_loop(a, b, hess))
    want = np.linalg.solve(a.astype(np.float64),
                           b.astype(np.float64)[..., None])[..., 0]
    good = np.arange(9) != 4
    assert _gap(x[good], want[good]) < 1e-5
    # From zero the gradient is -b: the failed lane moved to t * b, t one
    # of the line search's halvings, and lowered its objective.
    t = x[4] / b[4]
    assert np.isfinite(t).all() and np.ptp(t) < 1e-6 * t[0]
    assert np.isclose(np.log2(t[0]), np.round(np.log2(t[0])), atol=1e-5)
    assert 0.0 < t[0] <= 1.0


@pytest.mark.parametrize("t", [
    17, newton_re.DUAL_MAX_T, newton_re.NEWTON_CHUNK_MAX_P])
def test_no_width_traces_a_library_factorization(rng, t):
    """Up to the widest system a gate admits (``DUAL_MAX_T`` on the dual
    path, ``NEWTON_CHUNK_MAX_P`` on a chunked primal bucket) the loop
    traces no factorization primitive: not Cholesky, not LU."""
    assert newton_re.solve_form() == "lanes"
    a, b = _systems(rng, 4, t)
    traced = str(jax.make_jaxpr(lambda: _quadratic_loop(a, b, a))())
    assert _library_calls(traced) == set()


def test_debug_nans_takes_lu_and_says_so(rng):
    """Under ``jax_debug_nans`` a failed lane's NaN would raise, so the
    loop takes LU, and ``solve_form`` (the span's ``solve``) reports it."""
    a, b = _systems(rng, 4, 17)
    with jax.debug_nans(True):
        assert newton_re.solve_form() == "lu"
        traced = str(jax.make_jaxpr(lambda: _quadratic_loop(a, b, a))())
    assert _library_calls(traced) == {"lu", "triangular_solve"}


L2 = RegularizationContext(RegularizationType.L2)


@pytest.mark.parametrize("dtype, atol", [(np.float64, 1e-9),
                                         (np.float32, 2e-4)])
@pytest.mark.parametrize("solver", ["newton_primal", "newton_dual"])
def test_whole_bucket_fits_return_what_the_library_form_returned(
        rng, monkeypatch, solver, dtype, atol):
    """``fit_bucket_newton`` and ``fit_bucket_newton_dual`` at a tiny size:
    the coefficients under the lane form against those under the library
    form (patched in for the second fit). In float64 they are the same; in
    float32 two factorizations' directions differ in the last bits, a
    converged lane's last line search is rounding's (4.5e-5 and 3.4e-5
    apart here), and they are held as two float32 solvers are elsewhere
    (``test_random_effect.py``: 2e-4)."""
    dual = solver == "newton_dual"
    kw = dict(max_rows=5, min_support=8) if dual else {}
    idx, val, labels, keys = _make_entity_data(rng, n_entities=24, **kw)
    ds = build_random_effect_dataset(
        "userId", keys, idx, val, labels, global_dim=50, dtype=dtype)
    problem = GLMOptimizationProblem(
        task=TaskType.LOGISTIC_REGRESSION,
        optimizer_config=OptimizerConfig(max_iterations=30),
        regularization=L2, reg_weight=0.5)
    raw = (newton_re.fit_bucket_newton_dual if dual
           else newton_re.fit_bucket_newton).__wrapped__

    def fit_all():
        # A new function a form, or jit answers from the first one's trace:
        # the loop looks the solve up while tracing.
        fit = jax.jit(lambda *a: raw(*a),
                      static_argnums=(0, 5) if dual else 0)
        coefs = []
        for bucket in ds.buckets:
            e, p = bucket.n_entities, bucket.local_dim
            args = (problem,
                    bucket.local_batches(jnp.zeros(ds.n_rows, dtype)),
                    jnp.zeros((e, p), dtype), jnp.ones((e, p), dtype), None)
            model, _ = fit(*args, 0) if dual else fit(*args)
            coefs.append(np.asarray(model.coefficients.means))
        return coefs

    lanes = fit_all()
    traced = []

    def library_solve(h, b):
        traced.append(h.shape)
        return _library_cholesky_solve(h, b)

    monkeypatch.setattr(newton_re, "_lane_cholesky_solve", library_solve)
    library = fit_all()
    assert len(traced) == len(ds.buckets)
    for got, want in zip(lanes, library):
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, want, atol=atol)


@pytest.mark.parametrize("newton, solve", [
    ("0", None), ("1", "lanes"), ("dual", "lanes")])
def test_the_bucket_span_carries_the_form(rng, monkeypatch, newton, solve):
    """``optim.re_bucket``'s ``solve``: how a Newton bucket's systems were
    solved, and ``None`` where no Newton system is."""
    from photon_tpu.game import train_random_effects
    from photon_tpu.obs.trace import tracing

    monkeypatch.setenv("PHOTON_RE_NEWTON", newton)
    kw = dict(max_rows=5, min_support=8) if newton == "dual" else {}
    idx, val, labels, keys = _make_entity_data(rng, **kw)
    ds = build_random_effect_dataset(
        "userId", keys, idx, val, labels, global_dim=50, dtype=np.float64)
    problem = GLMOptimizationProblem(
        task=TaskType.LOGISTIC_REGRESSION,
        optimizer_config=OptimizerConfig(max_iterations=5),
        regularization=L2, reg_weight=0.5)
    with tracing() as col:
        train_random_effects(problem, ds, jnp.zeros(ds.n_rows))
    spans = [e["args"] for e in col.events if e["name"] == "optim.re_bucket"]
    assert len(spans) == len(ds.buckets)
    want = {"0": "vmapped_lbfgs", "1": "newton_primal",
            "dual": "newton_dual"}[newton]
    assert [(a["solver"], a["solve"]) for a in spans] == [
        (want, solve)] * len(spans)
