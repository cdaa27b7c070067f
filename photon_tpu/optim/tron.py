"""TRON — trust-region Newton with truncated conjugate-gradient inner solves.

Parity: reference ⟦photon-lib/.../optimization/TRON.scala⟧, itself a port of
LIBLINEAR's TRON (Lin, Weng & Keerthi 2008): an outer trust-region loop whose
step comes from a Steihaug truncated-CG solve of ``H p = −g`` using only
Hessian-vector products, with the classic η/σ radius-update constants. No line
search.

TPU-first design: the Hessian-vector product is *not* hand-coded per loss as in
the reference's ⟦HessianVectorAggregator⟧ — it is forward-over-reverse autodiff
(``jax.jvp`` of the gradient), which XLA fuses into the same data pass. Outer
loop, inner CG, and the radius logic all live in nested ``lax.while_loop``s, so
a full TRON solve is one XLA program (vs. one Spark job per CG step in the
reference, SURVEY.md §3.4).

What a solve says of itself (``docs/observability.md``): the device work
lies in the named scopes ``tron.cg`` (the CG solve, with the margins and
curvature hoisted to its head), ``tron.hvp`` (one Hessian-vector product,
inside it), ``tron.trial`` (the objective and gradient at the trial point)
and ``tron.radius`` (the radius update and the accept test); and the loop
state counts, where each happens, the Hessian-vector products, the CG steps
and the trial steps refused, which ``OptimizerResult`` carries beside
``data_passes``.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from photon_tpu.optim.base import (
    FUNCTION_VALUES_CONVERGED,
    NOT_CONVERGED,
    Optimizer,
    OptimizerResult,
    ValueAndGrad,
    check_convergence,
    finalize_reason,
)
from photon_tpu.optim.lbfgs import make_dot

Array = jax.Array

# LIBLINEAR TRON constants.
_ETA0, _ETA1, _ETA2 = 1e-4, 0.25, 0.75
_SIGMA1, _SIGMA2, _SIGMA3 = 0.25, 0.5, 4.0


def _boundary_tau(p: Array, d: Array, delta: Array, dot) -> Array:
    """τ ≥ 0 with ‖p + τ·d‖ = delta (positive root of the quadratic)."""
    dd = dot(d, d)
    pd = dot(p, d)
    pp = dot(p, p)
    disc = jnp.sqrt(jnp.maximum(pd * pd + dd * (delta * delta - pp), 0.0))
    return (-pd + disc) / jnp.maximum(dd, 1e-30)


def steihaug_cg(hvp, g: Array, delta: Array, max_iters: int, tol: Array,
                dot=jnp.dot):
    """Truncated CG for H p = −g inside ‖p‖ ≤ delta.

    Returns (p, Hp, n_hvp, n_steps) — Hp is maintained incrementally so the
    caller can compute the predicted reduction without another Hessian pass;
    n_hvp is the number of Hessian-vector products performed (for pass
    accounting), counted at the product, and n_steps the CG steps taken
    (today one product a step, so the two are equal).
    ``dot`` abstracts the inner product (a psum-reduced one when vectors are
    shards over a mesh axis).
    """

    class CGState(NamedTuple):
        p: Array
        r: Array      # residual = −g − Hp
        d: Array      # search direction
        hp: Array     # H @ p
        rr: Array
        it: Array
        n_hvp: Array
        done: Array

    r0 = -g
    init = CGState(
        p=jnp.zeros_like(g), r=r0, d=r0, hp=jnp.zeros_like(g),
        rr=dot(r0, r0), it=jnp.zeros((), jnp.int32),
        n_hvp=jnp.zeros((), jnp.int32), done=jnp.zeros((), bool),
    )

    def cond(st: CGState):
        return (~st.done) & (st.it < max_iters) & (jnp.sqrt(st.rr) > tol)

    def body(st: CGState) -> CGState:
        with jax.named_scope("tron.hvp"):
            hd = hvp(st.d)
        dhd = dot(st.d, hd)
        alpha = st.rr / jnp.where(dhd > 1e-30, dhd, 1.0)
        # Negative curvature or singular direction → walk to the boundary.
        neg_curv = dhd <= 1e-30
        p_try = st.p + alpha * st.d
        outside = jnp.sqrt(dot(p_try, p_try)) >= delta
        tau = _boundary_tau(st.p, st.d, delta, dot)
        hit_boundary = neg_curv | outside
        step = jnp.where(hit_boundary, tau, alpha)
        p_new = st.p + step * st.d
        hp_new = st.hp + step * hd
        r_new = st.r - step * hd
        rr_new = dot(r_new, r_new)
        beta = rr_new / jnp.maximum(st.rr, 1e-30)
        d_new = r_new + beta * st.d
        return CGState(
            p=p_new, r=r_new, d=d_new, hp=hp_new, rr=rr_new,
            it=st.it + 1, n_hvp=st.n_hvp + 1, done=hit_boundary,
        )

    st = lax.while_loop(cond, body, init)
    return st.p, st.hp, st.n_hvp, st.it


class _LoopState(NamedTuple):
    x: Array
    f: Array
    g: Array
    delta: Array
    it: Array
    reason: Array
    gnorm0: Array
    values: Array
    grad_norms: Array
    passes: Array   # int32 — instrumented data-pass counter
    hvp: Array      # int32 — Hessian-vector products of the CG solves
    cg_steps: Array  # int32 — CG steps
    rejected: Array  # int32 — trial steps the trust region refused


@dataclasses.dataclass(frozen=True)
class TRON(Optimizer):
    """Trust-region Newton. Requires an HVP factory alongside value+grad.

    ``optimize(value_and_grad, x0, hvp_at)`` where ``hvp_at(x)`` returns
    ``v ↦ H(x)·v``. The factory form lets an objective hoist work that
    depends only on x (GLM margins/curvature — see
    ``GLMObjective.bind_hvp_at``) out of the inner CG loop explicitly.
    Build one generically as
    ``lambda x: (lambda v: jax.jvp(grad_fn, (x,), (v,))[1])``.

    With ``axis_name`` set, ``x0``/gradients/CG vectors are SHARDS over that
    mesh axis (P3 feature sharding): every inner product psums across shards
    and the caller's value_and_grad/hvp must return globally-reduced values
    on shard-local vectors (see ``parallel/model_parallel.py``).
    """

    axis_name: str = None

    def optimize(  # type: ignore[override]
        self,
        value_and_grad: ValueAndGrad,
        x0: Array,
        hvp_at: "Callable[[Array], Callable[[Array], Array]]",
        hvp_passes: int = 2,
        factory_passes: int = 1,
    ) -> OptimizerResult:
        """``hvp_passes``/``factory_passes`` declare how many feature-data
        passes one H·v call / one ``hvp_at(x)`` call costs, for the
        ``data_passes`` counter. Defaults match ``GLMObjective.bind_hvp_at``
        (hoisted margin matvec at the factory, Xv matvec + rmatvec per HVP);
        callers with a different objective structure must pass their own
        costs (0/0 for objectives not backed by feature data)."""
        cfg = self.config
        max_it = cfg.max_iterations
        dtype = x0.dtype
        dot = make_dot(self.axis_name)
        norm = lambda v: jnp.sqrt(dot(v, v))

        f0, g0 = value_and_grad(x0)
        gnorm0 = norm(g0)
        values = jnp.full((max_it + 1,), jnp.inf, dtype).at[0].set(f0)
        gnorms = jnp.full((max_it + 1,), jnp.inf, dtype).at[0].set(gnorm0)

        init = _LoopState(
            x=x0, f=f0, g=g0, delta=gnorm0,
            it=jnp.zeros((), jnp.int32),
            reason=jnp.asarray(NOT_CONVERGED, jnp.int32),
            gnorm0=gnorm0, values=values, grad_norms=gnorms,
            passes=jnp.asarray(2, jnp.int32),  # init fused value+grad
            hvp=jnp.zeros((), jnp.int32), cg_steps=jnp.zeros((), jnp.int32),
            rejected=jnp.zeros((), jnp.int32),
        )

        def cond(st: _LoopState):
            return (st.reason == NOT_CONVERGED) & (st.it < max_it)

        def body(st: _LoopState) -> _LoopState:
            gnorm = norm(st.g)
            cg_tol = 0.1 * gnorm
            with jax.named_scope("tron.cg"):
                p, hp, n_hvp, n_cg = steihaug_cg(
                    hvp_at(st.x), st.g, st.delta,
                    cfg.max_cg_iterations, cg_tol, dot=dot,
                )
            with jax.named_scope("tron.trial"):
                # Predicted reduction of the quadratic model:
                # −(gᵀp + ½ pᵀHp).
                pred = -(dot(st.g, p) + 0.5 * dot(p, hp))
                x_try = st.x + p
                f_try, g_try = value_and_grad(x_try)
                actual = st.f - f_try
                rho = actual / jnp.where(jnp.abs(pred) > 1e-30, pred, 1.0)
                # A non-finite trial value must take the shrink branch.
                rho = jnp.where(jnp.isfinite(f_try), rho, -jnp.inf)

            with jax.named_scope("tron.radius"):
                pnorm = norm(p)
                # LIBLINEAR radius update: shrink on poor agreement, halve
                # on moderate, expand (bounded) on good.
                delta = jnp.where(
                    rho < _ETA1,
                    jnp.maximum(
                        _SIGMA1 * jnp.minimum(pnorm, st.delta), 1e-12),
                    jnp.where(
                        rho < _ETA2,
                        _SIGMA2 * st.delta,
                        jnp.clip(_SIGMA3 * pnorm, st.delta,
                                 _SIGMA3 * st.delta),
                    ),
                )
                accept = rho > _ETA0
                x_new = jnp.where(accept, x_try, st.x)
                f_new = jnp.where(accept, f_try, st.f)
                g_new = jnp.where(accept, g_try, st.g)

            it = st.it + 1
            gnorm_new = norm(g_new)
            # The function-value test is only meaningful on accepted steps —
            # a rejected step leaves f unchanged and must not read as
            # convergence; it shrinks delta and retries instead.
            reason = jnp.where(
                accept,
                check_convergence(it, st.f, f_new, gnorm_new, st.gnorm0, cfg),
                jnp.asarray(NOT_CONVERGED, jnp.int32),
            )
            # Collapsed radius means no further progress is possible.
            reason = jnp.where(
                (delta <= 1e-12) & (reason == NOT_CONVERGED),
                jnp.asarray(FUNCTION_VALUES_CONVERGED, jnp.int32),
                reason,
            )
            return _LoopState(
                x=x_new, f=f_new, g=g_new, delta=delta, it=it, reason=reason,
                gnorm0=st.gnorm0,
                values=st.values.at[it].set(f_new),
                grad_norms=st.grad_norms.at[it].set(gnorm_new),
                # Per outer iteration: the declared factory cost (hoisted
                # margin matvec for GLMs), hvp_passes per CG HVP, and 2 for
                # the fused trial value+grad.
                passes=st.passes + factory_passes + hvp_passes * n_hvp + 2,
                hvp=st.hvp + n_hvp, cg_steps=st.cg_steps + n_cg,
                rejected=st.rejected + (~accept).astype(jnp.int32),
            )

        st = lax.while_loop(cond, body, init)
        reason = finalize_reason(st.reason, st.it, max_it)
        return OptimizerResult(
            x=st.x, value=st.f, grad_norm=norm(st.g),
            iterations=st.it, converged_reason=reason,
            values=st.values, grad_norms=st.grad_norms,
            data_passes=st.passes,
            hvp=st.hvp, cg_steps=st.cg_steps, rejected=st.rejected,
        )
