"""L-BFGS as a single on-device XLA loop.

Parity: reference ⟦photon-lib/.../optimization/LBFGS.scala⟧ (which wraps
``breeze.optimize.LBFGS``): limited-memory quasi-Newton with the standard
two-loop recursion, line search, and dual convergence test.

TPU-first design (SURVEY.md §3.4, §7): where the reference runs the L-BFGS
iteration on the Spark *driver* — broadcasting coefficients and paying one
cluster round trip per iteration and per line-search probe — here the entire
loop (direction, line search, history update, convergence) is one
``lax.while_loop`` inside jit. Data-parallel gradients arrive via a ``psum``
baked into ``value_and_grad`` (see functions/distributed.py), so a whole
optimize() is one XLA program on the mesh with zero host round trips.

The history is a fixed-shape circular buffer ([m, D] S/Y plus [m] rho), masked
by the number of valid corrections — static shapes keep XLA happy and make the
optimizer `vmap`-able for batched per-entity random-effect solves.

Line search: backtracking Armijo with quadratic-fit shrink. Breeze uses strong
Wolfe; for batch-convex GLM objectives backtracking reaches the same optimum
(golden tests vs scipy assert optima, not trajectories) while costing one
fused value+grad pass per probe on-device.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from photon_tpu.optim.base import (
    FUNCTION_VALUES_CONVERGED,
    NOT_CONVERGED,
    Optimizer,
    OptimizerConfig,
    OptimizerResult,
    ValueAndGrad,
    check_convergence,
    finalize_reason,
)

Array = jax.Array


class LBFGSHistory(NamedTuple):
    """Circular-buffer curvature history."""

    s: Array      # [m, D] parameter deltas
    y: Array      # [m, D] gradient deltas
    rho: Array    # [m]    1 / (sᵀy)
    count: Array  # int32 — number of valid corrections (≤ m)
    pos: Array    # int32 — next write slot


def empty_history(m: int, d: int, dtype) -> LBFGSHistory:
    return LBFGSHistory(
        s=jnp.zeros((m, d), dtype),
        y=jnp.zeros((m, d), dtype),
        rho=jnp.zeros((m,), dtype),
        count=jnp.zeros((), jnp.int32),
        pos=jnp.zeros((), jnp.int32),
    )


def make_dot(axis_name=None):
    """Coefficient-space inner product. With ``axis_name``, vectors are
    SHARDS over that mesh axis (SURVEY.md §2.6 P3: feature-dimension-sharded
    optimizer state) and the dot completes with a ``psum`` over ICI — the
    sharded-state analog of the reference broadcasting whole vectors."""
    if axis_name is None:
        return jnp.dot
    return lambda a, b: lax.psum(jnp.dot(a, b), axis_name)


@jax.named_scope("lbfgs.direction")
def two_loop_direction(g: Array, hist: LBFGSHistory, dot=jnp.dot) -> Array:
    """Compute −H·g via the standard two-loop recursion over the masked buffer.

    Falls back to steepest descent when the history is empty. All loops are
    ``fori_loop`` over the *static* memory size m with masking, so the
    computation has fixed shape regardless of how many corrections are valid.
    Under a sharded ``dot``, g/s/y are per-device shards and every inner
    product psums over the model axis; α/ρ/γ scalars stay replicated.
    """
    m = hist.rho.shape[0]

    def backward(j, carry):
        q, alpha = carry
        idx = jnp.mod(hist.pos - 1 - j, m)
        valid = j < hist.count
        a = hist.rho[idx] * dot(hist.s[idx], q)
        a = jnp.where(valid, a, 0.0)
        q = q - a * hist.y[idx]
        alpha = alpha.at[idx].set(a)
        return q, alpha

    q0 = g
    alpha0 = jnp.zeros((m,), g.dtype)
    q, alpha = lax.fori_loop(0, m, backward, (q0, alpha0))

    # Initial Hessian scaling γ = sᵀy / yᵀy from the newest pair.
    newest = jnp.mod(hist.pos - 1, m)
    sy = dot(hist.s[newest], hist.y[newest])
    yy = dot(hist.y[newest], hist.y[newest])
    gamma = jnp.where(hist.count > 0, sy / jnp.maximum(yy, 1e-30), 1.0)
    r = gamma * q

    def forward(j, r):
        idx = jnp.mod(hist.pos - hist.count + j, m)
        valid = j < hist.count
        b = hist.rho[idx] * dot(hist.y[idx], r)
        corr = jnp.where(valid, alpha[idx] - b, 0.0)
        return r + corr * hist.s[idx]

    r = lax.fori_loop(0, m, forward, r)
    return -r


@jax.named_scope("lbfgs.update")
def update_history(
    hist: LBFGSHistory, s: Array, y: Array, dot=jnp.dot
) -> LBFGSHistory:
    """Push a curvature pair, skipping it if sᵀy is not sufficiently positive."""
    sy = dot(s, y)
    ok = sy > 1e-10 * jnp.sqrt(dot(s, s)) * jnp.sqrt(dot(y, y))

    def push(h: LBFGSHistory) -> LBFGSHistory:
        return LBFGSHistory(
            s=h.s.at[h.pos].set(s),
            y=h.y.at[h.pos].set(y),
            rho=h.rho.at[h.pos].set(1.0 / sy),
            count=jnp.minimum(h.count + 1, h.s.shape[0]),
            pos=jnp.mod(h.pos + 1, h.s.shape[0]),
        )

    pushed = push(hist)
    return jax.tree.map(lambda a, b: jnp.where(ok, a, b), pushed, hist)


@jax.named_scope("lbfgs.line_search")
def armijo_backtrack(
    probe,
    f: Array,
    dg: Array,
    init_aux,
    max_iters: int,
    c1: float = 1e-4,
    shrink: float = 0.5,
):
    """Shared Armijo backtracking core. ``probe: t ↦ (f(x + t·d), aux)`` —
    the aux rides along untouched (the plain path carries the probe's
    gradient; the scored path carries nothing).

    Returns ``(t_final, ft, aux, accept, n_probes)``; ``t_final`` is 0 on a
    fully failed search (the caller's convergence logic stops on function
    values). If no step satisfies Armijo within the cap, the last (smallest)
    probe is accepted only if it still decreases f. NaN/Inf-safe: non-finite
    probe values are treated as failures.
    """

    def cond(carry):
        t, fx, _, _, it, done = carry
        return (~done) & (it < max_iters)

    def body(carry):
        t, _, _, _, it, _ = carry
        ft, aux = probe(t)
        ok = (ft <= f + c1 * t * dg) & jnp.isfinite(ft)
        return (jnp.where(ok, t, t * shrink), ft, aux, t, it + 1, ok)

    t0 = jnp.asarray(1.0, f.dtype)
    t, ft, aux, t_used, n, ok = lax.while_loop(
        cond, body,
        (t0, f, init_aux, t0, jnp.zeros((), jnp.int32), jnp.zeros((), bool)),
    )
    accept = ok | (jnp.isfinite(ft) & (ft < f))
    t_final = jnp.where(accept, t_used, 0.0)
    return t_final, ft, aux, accept, n


def backtracking_line_search(
    value_and_grad: ValueAndGrad,
    x: Array,
    f: Array,
    g: Array,
    d: Array,
    max_iters: int,
    c1: float = 1e-4,
    shrink: float = 0.5,
    dot=jnp.dot,
):
    """Armijo backtracking from t=1. Returns (x⁺, f⁺, g⁺, t, n_probes).

    Each probe is one fused value+grad evaluation (one data pass on-device).
    """
    dg = dot(d, g)
    t_final, ft, gt, accept, n = armijo_backtrack(
        lambda t: value_and_grad(x + t * d), f, dg, g, max_iters, c1, shrink
    )
    # Select (not scale by t=0): keeps x clean even if d has NaN/Inf entries.
    x_new = jnp.where(accept, x + t_final * d, x)
    f_new = jnp.where(accept, ft, f)
    g_new = jax.tree.map(lambda a, b: jnp.where(accept, a, b), gt, g)
    return x_new, f_new, g_new, t_final, n


class _LoopState(NamedTuple):
    x: Array
    f: Array
    g: Array
    extra: object          # step-strategy carry (e.g. maintained scores z)
    hist: LBFGSHistory
    it: Array
    reason: Array
    gnorm0: Array
    values: Array
    grad_norms: Array
    passes: Array          # int32 — instrumented data-pass counter


@dataclasses.dataclass(frozen=True)
class LBFGS(Optimizer):
    """Limited-memory BFGS. ``optimize`` is pure/jittable/vmappable.

    With ``axis_name`` set, ``x0``/gradients/history are SHARDS over that
    mesh axis (run inside ``shard_map``); every coefficient-space inner
    product completes with a psum, so optimizer state never materializes
    full-length vectors on any device (SURVEY.md §2.6 P3).
    """

    axis_name: str = None

    def _solve(self, x0, f0, g0, extra0, step_fn, init_passes=2) -> OptimizerResult:
        """Shared loop core: direction, step via ``step_fn``, history update,
        convergence bookkeeping. ``step_fn(st, dvec, it) →
        (x, f, g, extra, t_final, passes)``; ``t_final == 0`` marks a fully
        failed line search (no further progress possible); ``passes`` is the
        number of data passes the step made (see OptimizerResult)."""
        cfg = self.config
        max_it = cfg.max_iterations
        dtype = x0.dtype
        dot = make_dot(self.axis_name)
        norm = lambda v: jnp.sqrt(dot(v, v))

        gnorm0 = norm(g0)
        values = jnp.full((max_it + 1,), jnp.inf, dtype).at[0].set(f0)
        gnorms = jnp.full((max_it + 1,), jnp.inf, dtype).at[0].set(gnorm0)

        init = _LoopState(
            x=x0, f=f0, g=g0, extra=extra0,
            hist=empty_history(cfg.history_length, x0.shape[-1], dtype),
            it=jnp.zeros((), jnp.int32),
            reason=jnp.asarray(NOT_CONVERGED, jnp.int32),
            gnorm0=gnorm0,
            values=values, grad_norms=gnorms,
            passes=jnp.asarray(init_passes, jnp.int32),
        )

        def cond(st: _LoopState):
            return (st.reason == NOT_CONVERGED) & (st.it < max_it)

        def body(st: _LoopState) -> _LoopState:
            dvec = two_loop_direction(st.g, st.hist, dot)
            # Safeguard: if not a descent direction, restart from −g.
            descent = dot(dvec, st.g) < 0
            dvec = jnp.where(descent, dvec, -st.g)

            x_new, f_new, g_new, extra, t, step_passes = step_fn(st, dvec, st.it)
            hist = update_history(st.hist, x_new - st.x, g_new - st.g, dot)
            it = st.it + 1
            gnorm = norm(g_new)
            reason = check_convergence(it, st.f, f_new, gnorm, st.gnorm0, cfg)
            # A fully failed line search (t == 0) cannot make further progress.
            reason = jnp.where(
                (t == 0.0) & (reason == NOT_CONVERGED),
                jnp.asarray(FUNCTION_VALUES_CONVERGED, jnp.int32),
                reason,
            )
            return _LoopState(
                x=x_new, f=f_new, g=g_new, extra=extra, hist=hist, it=it,
                reason=reason, gnorm0=st.gnorm0,
                values=st.values.at[it].set(f_new),
                grad_norms=st.grad_norms.at[it].set(gnorm),
                passes=st.passes + step_passes.astype(jnp.int32),
            )

        st = lax.while_loop(cond, body, init)
        reason = finalize_reason(st.reason, st.it, max_it)
        return OptimizerResult(
            x=st.x, value=st.f, grad_norm=norm(st.g),
            iterations=st.it, converged_reason=reason,
            values=st.values, grad_norms=st.grad_norms,
            data_passes=st.passes,
        )

    def optimize(self, value_and_grad: ValueAndGrad, x0: Array) -> OptimizerResult:
        cfg = self.config
        dot = make_dot(self.axis_name)
        f0, g0 = value_and_grad(x0)

        def step(st, dvec, it):
            x_new, f_new, g_new, t, n_probes = backtracking_line_search(
                value_and_grad, st.x, st.f, st.g, dvec,
                cfg.max_line_search_iterations, dot=dot,
            )
            # Each probe is one fused value+grad = 1 matvec + 1 rmatvec.
            return x_new, f_new, g_new, st.extra, t, 2 * n_probes

        return self._solve(x0, f0, g0, jnp.zeros((), x0.dtype), step)

    def optimize_scored(self, so, x0: Array) -> OptimizerResult:
        """L-BFGS with incrementally maintained margins z = Xw + offsets.

        The reference pays a full data pass (a Spark job) per line-search
        probe (SURVEY.md §3.4). Here each iteration computes Xp ONCE for the
        chosen direction; every probe prices f(w + t·p) from z + t·Xp with
        elementwise work only, and the accepted point costs one rmatvec for
        the gradient. Net data passes per iteration: 1 matvec + 1 rmatvec,
        independent of probe count.

        ``so`` is a ``functions.objective.ScoreSpaceObjective``. Same
        optimum/convergence semantics as ``optimize`` (identical math;
        floating-point rounding of z + t·Xp vs X(w + t·p) differs at ~ulp).
        """
        cfg = self.config
        dot = make_dot(self.axis_name)
        dtype = x0.dtype

        z0 = so.score(x0)
        f0 = so.value_from_scores(z0, x0)
        g0 = so.grad_from_scores(z0, x0)

        def step(st, dvec, it):
            z = st.extra
            zp = so.score_delta(dvec)          # the ONE data pass (matvec)
            dg = dot(dvec, st.g)
            # Probes are elementwise over maintained scores — no data pass.
            t_final, ft, _, accept, _ = armijo_backtrack(
                lambda t: (
                    so.value_from_scores(z + t * zp, st.x + t * dvec),
                    jnp.zeros((), dtype),
                ),
                st.f, dg, jnp.zeros((), dtype),
                cfg.max_line_search_iterations,
            )
            x_new = jnp.where(accept, st.x + t_final * dvec, st.x)
            z_new = jnp.where(accept, z + t_final * zp, z)
            # Refresh z from x periodically: the incremental z accumulates
            # one rounding per accepted step, which can stall convergence
            # near the optimum. One extra matvec every 8 iterations.
            refresh = jnp.mod(it + 1, 8) == 0
            z_new = lax.cond(
                refresh,
                lambda: so.score(x_new),
                lambda: z_new,
            )
            f_new = jnp.where(accept, ft, st.f)
            g_new = so.grad_from_scores(z_new, x_new)   # one rmatvec
            # 1 matvec (Xp) + 1 rmatvec (grad) + the conditional z refresh.
            passes = 2 + refresh.astype(jnp.int32)
            return x_new, f_new, g_new, z_new, t_final, passes

        return self._solve(x0, f0, g0, z0, step)
