"""Batched Newton solvers for random-effect buckets (primal and dual).

Why: the general RE path (``game/random_effect.py``) vmaps the full L-BFGS
``lax.while_loop`` over entities. Profiled at the ``game_scale`` bench
shape (100K users × 16 rows × 256-wide local subspaces, CPU), the dominant
cost is the O(E·m·P) L-BFGS HISTORY traffic — the [E, m, P] s/y stacks the
two-loop recursion reads and ``update_history`` rewrites every iteration
(measured: halving m halves the step; data passes are few and cheap).
Quasi-Newton memory is exactly the wrong data structure for a hundred
thousand tiny coupled solves.

Two history-free replacements, picked per bucket by shape:

* **Primal dense Newton** (``fit_bucket_newton``), for small local dims
  (P ≤ 64): the per-entity Hessian is [P, P], assembled as ONE batched
  einsum ``es,esp,esq->epq`` — an MXU-shaped contraction, no per-lane
  control flow — and solved as a batched factorization.
* **Span-reduced (dual) Newton** (``fit_bucket_newton_dual``), for the
  canonical RE regime of FEW ROWS in a WIDE subspace (S ≪ P, e.g. 16 rows
  × 256 features): for an L2/Gaussian-prior objective the stationarity
  condition ``D·w = −Xᵀ(tw·ℓ') + q`` puts the penalized coordinates of
  the optimum in the row span scaled by D⁻¹ (D = λ·mask + prior
  precision, q = precision·prior-mean). Parametrize
  ``w = D⁺(Xᵀα + q) + Σ_u β_u e_u`` (β for the ≤U unpenalized columns,
  typically just the intercept) and the whole solve lives in S+U ≈ 17
  dimensions: margins are LINEAR in θ=(α,β) via the Gram matrix
  G = X D⁺ Xᵀ [S,S], the penalty collapses to ½αᵀGα (+ a constant), and
  each Newton system is (S+U)². G builds once per solve as one batched
  einsum; iterations cost O(E·S³) instead of O(E·m·P) memory traffic.

Both paths share one damped-Newton driver (``_newton_loop``): ridge-damped
batched solves, steepest-descent fallback, and a vectorized line search —
ALL backtracking steps evaluate in one [L, E] pass over resident margins,
so no lane ever stalls another (the masked-divergence cost class of
vmapped while_loops is gone). Convergence is quadratic: ~5 Newton
iterations replace 15+ L-BFGS iterations. All four pointwise losses ship
analytic d2 (``ops/losses.py``), the L2 term and Gaussian priors are
quadratic (exact in the Hessian), and SIMPLE variances derive from the
primal Hessian diagonal — same formulas as
``GLMOptimizationProblem._variances``.

Scope (the eligibility gates in ``train_random_effects``): smooth
objectives only (no L1/OWL-QN — the orthant machinery needs its own
treatment), no normalization context, dense buffers within
``PHOTON_RE_NEWTON_BUDGET_MB``. Everything else falls back to the general
vmapped path; ``PHOTON_RE_NEWTON=0`` forces the fallback.

**Entity sub-batching** (``fit_bucket_in_chunks``): the per-entity solves
are embarrassingly parallel over the entity axis, so a bucket whose
``[E,P]``/``[E,S]`` probe footprint exceeds the budget gate no longer
surrenders to the vmapped L-BFGS fallback — it is split into entity chunks
drawn from a small CLOSED ladder of blessed sizes (``chunk_ladder()``),
each chunk solved through the same jitted kernel (one XLA compile per
ladder size, so the retrace sentinel stays quiet across sweeps), and the
results restacked. The last partial chunk is padded with inert lanes
(weight-0 rows, ghost columns, mask 1, precision-0 priors) — the same
convention as ``_pad_bucket`` — so chunking never adds compiled shapes
beyond the ladder. Chunking also *decouples convergence*: each chunk's
``while_loop`` stops when ITS slowest lane converges, instead of every
entity in the bucket iterating until the bucket-wide straggler is done.

**CPU/TPU kernel shape discipline**: the hot contractions (Gram build,
Hessian assembly) are written as explicit batched ``matmul``s over
``optimization_barrier``-materialized operands. Measured on the CPU
backend at the ``game_scale`` bench shape ([100K,16,256]): letting XLA
fuse the scatter/scale producers into the dot turns a 1.4 s batched GEMM
into a 9 s fused loop — the barrier forces the operands into contiguous
buffers the fast GEMM path can consume.

**The Newton systems' factorization.** The damped Hessian is symmetric PD
by construction, so the [T,T] system of every lane is solved by Cholesky,
not generic LU (the library's batched Cholesky halves LU's cost on the
CPU; that reading is the CPU's). On the chip the library call
(``jnp.linalg.cholesky``: the TPU's ``Cholesky`` custom call, and
``cho_solve``'s triangular inversions) does not use the batch axis: it
read 3.7 us a 32 x 32 system whatever the batch, 22.6 ms at 6,040 systems
and 69.3 ms at 18,879, the largest device operation of both GAME cells
(PERF.md §6, PR 34). ``_lane_cholesky_solve`` is the same float32
factorization written over [T,T,E] arrays whose minor axis is the entity,
a ``fori_loop`` of T column steps each one fused elementwise pass over all
E lanes: 1.10 and 2.17 ms at those sizes. Every width a gate admits takes
it (``scripts/newton_solve_check.py`` on a v5e, lanes | library, ms, at
4,096 systems: T 17: 0.67 | 8.41; 32: 1.09 | 15.4; 64: 3.28 | 33.6; 80:
5.51 | 43.5; 96: 46.2 | 55.2; 112: 72.9 | 91.4; 128: 109.0 | 103.7, and at
128 x 1,024, the widest a chunked bucket's ladder size makes it, 5.08 |
26.4; at 3 systems of 32 the two tie). The cost steps up where the [T,T,E]
matrix leaves the chip's fast memory (105 MB held there, 151 MB not), so
T*T*E decides, not T; walking E in blocks would flatten that and waits
for a cell with buckets that wide (PERF.md §7 item 12).

Parity: reference ⟦RandomEffectCoordinate.scala⟧ + ⟦SingleNodeOptimizationProblem⟧
(SURVEY.md §3.5) run one Breeze L-BFGS per entity; these solvers reach the
same optimum of the same objective, re-shaped for a batched accelerator.
"""
from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from photon_tpu.data.random_effect import SELECT_MAX_COLUMNS
from photon_tpu.models.coefficients import Coefficients
from photon_tpu.models.glm import GeneralizedLinearModel
from photon_tpu.obs import device_wait
from photon_tpu.ops.losses import loss_for_task
from photon_tpu.optim.base import (
    FUNCTION_VALUES_CONVERGED,
    NOT_CONVERGED,
    OptimizerResult,
    check_convergence,
    finalize_reason,
)

Array = jax.Array

NEWTON_MAX_P = 64           # [P,P] solves stay tiny; beyond this, fall back
                            # (documented gate: module doc and
                            # docs/scaling.md say P <= 64 — keep in sync)
NEWTON_CHUNK_MAX_P = 128    # wider P admitted for CHUNKED primal candidates
                            # under MEASURED routing only — at P in (64,128]
                            # the dense Hessian may or may not beat L-BFGS
                            # depending on S, so the calibration race (not a
                            # static gate) decides (game/solver_routing.py)
DUAL_MAX_T = 80  # S + U cap; beyond this the (S+U)^2 systems stop being tiny
_DEFAULT_BUDGET_MB = 2048   # dense X + H + probe buffers cap

# Blessed entity-chunk sizes for sub-batched solves. A CLOSED set on
# purpose: every chunked solve compiles at one of these sizes (last chunk
# padded up), so the number of XLA executables per (solver, S, P, dtype)
# class is bounded by the ladder length and the retrace sentinel stays
# quiet across sweeps. Override: PHOTON_RE_CHUNK_LADDER=256,1024,...
_DEFAULT_CHUNK_LADDER = (256, 1024, 4096, 16384)


def chunk_ladder() -> tuple:
    raw = os.environ.get("PHOTON_RE_CHUNK_LADDER", "")
    if raw:
        sizes = tuple(sorted({int(x) for x in raw.split(",") if x.strip()}))
        if not sizes or min(sizes) < 1:
            raise ValueError(
                f"PHOTON_RE_CHUNK_LADDER must be positive ints, got {raw!r}"
            )
        return sizes
    return _DEFAULT_CHUNK_LADDER


def _budget_bytes() -> float:
    return float(os.environ.get("PHOTON_RE_NEWTON_BUDGET_MB",
                                _DEFAULT_BUDGET_MB)) * 1e6


def _smooth_ok(problem, normalization) -> bool:
    if os.environ.get("PHOTON_RE_NEWTON", "") == "0":
        return False
    from photon_tpu.optim import OptimizerType

    if problem.optimizer_type not in (OptimizerType.LBFGS,
                                      OptimizerType.TRON):
        return False  # OWL-QN/L1: non-smooth, orthant semantics
    if problem.regularization.l1_weight(float(problem.reg_weight)) > 0.0:
        return False
    return normalization is None


def penalty_terms(problem, local_mask, local_prior, dtype=jnp.float32):
    """``(l2v, pm, pp, d_pen)`` in ``dtype`` — the quadratic-penalty pieces
    BOTH solvers and the eligibility gate derive everything from. ONE
    definition on purpose: the u_max gate counts ``d_pen <= 0`` and the dual
    solver inverts ``d_pen > 0`` — computed anywhere else (other dtype, other
    threshold) a divergence would silently pin a coefficient to zero. The
    gate's zero-count is dtype-insensitive (masks and λ are exact in f32),
    so callers may pass any float dtype without moving the threshold."""
    lam = problem.regularization.l2_weight(float(problem.reg_weight))
    l2v = lam * local_mask.astype(dtype)
    if local_prior is not None:
        pm = local_prior.means.astype(dtype)
        pp = local_prior.precisions.astype(dtype)
    else:
        pm = jnp.zeros_like(l2v)
        pp = jnp.zeros_like(l2v)
    return l2v, pm, pp, l2v + pp


def u_max_for(d_pen) -> int:
    """Worst-per-entity count of UNPENALIZED columns (d_pen == 0) that the
    dual path must carry as explicit β parameters — typically 1 (the
    reg-masked intercept). Static for jit."""
    with device_wait("u_max"):
        return int(jnp.max(jnp.sum(d_pen <= 0.0, axis=1)))


def _primal_need_bytes(e: int, s: int, p: int, esize: float) -> float:
    """Dominant dense buffers of an E-entity primal solve (in the data
    dtype): X [E,S,P+1], H [E,P,P], and the probe batch's [L,E,S] margins +
    [L,E,S] loss temporary + [L,E,P] trial parameters (L capped at 12)."""
    return esize * (e * s * (p + 1) + e * p * p + 12 * e * (2 * s + p))


def _dual_need_bytes(e: int, s: int, p: int, u: int, esize: float) -> float:
    """Dominant dense buffers of an E-entity dual solve: dense X [E,S,P+1]
    + G/J [E,S,S+U] + the probe batch's [12,E,S] margins + [12,E,S] loss
    temporary + [12,E,S+U] trial parameters. Dense X dominates at wide P."""
    return esize * (e * s * (p + 1) + 2 * e * s * (s + u)
                    + 12 * e * (2 * s + s + u))


def newton_eligible(problem, bucket, normalization, shards: int = 1) -> bool:
    """True when this bucket's solve may take the PRIMAL dense-Newton path.

    ``shards`` is the entity-axis mesh size: a sharded dispatch places
    E/shards lanes per device, so the budget gate prices the PER-DEVICE
    footprint — a bucket too big for one device's budget can still run
    full-bucket across the mesh (the gates get MORE permissive, exactly
    the reference's "add executors" scaling axis)."""
    if os.environ.get("PHOTON_RE_NEWTON", "") == "dual":
        return False  # test/debug override: route to the dual path
    if not _smooth_ok(problem, normalization):
        return False
    e, s, _ = bucket.idx.shape
    p = bucket.local_dim
    if p > NEWTON_MAX_P:
        return False
    esize = float(np.dtype(bucket.val.dtype).itemsize)
    e_dev = -(-e // max(1, shards))
    return _primal_need_bytes(e_dev, s, p, esize) <= _budget_bytes()


def _largest_fitting_chunk(need_at, e: int, multiple_of: int = 1):
    """Best blessed chunk size for an E-entity bucket, or None when even
    the smallest ladder size busts the budget. Padding lanes do FULL
    solver work, so a 2000-entity bucket should solve as 2x1024, not one
    4096-padded chunk — but shaving the last few padding percent is not
    worth an order of magnitude more dispatches (100K entities at chunk
    256 is 391 kernel calls). Rule: the LARGEST budget-fitting size whose
    total padded lanes ``ceil(E/C)*C`` stay within 12.5% of E; if none
    qualifies (tiny buckets), the size minimizing padded lanes.
    ``multiple_of`` (the entity-axis mesh size) filters the ladder to
    sizes that shard evenly — a chunk that doesn't divide over the mesh
    would leave devices with ragged lanes and re-lay the sharding out."""
    budget = _budget_bytes()
    fitting = []
    for c in chunk_ladder():
        if need_at(c) > budget:
            break  # ladder is sorted: larger sizes only need more
        if c % multiple_of:
            continue
        fitting.append(c)
        if c >= e:
            break  # larger sizes only add padding
    if not fitting:
        return None
    for c in reversed(fitting):
        if -(-e // c) * c <= e + (e >> 3):
            return c
    return min(fitting, key=lambda c: (-(-e // c) * c, -c))


def newton_chunk_size(problem, bucket, normalization,
                      max_p: int = NEWTON_MAX_P, shards: int = 1):
    """Blessed chunk size for an entity-sub-batched PRIMAL solve of this
    bucket, or None when the primal path is shape-excluded or even the
    smallest chunk busts the budget. ``max_p`` lets MEASURED routing admit
    wider subspaces (NEWTON_CHUNK_MAX_P) than the static gate. ``shards``
    > 1 prices the per-device slice of each sharded chunk and restricts
    the ladder to mesh-divisible sizes."""
    if os.environ.get("PHOTON_RE_NEWTON", "") == "dual":
        return None
    if not _smooth_ok(problem, normalization):
        return None
    e, s, _ = bucket.idx.shape
    p = bucket.local_dim
    if p > max_p:
        return None
    esize = float(np.dtype(bucket.val.dtype).itemsize)
    sh = max(1, shards)
    return _largest_fitting_chunk(
        lambda c: _primal_need_bytes(-(-c // sh), s, p, esize), e,
        multiple_of=sh)


def dual_chunk_size(problem, bucket, normalization, u_max: int,
                    shards: int = 1):
    """Blessed chunk size for an entity-sub-batched DUAL solve, or None."""
    if not dual_precheck(problem, bucket, normalization):
        return None
    e, s, _ = bucket.idx.shape
    p = bucket.local_dim
    if s + u_max > DUAL_MAX_T:
        return None
    esize = float(np.dtype(bucket.val.dtype).itemsize)
    sh = max(1, shards)
    return _largest_fitting_chunk(
        lambda c: _dual_need_bytes(-(-c // sh), s, p, u_max, esize), e,
        multiple_of=sh)


def dual_precheck(problem, bucket, normalization) -> bool:
    """The CHEAP dual-path gates — everything that does not need u_max.
    Callers check this FIRST: computing u_max is a device reduction + D2H
    sync per bucket, and paying it for a bucket that can never take the
    dual path (L1 run, wide rows, FULL variance) would serialize the
    streaming loop's transfer/compute overlap for nothing."""
    if not _smooth_ok(problem, normalization):
        return False
    from photon_tpu.functions.problem import VarianceComputationType

    if problem.variance_type == VarianceComputationType.FULL:
        return False  # diag(H^-1) needs the [P,P] primal Hessian
    _, s, _ = bucket.idx.shape
    p = bucket.local_dim
    # s+0 lower-bounds s+u_max, so this never rejects an eligible bucket.
    return s < p and s <= DUAL_MAX_T


def dual_eligible(problem, bucket, normalization, u_max: int,
                  shards: int = 1) -> bool:
    """True when this bucket may take the span-reduced Newton path.
    ``shards`` prices the per-device slice (see ``newton_eligible``)."""
    if not dual_precheck(problem, bucket, normalization):
        return False
    e, s, _ = bucket.idx.shape
    p = bucket.local_dim
    if s + u_max > DUAL_MAX_T:
        return False
    esize = float(np.dtype(bucket.val.dtype).itemsize)
    e_dev = -(-e // max(1, shards))
    return _dual_need_bytes(e_dev, s, p, u_max, esize) <= _budget_bytes()


@jax.named_scope("newton.design")
def _dense_design(batches, dtype):
    """Dense local design [E,S,P+1] — the ELL ghost column (== P) lands in
    the extra column, whose values are zero. ONE buffer replaces per-probe
    ELL gathers for the whole solve. Also returns (y, off, tw) in ``dtype``
    (the solve precision — f64 datasets keep full precision, ADVICE r5).

    Up to ``SELECT_MAX_COLUMNS`` local columns it is built by
    compare-select, not by a scatter: entry k of a slot goes to the column
    its index names, ``sum_k where(idx_k == column, val_k, 0)``, one fused
    elementwise pass over [E,S,K,P+1] that materializes only the design.
    The vmapped scatter-add it replaces there compiled for 15.5 s with
    1.69 GB of temporaries at 12,874 x 256 x 3 for a described v5e (0.4 s
    and none by the pick; PERF.md §6, PR 33), a program a size class.
    Entries of one slot that share a column add, either way."""
    idx = batches.features.idx
    val = batches.features.val.astype(dtype)
    p = batches.features.dim
    if p > SELECT_MAX_COLUMNS:
        e, s, _ = idx.shape
        ei = jnp.arange(e)[:, None, None]
        si = jnp.arange(s)[None, :, None]
        x_ext = jnp.zeros((e, s, p + 1), dtype).at[ei, si, idx].add(val)
    else:
        columns = jnp.arange(p + 1, dtype=idx.dtype)
        x_ext = jnp.sum(
            jnp.where(idx[..., None] == columns, val[..., None], 0), axis=2)
    # Materialization boundary: without it XLA fuses the producer into every
    # downstream dot, and the batched GEMMs degrade to a scalar loop
    # (measured 6x slower at the game_scale shape on CPU — module doc).
    x_ext = jax.lax.optimization_barrier(x_ext)
    return (
        x_ext,
        batches.labels.astype(dtype),
        batches.offsets.astype(dtype),
        batches.weights.astype(dtype),
    )


def solve_form() -> str:
    """How ``_newton_loop`` solves its [T,T] systems, for the loop and for
    the ``optim.re_bucket`` span: ``lanes``, or ``lu`` under
    ``jax_debug_nans`` (see the loop)."""
    return "lu" if jax.config.jax_debug_nans else "lanes"


def _lane_cholesky_solve(h, b):
    """Solve ``h @ x = b`` for every lane by a Cholesky whose minor axis is
    the entity: ``h`` [E,T,T] symmetric, ``b`` [E,T], returns ``x`` [E,T].

    The same float32 factorization the library call makes, laid out so the
    parallelism is where the work is: ``T`` column steps, each a few
    elementwise operations over all ``E`` lanes of a [T,T,E] array
    (right-looking: column ``j`` over the root of its pivot, rows above
    ``j`` masked, its outer product taken off the trailing matrix). Only
    the leading axis is indexed dynamically. Row ``j`` of the carried
    array ends as column ``j`` of L, so one array holds the input, the
    trailing matrix and the factor. A lane that is not positive definite
    takes the root of a pivot <= 0 and comes back NaN in that lane alone,
    as the library's does (``_newton_loop``'s ``bad`` test relies on it).
    """
    def at(m, j):                  # m[j], the leading axis alone dynamic
        return jax.lax.dynamic_index_in_dim(m, j, 0, keepdims=False)

    t_dim = h.shape[-1]
    rows = jnp.arange(t_dim)[:, None]                      # [T, 1]
    # The mean of the two triangles, as ``jnp.linalg.cholesky`` takes its
    # input: a Hessian from a float32 GEMM is symmetric only to rounding.
    a = 0.5 * (jnp.transpose(h, (1, 2, 0))
               + jnp.transpose(h, (2, 1, 0)))              # [T, T, E]
    y = b.T                                                # [T, E]

    def factor_column(j, a):
        col = at(a, j)
        l_j = jnp.where(rows >= j, col / jnp.sqrt(at(col, j)), 0.0)
        # One elementwise pass over the matrix, row j written by the same
        # select: a dynamic_update_slice after the subtraction costs the
        # TPU's compiler two more passes (an update fusion and a copy).
        return jnp.where(rows[:, :, None] == j, l_j[None, :, :],
                         a - l_j[:, None, :] * l_j[None, :, :])

    a = jax.lax.fori_loop(0, t_dim, factor_column, a)

    def forward(j, y):                                     # L y = b
        l_j = at(a, j)
        y_j = at(y, j) / at(l_j, j)
        return jnp.where(rows == j, y_j, y - y_j * l_j)

    y = jax.lax.fori_loop(0, t_dim, forward, y)

    def backward(k, x):                                    # L^T x = y
        j = t_dim - 1 - k
        l_j = at(a, j)
        # x is still zero at rows <= j and l_j is zero above j: the sum is
        # over the rows already solved.
        x_j = (at(y, j) - jnp.sum(l_j * x, axis=0)) / at(l_j, j)
        return jax.lax.dynamic_update_index_in_dim(x, x_j, j, 0)

    return jax.lax.fori_loop(0, t_dim, backward, jnp.zeros_like(y)).T


def _newton_loop(x0, z0, cfg, value_at, grad_at, hess_at, lin_map,
                 probe_values, ridge):
    """Shared damped-Newton driver over a batch of independent lanes.

    ``x0`` [E,T] parameters, ``z0`` [E,S] resident margins. Closures:
    ``value_at(x, z) -> [E]``, ``grad_at(x, z) -> [E,T]``,
    ``hess_at(x, z) -> [E,T,T]``, ``lin_map(d) -> [E,S]`` (margin delta of
    a parameter direction — margins are linear in the parameters on both
    paths), ``probe_values(x, z, d, zd, ts) -> [L,E]`` (objective at every
    backtracking step in one vectorized pass). ``ridge`` scales the
    trace-relative jitter that keeps the batched factorization PD on
    degenerate lanes (all-zero padded entities; dual G nullspace).

    Returns ``(x, z, f, g, reason, it, values, gnorms, passes, iters)``
    with the same per-lane bookkeeping conventions as the vmapped L-BFGS
    path (inf-filled trajectory tails, accepted-step iteration counts).
    """
    e, t_dim = x0.shape
    dt = x0.dtype
    max_it = cfg.max_iterations
    # 12 vectorized backtracking probes reach t = 2^-11 ≈ 5e-4 — below
    # that a damped-Newton step on a smooth convex objective is noise.
    n_probe = min(cfg.max_line_search_iterations, 12)
    ts = 0.5 ** jnp.arange(n_probe, dtype=dt)
    eye = jnp.eye(t_dim, dtype=dt)
    c1 = 1e-4

    f = value_at(x0, z0)
    g = grad_at(x0, z0)
    gnorm0 = jnp.linalg.norm(g, axis=1)
    values = jnp.full((e, max_it + 1), jnp.inf, dt).at[:, 0].set(f)
    gnorms = jnp.full((e, max_it + 1), jnp.inf, dt).at[:, 0].set(gnorm0)

    state = (
        x0, z0, f, g,
        jnp.full((e,), NOT_CONVERGED, jnp.int32),          # reason
        jnp.asarray(0, jnp.int32),                         # it (loop)
        values, gnorms,
        jnp.full((e,), 2, jnp.int32),                      # passes
        jnp.zeros((e,), jnp.int32),                        # per-lane steps
    )

    def cond(st):
        _, _, _, _, reason, it, *_ = st
        return jnp.any(reason == NOT_CONVERGED) & (it < max_it)

    def body(st):
        x, z, f, g, reason, it, values, gnorms, passes, iters = st
        active = reason == NOT_CONVERGED

        with jax.named_scope("newton.hessian"):
            h = hess_at(x, z)
            scale = 1.0 + jax.vmap(jnp.trace)(h) / t_dim
            h_damped = h + (ridge * scale)[:, None, None] * eye
        # The damped Hessian is symmetric PD by construction, so Cholesky,
        # with the entities on the lane axis (module doc). Under
        # --debug-nans take LU instead: a lane whose Hessian lost PD to
        # rounding makes Cholesky EMIT NaN by design (caught by the
        # fallback below), which debug_nans would escalate to
        # FloatingPointError on an otherwise healthy run — LU returns a
        # finite non-descent direction the same guard handles. Trace-time
        # read: the flag is process-static.
        with jax.named_scope("newton.solve"):
            if solve_form() == "lu":
                d = -jnp.linalg.solve(h_damped, g[..., None])[..., 0]
            else:
                d = -_lane_cholesky_solve(h_damped, g)
        dg = jnp.sum(d * g, axis=1)
        # H is PD(+ridge) so d is descent; a numerically non-descent lane —
        # including a failed factorization (NaN Cholesky of a lane whose
        # Hessian lost PD to rounding) — falls back to steepest descent
        # (mirrors the L-BFGS restart rule).
        bad = (dg >= 0.0) | ~jnp.isfinite(dg)
        d = jnp.where(bad[:, None], -g, d)
        dg = jnp.where(bad, -jnp.sum(g * g, axis=1), dg)

        with jax.named_scope("newton.line_search"):
            zd = lin_map(d)                                    # [E, S]
            ft = probe_values(x, z, d, zd, ts)                 # [L, E]
            armijo = jnp.isfinite(ft) & (ft <= f[None] + c1 * ts[:, None]
                                         * dg[None])
            any_ok = jnp.any(armijo, axis=0)
            first = jnp.argmax(armijo, axis=0)                 # largest t
            # No probe passes: smallest step that still decreases f (same
            # terminal fallback as the streamed L-BFGS), else freeze the
            # lane.
            last = ft[-1]
            salvage = (~any_ok) & jnp.isfinite(last) & (last < f)
            t_pick = jnp.where(any_ok, ts[first],
                               jnp.where(salvage, ts[-1], 0.0))
        stepped = active & (t_pick > 0.0)

        x_new = jnp.where(stepped[:, None], x + t_pick[:, None] * d, x)
        z_new = jnp.where(stepped[:, None], z + t_pick[:, None] * zd, z)
        fs = value_at(x_new, z_new)
        gs = grad_at(x_new, z_new)
        f_new = jnp.where(stepped, fs, f)
        g_new = jnp.where(stepped[:, None], gs, g)

        it = it + 1
        gn = jnp.linalg.norm(g_new, axis=1)
        conv = check_convergence(it, f, f_new, gn, gnorm0, cfg)
        reason_new = jnp.where(
            active,
            jnp.where(stepped, conv,
                      jnp.asarray(FUNCTION_VALUES_CONVERGED, jnp.int32)),
            reason,
        )
        values = values.at[:, it].set(jnp.where(stepped, f_new, jnp.inf))
        gnorms = gnorms.at[:, it].set(jnp.where(stepped, gn, jnp.inf))
        # Hessian+grad assembly ≈ 2 data-equivalent passes, the probe
        # batch 1 — instrumented like the other solvers' pass counters.
        passes = passes + jnp.where(active, 3, 0).astype(jnp.int32)
        return (x_new, z_new, f_new, g_new, reason_new, it, values,
                gnorms, passes, iters + stepped.astype(jnp.int32))

    out = jax.lax.while_loop(cond, body, state)
    (x, z, f, g, reason, it, values, gnorms, passes, iters) = out
    return (x, z, f, g, finalize_reason(reason, it, cfg.max_iterations),
            it, values, gnorms, passes, iters)


@partial(jax.jit, static_argnums=0)
def fit_bucket_newton(problem, batches, w0, local_mask, local_prior):
    """Primal damped-Newton solve of every entity in one bucket (module
    doc). Same inputs as ``_fit_bucket_jitted`` (minus normalization, which
    the eligibility gate excludes) and the same ``(models, result)`` pytree
    shapes out, so ``train_random_effects`` can swap it in per bucket."""
    from photon_tpu.functions.problem import VarianceComputationType
    from photon_tpu.obs import retrace

    retrace.note_trace("fit_bucket_newton")  # 1 trace == 1 XLA compile

    # Solve in the data/warm-start precision: f64 RE configs must not
    # silently drop to f32 on the default fast path (ADVICE r5).
    dt = w0.dtype
    loss = loss_for_task(problem.task)
    x_ext, y, off, tw = _dense_design(batches, dt)
    # Contiguous copy of the ghost-stripped design: the batched GEMMs below
    # need a materialized operand, not a strided slice fused per-element.
    x = jax.lax.optimization_barrier(x_ext[..., : batches.features.dim])
    xt = jnp.swapaxes(x, 1, 2)                              # [E, P, S]
    l2v, pm, pp, _ = penalty_terms(problem, local_mask, local_prior, dt)

    def value_at(w, z):
        return (
            jnp.sum(tw * loss.loss(z, y), axis=1)
            + 0.5 * jnp.sum(l2v * w * w, axis=1)
            + 0.5 * jnp.sum(pp * (w - pm) ** 2, axis=1)
        )

    def grad_at(w, z):
        d1 = tw * loss.d1(z, y)
        return (jnp.matmul(d1[:, None, :], x)[:, 0]
                + l2v * w + pp * (w - pm))

    def hess_at(w, z):
        d2 = tw * loss.d2(z, y)
        # Xᵀ diag(d2) X as one batched GEMM over a materialized weighted
        # design (barrier: keep XLA from re-fusing the scale into the dot).
        xw = jax.lax.optimization_barrier(x * d2[..., None])
        h = jnp.matmul(xt, xw)
        return h + jax.vmap(jnp.diag)(l2v + pp)

    def lin_map(d):
        return jnp.matmul(x, d[..., None])[..., 0]

    def probe_values(w, z, d, zd, ts):
        zt = z[None] + ts[:, None, None] * zd[None]            # [L, E, S]
        wt = w[None] + ts[:, None, None] * d[None]             # [L, E, P]
        return (
            jnp.sum(tw[None] * loss.loss(zt, y[None]), axis=2)
            + 0.5 * jnp.sum(l2v[None] * wt * wt, axis=2)
            + 0.5 * jnp.sum(pp[None] * (wt - pm[None]) ** 2, axis=2)
        )

    w = w0.astype(dt)
    z = off + lin_map(w)
    (w, z, f, g, reason, _, values, gnorms, passes, iters) = _newton_loop(
        w, z, problem.optimizer_config, value_at, grad_at, hess_at,
        lin_map, probe_values, ridge=1e-8,
    )

    variances = None
    if problem.variance_type != VarianceComputationType.NONE:
        # Same formulas as GLMOptimizationProblem._variances, from the
        # final Hessian this solver already assembles: SIMPLE = 1/diag H,
        # FULL = diag H⁻¹ (H includes the L2 term and prior precision).
        h = hess_at(w, z)
        if problem.variance_type == VarianceComputationType.SIMPLE:
            diag = jax.vmap(jnp.diag)(h)
            variances = 1.0 / jnp.maximum(diag, 1e-12)
        else:
            eye = jnp.eye(w.shape[1], dtype=dt)
            hinv = jnp.linalg.inv(h + 1e-12 * eye)
            variances = jax.vmap(jnp.diag)(hinv)
        variances = variances.astype(w0.dtype)

    result = OptimizerResult(
        x=w.astype(w0.dtype),
        value=f,
        grad_norm=jnp.linalg.norm(g, axis=1),
        iterations=iters,  # accepted steps per lane, like the vmapped path
        converged_reason=reason,
        values=values,
        grad_norms=gnorms,
        data_passes=passes,
    )
    model = GeneralizedLinearModel(
        Coefficients(means=w.astype(w0.dtype), variances=variances),
        problem.task,
    )
    return model, result


@partial(jax.jit, static_argnums=(0, 5))
def fit_bucket_newton_dual(problem, batches, w0, local_mask, local_prior,
                           u_max: int):
    """Span-reduced Newton solve of every entity in one bucket (module doc).

    Same ``(models, result)`` pytree shapes as ``_fit_bucket_jitted``.
    ``w0`` is intentionally unused: an arbitrary warm start is outside the
    span parametrization, and quadratic convergence from θ=0 costs at most
    a couple of extra iterations — the trade for a history-free solver.
    """
    from photon_tpu.functions.problem import VarianceComputationType
    from photon_tpu.obs import retrace

    retrace.note_trace("fit_bucket_newton_dual")  # 1 trace == 1 XLA compile

    # Same dtype contract as the primal path: solve in w0.dtype so f64
    # datasets keep full precision (ADVICE r5). w0's VALUES stay unused
    # (module doc); only its dtype steers the compute precision.
    dt = w0.dtype
    loss = loss_for_task(problem.task)
    x_ext, y, off, tw = _dense_design(batches, dt)
    e, s, _ = x_ext.shape
    p = batches.features.dim
    # Contiguous ghost-stripped design for the batched GEMMs (module doc).
    x = jax.lax.optimization_barrier(x_ext[..., :p])

    _, pm, pp, d_pen = penalty_terms(problem, local_mask, local_prior, dt)
    d_pinv = jnp.where(d_pen > 0.0, 1.0 / jnp.maximum(d_pen, 1e-30), 0.0)
    q = pp * pm                                            # [E, P]

    # Unpenalized columns (d_pen == 0, typically the reg-masked intercept):
    # top-u_max indices per entity, ghost-padded with column P (zero in
    # x_ext, so an absent slot is inert).
    if u_max > 0:
        zero_d = d_pen <= 0.0                              # [E, P]
        # argsort puts False (penalized) last; take the first u_max true.
        order = jnp.argsort(~zero_d, axis=1, stable=True)[:, :u_max]
        have = jnp.take_along_axis(zero_d, order, axis=1)
        u_idx = jnp.where(have, order, p)                  # ghost when absent
        x_u = jnp.take_along_axis(
            x_ext, u_idx[:, None, :].repeat(s, axis=1), axis=2
        )                                                  # [E, S, U]
    else:
        u_idx = jnp.zeros((e, 0), jnp.int32)
        x_u = jnp.zeros((e, s, 0), dt)

    xd = jax.lax.optimization_barrier(
        x * d_pinv[:, None, :]                             # X·D⁺  [E,S,P]
    )
    gram = jnp.matmul(xd, jnp.swapaxes(x, 1, 2))           # G = XD⁺Xᵀ [E,S,S]
    j_mat = jax.lax.optimization_barrier(
        jnp.concatenate([gram, x_u], axis=2)               # [E, S, T]
    )
    j_t = jnp.swapaxes(j_mat, 1, 2)                        # [E, T, S]
    if local_prior is None:
        # q ≡ 0: the θ=0 margins are just the offsets and the primal
        # regularization constant vanishes — skip two [E,S,P] matvecs.
        z0 = off
        c_reg = jnp.zeros((e,), dt)
    else:
        z0 = off + jnp.matmul(xd, q[..., None])[..., 0]    # margins at θ=0
        # Primal-objective constant: reg(w(θ)) = ½αᵀGα + c_reg (module doc).
        c_reg = 0.5 * jnp.sum(pp * pm * pm, axis=1) - 0.5 * jnp.sum(
            d_pinv * q * q, axis=1
        )

    def ga_of(alpha):
        return jnp.einsum("est,...et->...es", gram, alpha)

    def value_at(theta, z):
        alpha = theta[:, :s]
        return (jnp.sum(tw * loss.loss(z, y), axis=1)
                + 0.5 * jnp.sum(alpha * ga_of(alpha), axis=1) + c_reg)

    def grad_at(theta, z):
        d1 = tw * loss.d1(z, y)
        g = jnp.matmul(d1[:, None, :], j_mat)[:, 0]
        return g.at[:, :s].add(ga_of(theta[:, :s]))

    def hess_at(theta, z):
        d2 = tw * loss.d2(z, y)
        # Jᵀ diag(d2) J as one batched GEMM (barrier: module doc).
        jw = jax.lax.optimization_barrier(j_mat * d2[..., None])
        h = jnp.matmul(j_t, jw)
        return h.at[:, :s, :s].add(gram)

    def lin_map(d):
        return jnp.matmul(j_mat, d[..., None])[..., 0]

    def probe_values(theta, z, d, zd, ts):
        zt = z[None] + ts[:, None, None] * zd[None]          # [L, E, S]
        alpha_t = theta[None, :, :s] + ts[:, None, None] * d[None, :, :s]
        return (jnp.sum(tw[None] * loss.loss(zt, y[None]), axis=2)
                + 0.5 * jnp.sum(alpha_t * ga_of(alpha_t), axis=2)
                + c_reg[None])

    theta0 = jnp.zeros((e, s + u_max), dt)
    (theta, z, f, g, reason, _, values, gnorms, passes,
     iters) = _newton_loop(
        theta0, z0, problem.optimizer_config, value_at, grad_at, hess_at,
        # The G-induced curvature can be singular along directions outside
        # the row span (α nullspace — w(θ) is unaffected there), so a
        # slightly larger ridge both damps and selects the min-norm step.
        lin_map, probe_values, ridge=1e-7,
    )

    # Recover primal coefficients: w = D⁺(Xᵀα + q) + scatter(β at u_idx).
    alpha, beta = theta[:, :s], theta[:, s:]
    w = d_pinv * (jnp.matmul(alpha[:, None, :], x)[:, 0] + q)
    if u_max > 0:
        w_full = jnp.concatenate([w, jnp.zeros((e, 1), dt)], axis=1)
        w_full = w_full.at[jnp.arange(e)[:, None], u_idx].add(beta)
        w = w_full[:, :p]

    # Primal gradient norm for the reported result (θ-space norms steer
    # the loop; the artifact-facing number matches the other solvers).
    z_w = off + jnp.matmul(x, w[..., None])[..., 0]
    d1 = tw * loss.d1(z_w, y)
    g_primal = jnp.matmul(d1[:, None, :], x)[:, 0] + d_pen * w - q

    variances = None
    if problem.variance_type == VarianceComputationType.SIMPLE:
        d2 = tw * loss.d2(z_w, y)
        diag = jnp.einsum("es,esp->ep", d2, x * x) + d_pen
        variances = (1.0 / jnp.maximum(diag, 1e-12)).astype(w0.dtype)

    result = OptimizerResult(
        x=w.astype(w0.dtype),
        value=f,
        grad_norm=jnp.linalg.norm(g_primal, axis=1),
        iterations=iters,
        converged_reason=reason,
        values=values,
        grad_norms=gnorms,
        data_passes=passes,
    )
    model = GeneralizedLinearModel(
        Coefficients(means=w.astype(w0.dtype), variances=variances),
        problem.task,
    )
    return model, result


# ------------------------------------------------------- entity sub-batching


def _slice_pad_batches(batches, lo: int, hi: int, chunk: int):
    """``batches[lo:hi]`` padded on the entity axis to exactly ``chunk``
    lanes. Padding lanes are inert by the same convention as
    ``_pad_bucket``: ghost feature columns (== local dim, dropped by the
    dense scatter), value/label/offset 0, weight 0."""
    from photon_tpu.data.batch import LabeledBatch, SparseFeatures

    f = batches.features

    def pz(a, fill=0):
        return _slice_pad_lanes(a, lo, hi, chunk, fill)

    return LabeledBatch(
        features=SparseFeatures(idx=pz(f.idx, f.dim), val=pz(f.val),
                                dim=f.dim),
        labels=pz(batches.labels),
        offsets=pz(batches.offsets),
        weights=pz(batches.weights),
    )


def _slice_pad_lanes(a, lo: int, hi: int, chunk: int, fill=0):
    """One [E, ...] per-entity leaf sliced and padded to ``chunk`` lanes.

    Host numpy leaves stay HOST numpy (np.pad, not jnp.pad): under a mesh
    the per-chunk placement device_puts each chunk row-sharded, and a host
    source streams each shard straight to its device — a jnp.pad here
    would first commit the chunk to the default device and pay the
    transfer twice."""
    a = a[lo:hi]
    pad = chunk - (hi - lo)
    if pad:
        widths = [(0, pad)] + [(0, 0)] * (a.ndim - 1)
        if isinstance(a, np.ndarray):
            return np.pad(a, widths, constant_values=fill)
        a = jnp.pad(a, widths, constant_values=fill)
    return a


def fit_bucket_in_chunks(fit_one, chunk: int, batches, w0, local_mask,
                         local_prior, put=None, ahead: int = 0):
    """Solve one bucket in entity chunks of a blessed size and restack.

    ``fit_one(batches, w0, local_mask, local_prior) -> (model, result)`` is
    a closure over the solver + its static arguments (problem, u_max, ...).
    Every chunk — including the padded tail — has EXACTLY ``chunk`` lanes,
    so the underlying jitted kernel compiles once per ladder size and the
    retrace sentinel stays quiet across sweeps. Padded lanes carry weight-0
    rows, mask 1 (so the ridge keeps their Hessians PD), and precision-0
    priors; they converge at the zero model on the first iteration and are
    sliced away before the restack.

    ``put`` (optional) places each chunk's argument pytree before dispatch
    — under a mesh it is the entity-sharded ``device_put`` that fans every
    chunk out across the devices (each device owns ``chunk/n_devices``
    lanes of EVERY chunk, so all devices work on every dispatch). With
    ``ahead > 0`` the placements run through ``pipelined_puts`` so chunk
    N+1's per-shard H2D is issued before chunk N's solve dispatches —
    the RE-side analogue of the out-of-core ``ell_feed`` double buffer.
    """
    e = w0.shape[0]
    spans = [(lo, min(lo + chunk, e)) for lo in range(0, e, chunk)]

    def args_for(span):
        lo, hi = span
        sl_prior = (
            jax.tree.map(lambda a: _slice_pad_lanes(a, lo, hi, chunk),
                         local_prior)
            if local_prior is not None else None
        )
        args = (
            _slice_pad_batches(batches, lo, hi, chunk),
            _slice_pad_lanes(w0, lo, hi, chunk),
            _slice_pad_lanes(local_mask, lo, hi, chunk, fill=1),
            sl_prior,
        )
        return args if put is None else put(args)

    if put is not None and ahead > 0 and len(spans) > 1:
        from photon_tpu.io.prefetch import pipelined_puts

        feed = pipelined_puts(spans, args_for, ahead=ahead)
    else:
        feed = (args_for(s) for s in spans)

    outs = []
    for (lo, hi), args in zip(spans, feed):
        model, result = fit_one(*args)
        n = hi - lo
        outs.append(jax.tree.map(lambda a: a[:n], (model, result)))
    if len(outs) == 1:
        return outs[0]
    return jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *outs)
