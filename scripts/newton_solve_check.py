"""The lane Cholesky of ``game/newton_re.py`` beside the batched library
call it replaced (kept here as the reference): both forms of the Newton
solve ``h d = g`` at given [E,T,T] shapes (PERF.md §6, PR 34).

    python scripts/newton_solve_check.py <mode> E,T [E,T ...]

``compile``: compile for a described v5e, no chip needed (seconds,
temporaries, whether the program holds the ``Cholesky`` custom call).
``run``: on the chip, compile and run (median of 7), each form's gap to a
NumPy float64 solve, and whether a lane that is not positive definite
comes back NaN in that lane alone. The systems are shaped like the cells':
about two thirds real columns, the rest padded by identity.
``rebatch``: on the chip, whether a lane's float32 answer depends on its
place in the batch: the lane solve, and a whole ``fit_bucket_newton`` fit
of E users x 2 T rows in T columns under either form, whole against entity
chunks of 256.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from photon_tpu.game import newton_re


def library_cholesky_solve(h, b):
    """``h @ x = b`` a lane by the batched library calls on [E,T,T]."""
    chol = jnp.linalg.cholesky(h)
    return jax.scipy.linalg.cho_solve((chol, True), b[..., None])[..., 0]


FORMS = {"lanes": newton_re._lane_cholesky_solve,
         "library": library_cholesky_solve}
REBATCH_CHUNK = 256


def out(**kw):
    print(json.dumps(kw), flush=True)


def systems(rng, e, t):
    """[E,T,T] Hessians of a logistic problem over ``real`` columns and 4 T
    rows, identity on the padded columns, and a right-hand side that is
    zero there."""
    real = max(1, (2 * t) // 3)
    x = rng.standard_normal((e, 4 * t, real)).astype(np.float32)
    d2 = rng.uniform(0.05, 0.25, (e, 4 * t, 1)).astype(np.float32)
    h = np.zeros((e, t, t), np.float32)
    h[:, :real, :real] = np.swapaxes(x, 1, 2) @ (x * d2)
    h += np.eye(t, dtype=np.float32)
    b = np.zeros((e, t), np.float32)
    b[:, :real] = rng.standard_normal((e, real)).astype(np.float32)
    return h, b


def main(mode, shapes):
    if mode == "compile":
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        jax.config.update("jax_enable_compilation_cache", False)
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        sh = SingleDeviceSharding(topo.devices[0])
        for e, t in shapes:
            for name, f in FORMS.items():
                t0 = time.perf_counter()
                c = jax.jit(f).lower(
                    jax.ShapeDtypeStruct((e, t, t), jnp.float32, sharding=sh),
                    jax.ShapeDtypeStruct((e, t), jnp.float32, sharding=sh),
                ).compile()
                out(mode=mode, e=e, t=t, form=name,
                    compile_s=round(time.perf_counter() - t0, 2),
                    temp_gb=c.memory_analysis().temp_size_in_bytes / 1e9,
                    cholesky_call="Cholesky" in c.as_text())
        return
    if jax.devices()[0].platform != "tpu":
        sys.exit(f"{mode}: a chip reading, and this is "
                 f"{jax.devices()[0].platform}")
    out(device=jax.devices()[0].device_kind)
    rng = np.random.default_rng(0)
    if mode == "rebatch":
        for e, t in shapes:
            rebatch(rng, e, t)
        return
    for e, t in shapes:
        h, b = systems(rng, e, t)
        want = np.linalg.solve(h.astype(np.float64),
                               b.astype(np.float64)[..., None])[..., 0]
        bad = h.copy()
        bad[e // 2] = -bad[e // 2]
        hd, bd, badd = jnp.asarray(h), jnp.asarray(b), jnp.asarray(bad)
        for name, f in FORMS.items():
            t0 = time.perf_counter()
            c = jax.jit(f).lower(hd, bd).compile()
            row = dict(e=e, t=t, form=name,
                       compile_s=round(time.perf_counter() - t0, 2),
                       temp_gb=c.memory_analysis().temp_size_in_bytes / 1e9)
            got = np.asarray(c(hd, bd))
            ts = []
            for _ in range(7):
                t0 = time.perf_counter()
                c(hd, bd).block_until_ready()
                ts.append(time.perf_counter() - t0)
            row["run_ms"] = round(1e3 * float(np.median(ts)), 3)
            row["gap_to_float64"] = float(
                np.max(np.abs(got - want)) / np.max(np.abs(want)))
            nan_lanes = np.flatnonzero(
                np.isnan(np.asarray(c(badd, bd))).any(axis=1))
            row["nan_lanes_are_the_bad_one"] = nan_lanes.tolist() == [e // 2]
            out(**row)


def rebatch(rng, e, t):
    from photon_tpu.data.batch import LabeledBatch, SparseFeatures
    from photon_tpu.functions.problem import GLMOptimizationProblem
    from photon_tpu.optim import (
        OptimizerConfig,
        RegularizationContext,
        RegularizationType,
    )
    from photon_tpu.types import TaskType

    def differing(whole, chunked):
        gap = np.abs(np.asarray(whole) - np.asarray(chunked))
        return dict(lanes_differing=int((gap.max(axis=1) > 0).sum()),
                    max_gap=float(gap.max()))

    c = REBATCH_CHUNK
    h, b = (jnp.asarray(a) for a in systems(rng, e, t))
    solve = jax.jit(newton_re._lane_cholesky_solve)
    out(e=e, t=t, what="lane_solve", chunk=c, **differing(
        solve(h, b),
        np.concatenate([np.asarray(solve(h[lo:lo + c], b[lo:lo + c]))
                        for lo in range(0, e, c)])))

    s, k = 2 * t, 3
    f32 = np.float32
    x = rng.standard_normal((e, s, k)).astype(f32)
    batches = LabeledBatch(
        features=SparseFeatures(
            idx=jnp.asarray(rng.integers(0, t, (e, s, k)), jnp.int32),
            val=jnp.asarray(x), dim=t),
        labels=jnp.asarray(rng.random((e, s)) < 0.5, f32),
        offsets=jnp.zeros((e, s), f32), weights=jnp.ones((e, s), f32))
    problem = GLMOptimizationProblem(
        task=TaskType.LOGISTIC_REGRESSION,
        optimizer_config=OptimizerConfig(),
        regularization=RegularizationContext(RegularizationType.L2),
        reg_weight=1.0)

    # Under both forms, so that what moves with the batch can be put down
    # to the solve or to the rest of the Newton program. A new function a
    # form (or jit answers from the first one's trace): the loop looks the
    # solve up while tracing.
    w0, mask = jnp.zeros((e, t), f32), jnp.ones((e, t), f32)
    lanes = newton_re._lane_cholesky_solve
    for name, f in FORMS.items():
        newton_re._lane_cholesky_solve = f
        fit = jax.jit(
            lambda *a: newton_re.fit_bucket_newton.__wrapped__(*a),
            static_argnums=0)

        def fit_one(bb, w, m, pr):
            return fit(problem, bb, w, m, pr)

        whole, whole_r = fit_one(batches, w0, mask, None)
        chunked, chunked_r = newton_re.fit_bucket_in_chunks(
            fit_one, c, batches, w0, mask, None)
        out(e=e, t=t, what="fit_bucket_newton", form=name, chunk=c,
            iterations_differing=int(np.sum(
                np.asarray(whole_r.iterations)
                != np.asarray(chunked_r.iterations))),
            **differing(whole.coefficients.means, chunked.coefficients.means))
    newton_re._lane_cholesky_solve = lanes


if __name__ == "__main__":
    main(sys.argv[1],
         [tuple(int(x) for x in a.split(",")) for a in sys.argv[2:]])
