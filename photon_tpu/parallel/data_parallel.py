"""Data-parallel fixed-effect training over a device mesh.

Parity: reference ⟦DistributedOptimizationProblem + DistributedGLMLossFunction⟧
— the Spark path where every L-BFGS iteration broadcasts coefficients and
``treeAggregate``s (loss, gradient) partials back to the driver (SURVEY.md
§3.4, the reference's scalability bottleneck).

TPU-native replacement (SURVEY.md §2.6 P1): the batch lives row-sharded over
the ``data`` mesh axis; coefficients are replicated. Two equivalent
implementations are provided:

1. ``fit_data_parallel`` — GSPMD: jit with explicit in/out shardings; XLA
   partitions the whole optimizer loop and inserts a single fused AllReduce
   over ICI for the row-sum in each value/grad evaluation. The entire
   multi-iteration solve is ONE XLA program — zero host round trips.

2. ``spmd_value_and_grad`` — explicit ``shard_map`` + ``psum``: per-device
   partial (loss, grad) reduced with one collective. Useful when manual
   control of the collective placement is needed (multi-slice DCN meshes)
   and as an executable spec of what (1) compiles to.

Both are verified equal to the single-device solve in tests/test_distributed.py
on an 8-device mesh (the reference's `local[*]` equivalent).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P
from photon_tpu.parallel.mesh import shard_map

from photon_tpu.data.batch import LabeledBatch
from photon_tpu.functions.objective import GLMObjective
from photon_tpu.functions.problem import GLMOptimizationProblem
from photon_tpu.parallel.mesh import (
    DATA_AXIS,
    axes_size,
    axis_tuple,
    replicated,
    shard_batch_pytree,
)

Array = jax.Array


def fit_data_parallel(
    problem: GLMOptimizationProblem,
    batch: LabeledBatch,
    w0: Array,
    mesh,
    data_axis: str = DATA_AXIS,
    normalization=None,
):
    """Run the full solve with the batch row-sharded over ``data_axis``.

    ``data_axis`` may be one mesh axis or a tuple — pass ``("dcn", "data")``
    on a 2-level multi-slice mesh (``make_multislice_mesh``) to shard rows
    over slices × chips; XLA lowers the gradient AllReduce hierarchically
    (ICI within each slice, DCN across slices — SURVEY.md §5.8).

    Row counts that don't divide the axis size are padded with weight-0 rows
    (padding is invisible to the objective — SURVEY.md batch semantics).
    Returns (GeneralizedLinearModel, OptimizerResult), both replicated.
    """
    from photon_tpu.parallel.mesh import (
        note_sharded_bytes,
        pad_and_shard_batch,
    )

    batch = pad_and_shard_batch(batch, mesh, data_axis)
    note_sharded_bytes("fixed_effect_features", batch.features)
    rep = replicated(mesh)
    w0 = jax.device_put(w0, rep)
    # Array-valued reg_mask / prior / normalization can't be part of the
    # static jit key; pass them dynamically (same convention as
    # GLMOptimizationProblem.fit).
    mask, prior = problem.reg_mask, problem.prior
    key = (
        dataclasses.replace(problem, reg_mask=None, prior=None)
        if (mask is not None or prior is not None)
        else problem
    )
    return _fit_dp_jitted(key, rep, batch, w0, mask, prior, normalization)


@partial(jax.jit, static_argnums=(0, 1))
def _fit_dp_jitted(problem, out_sharding, batch, w0, reg_mask, prior, normalization):
    # out_sharding (a NamedSharding: hashable) is applied via lax constraint
    # so the whole (problem, sharding) pair stays one cached executable.
    model, result = problem.run(batch, w0, reg_mask, normalization, prior)
    return jax.tree.map(
        lambda a: jax.lax.with_sharding_constraint(a, out_sharding),
        (model, result),
    )


def spmd_value_and_grad(
    obj: GLMObjective,
    batch: LabeledBatch,
    mesh,
    data_axis: str = DATA_AXIS,
):
    """Explicit-collective objective: w ↦ psum over shards of (value, grad).

    The returned closure can be handed straight to any Optimizer — the psum
    rides ICI inside whatever jit the optimizer loop compiles into. The L2
    term is added once globally (outside the psum), not once per shard.
    ``data_axis`` may be a tuple (multi-slice: the psum over
    ``("dcn", "data")`` is the hierarchical treeAggregate replacement).
    """
    from photon_tpu.parallel.mesh import strip_unshardable_aux

    axes = axis_tuple(data_axis)
    data_obj = GLMObjective(loss=obj.loss, l2_weight=0.0, reg_mask=None)
    batch = strip_unshardable_aux(batch)
    batch_specs = jax.tree.map(
        lambda leaf: P(axes, *([None] * (leaf.ndim - 1))), batch
    )

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(), batch_specs),
        out_specs=(P(), P()),
    )
    def _vg(w, local_batch):
        v, g = data_obj.value_and_grad(w, local_batch)
        return lax.psum(v, axes), lax.psum(g, axes)

    sharded = shard_batch_pytree(batch, mesh, data_axis)

    def vg(w):
        import jax.numpy as jnp

        v, g = _vg(w, sharded)
        lam = obj._l2_vec(w)
        v = v + 0.5 * jnp.sum(lam * w * w)
        g = g + lam * w
        return v, g

    return vg
