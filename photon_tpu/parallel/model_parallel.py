"""Feature-dimension-sharded training: 2D (data × model) mesh L-BFGS.

Parity/North-star: SURVEY.md §2.6 P3 — the reference broadcasts the whole
coefficient vector every iteration and holds it on the driver; at 10M
features that is the scalability wall. Here the coefficient vector, gradient,
and the L-BFGS S/Y history live SHARDED over the ``model`` mesh axis while
batch rows shard over the ``data`` axis:

* margins: each model shard computes the partial zᵢ from its own feature
  columns; one ``psum`` over the model axis completes z (communication is
  O(rows_per_device), NOT O(D) — no all-gather of coefficients, ever);
* loss/value: summed over the data axis with a second ``psum``;
* gradient: each model shard scatter-accumulates only its own columns, then
  psums over the data axis — gradient shards never leave their device;
* two-loop recursion: every coefficient-space inner product is a local dot +
  scalar ``psum`` over the model axis (``LBFGS(axis_name=...)``).

The whole multi-iteration solve is ONE ``shard_map``-ped XLA program on the
mesh — zero host round trips, optimizer state O(D / n_model_shards) per
device.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P
from photon_tpu.parallel.mesh import shard_map

from photon_tpu.data.batch import DenseFeatures, LabeledBatch, SparseFeatures
from photon_tpu.functions.problem import GLMOptimizationProblem
from photon_tpu.models.coefficients import Coefficients
from photon_tpu.models.glm import GeneralizedLinearModel
from photon_tpu.optim import LBFGS, OWLQN, TRON, OptimizerType
from photon_tpu.ops.losses import loss_for_task
from photon_tpu.parallel.mesh import axes_size, axis_tuple, pad_rows_to_multiple

Array = jax.Array

DATA_AXIS = "data"
MODEL_AXIS = "model"


def _pad_dim_sparse(feats: SparseFeatures, new_dim: int) -> SparseFeatures:
    # Ghost column moves from dim to new_dim; remap ghost entries.
    idx = jnp.where(feats.idx >= feats.dim, new_dim, feats.idx)
    return SparseFeatures(idx=idx, val=feats.val, dim=new_dim)


def fit_model_parallel(
    problem: GLMOptimizationProblem,
    batch: LabeledBatch,
    w0: Array,
    mesh,
    data_axis: str = DATA_AXIS,
    model_axis: str = MODEL_AXIS,
    normalization=None,
):
    """Full solve with coefficients sharded over ``model_axis`` and rows over
    ``data_axis`` (one axis or a tuple — e.g. ``("dcn", "data")``). Returns
    (GeneralizedLinearModel, OptimizerResult) with full-length
    (host-assembled) coefficients.

    Supports L-BFGS, OWL-QN, and TRON (orthant/CG vector ops are elementwise
    → shard-local; inner products psum over the model axis, and TRON's
    Hessian-vector product composes the same margins-psum + shard-local
    transpose as the gradient), NONE/SIMPLE variances (SIMPLE's Hessian
    diagonal is computed per feature shard), and normalization contexts (the
    coefficient-space map's shift correction is one scalar psum over the
    model axis; SURVEY.md §7 hard-part #5). FULL variance uses the
    data-parallel path: a D×D inverse doesn't fit the sharded-state design.
    """
    # Guards a future OptimizerType addition from silently training with the
    # wrong solver; every CURRENT member is supported.
    if problem.optimizer_type not in (
        OptimizerType.LBFGS, OptimizerType.OWLQN, OptimizerType.TRON
    ):
        raise ValueError(
            "model-parallel training supports LBFGS, OWLQN, and TRON "
            f"(got {problem.optimizer_type.name})"
        )
    if problem.variance_type.name == "FULL":
        raise ValueError(
            "model-parallel training computes NONE/SIMPLE variances only "
            "(FULL materializes a DxD Hessian)"
        )
    if normalization is not None and normalization.is_identity:
        normalization = None
    if normalization is not None and problem.prior is not None:
        raise ValueError(
            "model-parallel training does not combine a normalization "
            "context with an incremental-training prior"
        )

    data_axes = axis_tuple(data_axis)
    n_data = axes_size(mesh, data_axes)
    n_model = mesh.shape[model_axis]
    d = batch.dim
    d_pad = -d % n_model
    d_full = d + d_pad

    if batch.n_rows % n_data:
        batch = pad_rows_to_multiple(batch, n_data)
    feats = batch.features
    if isinstance(feats, SparseFeatures):
        feats = _pad_dim_sparse(feats, d_full)
        feats_specs = SparseFeatures(
            idx=P(data_axes, None), val=P(data_axes, None), dim=feats.dim
        )
    elif isinstance(feats, DenseFeatures):
        if d_pad:
            feats = DenseFeatures(jnp.pad(feats.x, ((0, 0), (0, d_pad))))
        feats_specs = DenseFeatures(x=P(data_axes, model_axis))
    else:  # pragma: no cover - union is closed
        raise TypeError(f"unknown feature container {type(feats)}")
    batch = dataclasses.replace(batch, features=feats)

    w0 = jnp.pad(w0, (0, d_pad))
    lam_mask = problem.reg_mask
    if lam_mask is not None:
        lam_mask = jnp.pad(lam_mask.astype(w0.dtype), (0, d_pad))
    else:
        # padding columns must carry 0 penalty? They stay at 0 anyway (no
        # data touches them); keep 1 to preserve SPD behavior.
        lam_mask = jnp.pad(jnp.ones((d,), w0.dtype), (0, d_pad), constant_values=1.0)

    shard_d = d_full // n_model
    l2 = problem.regularization.l2_weight(problem.reg_weight)
    l1 = problem.regularization.l1_weight(problem.reg_weight)
    if l1 > 0.0 and problem.optimizer_type != OptimizerType.OWLQN:
        # Reference parity (same guard as GLMOptimizationProblem.run): L1 is
        # only handled by OWL-QN; silently training unregularized is worse.
        raise ValueError(
            f"{problem.regularization.reg_type.name} regularization requires "
            f"OptimizerType.OWLQN, got {problem.optimizer_type.name}"
        )
    loss = loss_for_task(problem.task)
    prior = problem.prior
    if prior is not None:
        prior = jax.tree.map(lambda a: jnp.pad(a, (0, d_pad)), prior)

    # Normalization arrays, sanitized (intercept slot forced to factor 1 /
    # shift 0) and padded to the sharded width. Padding columns get factor 1
    # so the map stays invertible there (they carry zero data and zero w).
    norm_f = norm_s = norm_onehot = None
    if normalization is not None:
        nf, ns = normalization._effective()
        if nf is not None:
            norm_f = jnp.pad(nf.astype(w0.dtype), (0, d_pad), constant_values=1.0)
        if ns is not None:
            norm_s = jnp.pad(ns.astype(w0.dtype), (0, d_pad))
            norm_onehot = (
                jnp.zeros((d_full,), w0.dtype)
                .at[normalization.intercept_index]
                .set(1.0)
            )

    row_specs = P(data_axes)
    batch_specs = LabeledBatch(
        features=feats_specs, labels=row_specs, offsets=row_specs,
        weights=row_specs,
    )
    key = dataclasses.replace(problem, reg_mask=None, prior=None)

    from photon_tpu.optim.base import OptimizerResult

    res_specs = OptimizerResult(
        x=P(), value=P(), grad_norm=P(), iterations=P(),
        converged_reason=P(), values=P(), grad_norms=P(), data_passes=P(),
    )
    if problem.optimizer_type == OptimizerType.TRON:
        # TRON's own counters (None on the other optimizers' results).
        res_specs = dataclasses.replace(
            res_specs, hvp=P(), cg_steps=P(), rejected=P())

    norm_arrays = (norm_f, norm_s, norm_onehot)

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            P(model_axis),
            batch_specs,
            P(model_axis),
            jax.tree.map(lambda _: P(model_axis), prior),
            jax.tree.map(lambda _: P(model_axis), norm_arrays),
        ),
        out_specs=((P(model_axis), P(model_axis)), res_specs),
        check_vma=False,
    )
    def solve(w_shard, local_batch, lam_shard, prior_shard, norm_shards):
        lf = local_batch.features
        f_sh, s_sh, onehot_sh = norm_shards

        if isinstance(lf, SparseFeatures):
            lo = lax.axis_index(model_axis) * shard_d

            def margins(ws):
                li = lf.idx - lo
                own = (li >= 0) & (li < shard_d)
                li = jnp.where(own, li, shard_d)
                w_ext = jnp.concatenate([ws, jnp.zeros((1,), ws.dtype)])
                zp = jnp.sum(w_ext[li] * lf.val, axis=-1)
                return lax.psum(zp, model_axis)

            def grad_shard(dz):
                li = lf.idx - lo
                own = (li >= 0) & (li < shard_d)
                li = jnp.where(own, li, shard_d)
                contrib = lf.val * dz[:, None]
                g = jnp.zeros((shard_d + 1,), contrib.dtype)
                g = g.at[li.ravel()].add(contrib.ravel())
                return g[:shard_d]

            def sq_shard(dz):
                li = lf.idx - lo
                own = (li >= 0) & (li < shard_d)
                li = jnp.where(own, li, shard_d)
                contrib = lf.val * lf.val * dz[:, None]
                g = jnp.zeros((shard_d + 1,), contrib.dtype)
                g = g.at[li.ravel()].add(contrib.ravel())
                return g[:shard_d]
        else:

            def margins(ws):
                return lax.psum(lf.x @ ws, model_axis)

            def grad_shard(dz):
                return lf.x.T @ dz

            def sq_shard(dz):
                return (lf.x * lf.x).T @ dz

        # Coefficient-space maps for normalization (SURVEY.md §7 hard-part
        # #5): shard-local elementwise scaling; the shift correction and its
        # pullback each cost ONE scalar psum over the model axis.
        #   to_original:  w = (I − e·sᵀ)·F·w'      (e = intercept one-hot)
        #   pullback:     ∇w' = F·(∇w − s·(eᵀ∇w))
        def to_original(wp):
            out = wp if f_sh is None else wp * f_sh
            if s_sh is not None:
                corr = lax.psum(jnp.sum(out * s_sh), model_axis)
                out = out - onehot_sh * corr
            return out

        def pullback(g):
            if s_sh is not None:
                g_int = lax.psum(jnp.sum(onehot_sh * g), model_axis)
                g = g - s_sh * g_int
            if f_sh is None:
                return g
            return g * f_sh

        def to_transformed(w):
            if s_sh is not None:
                corr = lax.psum(jnp.sum(w * s_sh), model_axis)
                w = w + onehot_sh * corr
            return w if f_sh is None else w / f_sh

        use_norm = f_sh is not None or s_sh is not None

        def data_vg(w_orig):
            z = margins(w_orig) + local_batch.offsets
            lv = jnp.sum(local_batch.weights * loss.loss(z, local_batch.labels))
            lv = lax.psum(lv, data_axes)
            dz = local_batch.weights * loss.d1(z, local_batch.labels)
            g = lax.psum(grad_shard(dz), data_axes)
            return lv, g

        lam = l2 * lam_shard

        def vg(ws):
            # Data term at the original-space point; regularization on the
            # transformed-space coefficients (what the optimizer sees) —
            # reference semantics.
            lv, g = data_vg(to_original(ws) if use_norm else ws)
            if use_norm:
                g = pullback(g)
            # L2 value is a model-axis-sharded sum; data term already global.
            lv = lv + lax.psum(0.5 * jnp.sum(lam * ws * ws), model_axis)
            g = g + lam * ws
            if prior_shard is not None:
                lv = lv + lax.psum(prior_shard.value(ws), model_axis)
                g = g + prior_shard.gradient(ws)
            return lv, g

        w_start = to_transformed(w_shard) if use_norm else w_shard
        if key.optimizer_type == OptimizerType.OWLQN:
            result = OWLQN(key.optimizer_config, axis_name=model_axis).optimize(
                vg, w_start, l1 * lam_shard
            )
        elif key.optimizer_type == OptimizerType.TRON:
            # Sharded HVP: H'v = Jᵀ(Xᵀ D X)(Jv) + λv (+ prior precisions),
            # with J the (linear) normalization coefficient map. Margins and
            # curvature hoist per outer iterate, exactly like the
            # single-device GLMObjective.bind_hvp_at.
            def hvp_at(ws):
                w_orig = to_original(ws) if use_norm else ws
                z = margins(w_orig) + local_batch.offsets
                d2w = local_batch.weights * loss.d2(z, local_batch.labels)

                def hv(v):
                    v_orig = to_original(v) if use_norm else v
                    zv = margins(v_orig)
                    out = lax.psum(grad_shard(d2w * zv), data_axes)
                    if use_norm:
                        out = pullback(out)
                    out = out + lam * v
                    if prior_shard is not None:
                        out = out + prior_shard.hessian_vector(v)
                    return out

                return hv

            result = TRON(key.optimizer_config, axis_name=model_axis).optimize(
                vg, w_start, hvp_at
            )
        else:
            result = LBFGS(key.optimizer_config, axis_name=model_axis).optimize(
                vg, w_start
            )
        x_orig = to_original(result.x) if use_norm else result.x

        # SIMPLE variance (reference VarianceComputationType.SIMPLE): inverse
        # Hessian diagonal of the trained objective, per feature shard. Under
        # normalization the effective original-space penalty is λ/f².
        if key.variance_type.name == "SIMPLE":
            z = margins(x_orig) + local_batch.offsets
            d2 = local_batch.weights * loss.d2(z, local_batch.labels)
            diag = lax.psum(sq_shard(d2), data_axes)
            lam_eff = lam if f_sh is None else lam / (f_sh * f_sh)
            diag = diag + lam_eff
            if prior_shard is not None:
                diag = diag + prior_shard.hessian_diagonal()
            variances = 1.0 / jnp.maximum(diag, 1e-12)
        else:
            variances = jnp.zeros_like(x_orig)

        return (x_orig, variances), dataclasses.replace(
            result, x=jnp.zeros((0,), w_shard.dtype)
        )

    put_model = lambda a: (
        None if a is None
        else jax.device_put(a, NamedSharding(mesh, P(model_axis)))
    )
    (x_sharded, var_sharded), result = solve(
        put_model(w0),
        _shard_batch(batch, mesh, batch_specs),
        put_model(lam_mask),
        jax.tree.map(put_model, prior),
        jax.tree.map(put_model, norm_arrays),
    )
    x = jnp.asarray(x_sharded)[:d]
    result = dataclasses.replace(result, x=x)
    variances = (
        jnp.asarray(var_sharded)[:d]
        if problem.variance_type.name == "SIMPLE"
        else None
    )
    model = GeneralizedLinearModel(
        Coefficients(means=x, variances=variances), problem.task
    )
    return model, result


def _shard_batch(batch: LabeledBatch, mesh, specs) -> LabeledBatch:
    return jax.tree.map(
        lambda leaf, spec: jax.device_put(leaf, NamedSharding(mesh, spec)),
        batch,
        specs,
    )
