"""GAME coordinates: one unit of block-coordinate descent.

Parity: reference ⟦photon-api/.../algorithm/Coordinate.scala,
FixedEffectCoordinate.scala, RandomEffectCoordinate.scala⟧ (SURVEY.md §2.2,
§3.4/§3.5). A coordinate owns its training data and optimization problem and
exposes ``train(offsets, init) -> model`` and ``score(model) -> [N]``.

TPU-first: offsets are a plain per-row array aligned with the global sample
order (fixed at dataset build time), so the reference's score-RDD joins by
``UniqueSampleId`` become elementwise adds (SURVEY.md §2.6 comm table).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp

from photon_tpu.data.batch import LabeledBatch
from photon_tpu.data.random_effect import RandomEffectDataset
from photon_tpu.functions.problem import GLMOptimizationProblem
from photon_tpu.game.random_effect import (
    RandomEffectModel,
    train_random_effects,
)
from photon_tpu.models.glm import GeneralizedLinearModel
from photon_tpu.obs import device_wait, trace_span, tracing_active
from photon_tpu.parallel.data_parallel import fit_data_parallel

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class FixedEffectModel:
    """Population-level GLM for one feature shard — reference
    ⟦FixedEffectModel(coefficientsBroadcast, featureShardId)⟧. Replication
    over the mesh replaces the broadcast."""

    model: GeneralizedLinearModel
    feature_shard: str

    def score_batch(self, batch: LabeledBatch) -> Array:
        """Raw per-row scores WITHOUT offsets (GAME sums coordinate scores)."""
        return batch.features.matvec(self.model.coefficients.means)


@dataclasses.dataclass(frozen=True)
class FixedEffectCoordinate:
    """Train one GLM on all rows, data-parallel over the mesh (SURVEY §3.4)."""

    batch: LabeledBatch            # offsets field ignored; passed per train()
    problem: GLMOptimizationProblem
    feature_shard: str = "global"
    mesh: Optional[object] = None
    data_axis: str = "data"
    normalization: Optional[object] = None   # NormalizationContext or None
    # When set (with a mesh that has this axis), coefficients/gradients/
    # L-BFGS history shard over it — the P3 feature-dimension path for very
    # wide feature spaces (SURVEY.md §2.6 P3).
    model_axis: Optional[str] = None

    def train(self, offsets: Array, init: Optional[FixedEffectModel] = None):
        batch = self.batch.with_offsets(offsets.astype(self.batch.labels.dtype))
        if init is not None:
            w0 = init.model.coefficients.means
        else:
            w0 = jnp.zeros((batch.dim,), batch.labels.dtype)
        with trace_span("optim.fixed_solve", cat="optim",
                        shard=self.feature_shard, rows=batch.n_rows,
                        dim=batch.dim) as sp:
            if self.mesh is not None and self.model_axis is not None:
                from photon_tpu.parallel.model_parallel import fit_model_parallel

                model, result = fit_model_parallel(
                    self.problem, batch, w0, self.mesh,
                    self.data_axis, self.model_axis,
                    normalization=self.normalization,
                )
            elif self.mesh is not None:
                model, result = fit_data_parallel(
                    self.problem, batch, w0, self.mesh, self.data_axis,
                    normalization=self.normalization,
                )
            else:
                model, result = self.problem.fit(batch, w0, normalization=self.normalization)
            if tracing_active():
                # One tiny D2H per solve, paid only when a trace is being
                # collected: iteration count + convergence reason make the
                # optimizer lane of the timeline self-describing.
                with device_wait("fixed_solve"):
                    sp.set(iterations=int(result.iterations),
                           reason=result.reason_name())
        return FixedEffectModel(model, self.feature_shard), result

    def score(self, model: FixedEffectModel) -> Array:
        return model.score_batch(self.batch)


@dataclasses.dataclass(frozen=True)
class RandomEffectCoordinate:
    """Per-entity GLMs over a RandomEffectDataset (SURVEY §3.5)."""

    dataset: RandomEffectDataset
    problem: GLMOptimizationProblem
    mesh: Optional[object] = None
    # One mesh axis or a tuple (mesh.AxisSpec; e.g. ("dcn", "data")).
    entity_axis: "str | tuple" = "data"
    global_reg_mask: Optional[Array] = None
    normalization: Optional[object] = None   # shard-level NormalizationContext
    # Per-bucket PriorDistribution pytrees for incremental training
    # (RandomEffectModel.project_prior_to output).
    priors: Optional[Sequence] = None
    # Device-resident sweep cache (data/device_cache.py): host-resident
    # bucket datasets pin on device at first touch, so sweep 1+ of a
    # multi-sweep descent (train AND score) stops re-uploading per bucket.
    # The cache's mirror is identity-stable, so _same_structure keeps
    # detecting "trained on this dataset" across sweeps.
    device_cache: Optional[object] = None

    def _data(self) -> RandomEffectDataset:
        """The dataset every train/score consumes: the device-resident
        mirror when a sweep cache holds it, else the original (device-backed
        builds and budget-busted spills are both the original object)."""
        if self.device_cache is None:
            return self.dataset
        return self.device_cache.dataset_mirror(self.dataset)

    def _same_structure(self, model: RandomEffectModel) -> bool:
        # A model trained on THIS dataset (every coordinate-descent sweep)
        # shares bucket structure by object identity. Anything else — a
        # loaded model, a model from different data — must be re-projected
        # into this dataset's bucket/subspace structure.
        dataset = self._data()
        return len(model.bucket_coefs) == len(dataset.buckets) and all(
            p is b.proj for p, b in zip(model.bucket_proj, dataset.buckets)
        )

    def _init_coefs(self, init: Optional[RandomEffectModel]):
        if init is None:
            return None
        return (
            init.bucket_coefs
            if self._same_structure(init)
            else init.project_to(self._data())
        )

    def train(self, offsets: Array, init: Optional[RandomEffectModel] = None):
        return train_random_effects(
            self.problem, self._data(), offsets,
            mesh=self.mesh, entity_axis=self.entity_axis,
            global_reg_mask=self.global_reg_mask,
            init_coefs=self._init_coefs(init),
            normalization=self.normalization,
            priors=self.priors,
        )

    def span_arguments(self) -> dict:
        """What a step of this coordinate works on, for its
        ``descent.step`` span."""
        return self.dataset.span_arguments()

    def score(self, model: RandomEffectModel) -> Array:
        dataset = self._data()
        if self._same_structure(model):
            return model.score_dataset(dataset)
        # Foreign model (loaded warm start / locked coordinate): project its
        # per-entity coefficients into this dataset's structure first.
        return model.score_new_dataset(dataset)


@dataclasses.dataclass(frozen=True)
class FactoredRandomEffectCoordinate:
    """Per-entity models in a learned latent space — reference
    ⟦FactoredRandomEffectCoordinate⟧ (see game/factored_random_effect.py)."""

    dataset: RandomEffectDataset
    problem: GLMOptimizationProblem
    latent_dim: int = 8
    n_alternations: int = 2
    seed: int = 0

    def train(self, offsets: Array, init=None):
        from photon_tpu.game.factored_random_effect import (
            FactoredRandomEffectModel,
            train_factored_random_effects,
        )

        # A loaded warm start arrives as the saved EFFECTIVE RandomEffectModel;
        # train_factored_random_effects re-factors it spectrally (the
        # effective matrix is exactly rank-p, so the SVD recovers the saved
        # factorization's subspace).
        if not isinstance(init, (FactoredRandomEffectModel, RandomEffectModel)):
            init = None
        return train_factored_random_effects(
            self.problem, self.dataset, offsets,
            latent_dim=self.latent_dim,
            n_alternations=self.n_alternations,
            seed=self.seed,
            init=init,
        )

    def score(self, model) -> Array:
        # Score through the effective per-entity model; a foreign model
        # (loaded warm start / locked coordinate, possibly a plain
        # RandomEffectModel) goes through key-matched re-projection.
        eff = getattr(model, "effective", model)
        same = len(eff.bucket_proj) == len(self.dataset.buckets) and all(
            p is b.proj for p, b in zip(eff.bucket_proj, self.dataset.buckets)
        )
        return (
            eff.score_dataset(self.dataset)
            if same
            else eff.score_new_dataset(self.dataset)
        )


Coordinate = Union[
    FixedEffectCoordinate, RandomEffectCoordinate, FactoredRandomEffectCoordinate
]
DatumScoringModel = Union[FixedEffectModel, RandomEffectModel]
