"""GameEstimator: end-to-end GAME fit over a configuration sweep.

Parity: reference ⟦photon-api/.../estimators/GameEstimator.scala⟧ (SURVEY.md
§3.2): DataFrame → per-coordinate datasets (built ONCE, reused across every
optimization configuration) → for each configuration, coordinate descent →
``Seq[(GameModel, Option[EvaluationResults], GameOptimizationConfiguration)]``.

TPU-first differences from the reference:
* per-coordinate datasets are device arrays (fixed-effect ``LabeledBatch``,
  bucketed ``RandomEffectDataset``) in one fixed global row order — the
  reference's GameDatum RDD partitioning/persist bookkeeping disappears;
* validation scoring per coordinate is a closure over pre-built validation
  structures, so coordinate descent's per-step evaluation does no joins;
* normalization contexts are computed from on-device feature statistics
  (one ``sq_rmatvec`` pass) instead of a Spark summarizer job.
"""
from __future__ import annotations

import dataclasses
import logging
import os
from typing import Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from photon_tpu.data.batch import LabeledBatch
from photon_tpu.data.normalization import (
    NormalizationContext,
    NormalizationType,
    context_from_statistics,
)
from photon_tpu.data.random_effect import (
    RandomEffectDataset,
    build_random_effect_dataset,
)
from photon_tpu.data.sampling import down_sampler_for_task
from photon_tpu.data.statistics import compute_feature_statistics
from photon_tpu.estimators.config import (
    CoordinateDataConfig,
    FactoredRandomEffectDataConfig,
    FixedEffectDataConfig,
    GameOptimizationConfiguration,
    GLMOptimizationConfiguration,
    RandomEffectDataConfig,
)
from photon_tpu.evaluation import EvaluationResults, EvaluationSuite
from photon_tpu.functions.objective import intercept_reg_mask
from photon_tpu.game.coordinates import (
    Coordinate,
    FixedEffectCoordinate,
    RandomEffectCoordinate,
)
from photon_tpu.game.descent import (
    CoordinateDescent,
    CoordinateStepRecord,
    GameModel,
    ValidationData,
)
from photon_tpu.io.data_reader import GameDataBundle
from photon_tpu.obs import (
    current_trace_id,
    device_wait,
    new_trace_id,
    trace_context,
    trace_span,
)
from photon_tpu.types import TaskType

Array = jax.Array

logger = logging.getLogger("photon_tpu.estimators")


@dataclasses.dataclass(frozen=True)
class GameFitResult:
    """One entry of the estimator's output sequence — reference
    ⟦(GameModel, Option[EvaluationResults], GameOptimizationConfiguration)⟧
    plus the per-step tracker."""

    model: GameModel
    evaluation: Optional[EvaluationResults]
    config: GameOptimizationConfiguration
    tracker: Sequence[CoordinateStepRecord]


def build_re_dataset_from_bundle(
    bundle: GameDataBundle,
    cfg: RandomEffectDataConfig,
    intercept_index: Optional[int] = None,
    for_scoring: bool = False,
) -> RandomEffectDataset:
    """Group a bundle's rows by ``cfg.re_type`` into a bucketed per-entity
    dataset. For scoring/validation datasets every entity is kept (rows of
    entities unseen at training time score 0 — the reference's zero-model
    fallback) and no active/passive split applies."""
    sf = bundle.features[cfg.feature_shard]
    if cfg.re_type not in bundle.id_tags:
        raise ValueError(
            f"random effect {cfg.re_type!r} needs id tag column "
            f"{cfg.re_type!r}; bundle has {sorted(bundle.id_tags)}"
        )
    # Reading the shard back, grouping the rows by key on the host, packing
    # and placing the buckets: timed by key, since a fit with two keys
    # groups its rows twice (docs/observability.md).
    with trace_span("data.re_dataset", cat="data", re_type=cfg.re_type,
                    scoring=for_scoring) as span:
        with device_wait("re_dataset"):
            val_np = np.asarray(jax.device_get(sf.val))
            idx_np = np.asarray(jax.device_get(sf.idx))
        # Follow the bundle's feature precision (float64 under --dtype
        # float64) — EXCEPT sub-f32 feed dtypes: the bf16 feed narrows the
        # fixed-effect transfer only, while per-entity solves accumulate in
        # f32 (the batched Cholesky kernels have no bf16 lowering), so RE
        # buckets re-pack the already-quantized values as f32.
        re_dtype = val_np.dtype
        if re_dtype.itemsize < 4:
            re_dtype = np.dtype(np.float32)
        dataset = build_random_effect_dataset(
            re_type=cfg.re_type,
            entity_keys_per_row=bundle.id_tags[cfg.re_type],
            idx=idx_np,
            val=val_np,
            labels=bundle.labels,
            global_dim=sf.dim,
            weights=bundle.weights,
            active_bound=None if for_scoring else cfg.active_bound,
            min_entity_rows=1 if for_scoring else cfg.min_entity_rows,
            intercept_index=intercept_index,
            max_features_per_entity=(
                None if for_scoring else cfg.max_features_per_entity
            ),
            max_bucket_entities=cfg.max_bucket_entities,
            host_resident=cfg.host_resident,
            dtype=re_dtype,
        )
        span.set(entities=dataset.n_entities,
                 classes=len(dataset.size_classes),
                 **dataset.span_arguments())
    return dataset


def _factorize_group_ids(values: np.ndarray) -> tuple[Array, int]:
    keys, inv = np.unique(values, return_inverse=True)
    return jnp.asarray(inv.astype(np.int32)), len(keys)


@dataclasses.dataclass
class GameEstimator:
    """Configured GAME trainer; ``fit`` runs the configuration sweep.

    ``coordinate_data_configs`` fixes each coordinate's dataset; the
    ``update_sequence`` (default: insertion order) and sweep count mirror the
    reference params ⟦coordinateUpdateSequence, coordinateDescentIterations⟧.
    ``intercept_indices`` (shard → column) excludes intercepts from
    regularization and anchors normalization shifts, as the reference derives
    from its index maps.
    """

    task: TaskType
    coordinate_data_configs: Mapping[str, CoordinateDataConfig]
    update_sequence: Optional[Sequence[str]] = None
    n_sweeps: int = 1
    evaluator_specs: Sequence[str] = ()
    normalization: NormalizationType = NormalizationType.NONE
    intercept_indices: Optional[Mapping[str, int]] = None
    mesh: Optional[object] = None
    data_axis: str = "data"
    # Fixed-effect coordinates train feature-dimension-sharded over this
    # mesh axis when set (P3; random effects always shard over data_axis).
    model_axis: Optional[str] = None
    # Auto-routing (SURVEY.md §2.6 P3): when ``model_axis`` is unset but the
    # mesh HAS a "model" axis, fixed-effect coordinates whose feature dim
    # exceeds this threshold train feature-sharded; smaller ones stay
    # data-parallel (coefficients replicated over the model axis).
    auto_p3_threshold: int = 1 << 20
    # Device-resident sweep cache budget in MB for host-resident coordinate
    # data (data/device_cache.py): multi-sweep descent pins those datasets
    # on device after first touch instead of re-uploading every sweep.
    # None = PHOTON_SWEEP_CACHE_MB (default 2048); 0 disables.
    sweep_cache_mb: Optional[float] = None
    seed: int = 0

    def __post_init__(self):
        if self.update_sequence is None:
            self.update_sequence = tuple(self.coordinate_data_configs)
        for cid in self.update_sequence:
            if cid not in self.coordinate_data_configs:
                raise ValueError(
                    f"update sequence names unknown coordinate {cid!r}"
                )

    def fingerprint_parts(self) -> tuple:
        """The estimator's training-semantics identity, for checkpoint
        fingerprints (tuning resume refuses a changed configuration)."""
        return (
            self.task,
            tuple(self.update_sequence),
            self.n_sweeps,
            tuple(self.evaluator_specs),
            self.normalization,
            sorted((cid, repr(c))
                   for cid, c in self.coordinate_data_configs.items()),
        )

    # ------------------------------------------------------------------ fit

    def fit(
        self,
        data: GameDataBundle,
        validation_data: Optional[GameDataBundle] = None,
        configs: Sequence[GameOptimizationConfiguration] = (),
        initial_model: Optional[GameModel] = None,
        checkpoint_manager=None,
    ) -> list[GameFitResult]:
        """Train one GameModel per optimization configuration.

        Datasets, normalization contexts, and validation structures are built
        once and shared across the sweep (reference: datasets persist across
        the config loop and unpersist after). ``initial_model`` warm-starts
        every configuration (reference ⟦modelInputDirectory⟧).

        ``checkpoint_manager`` (photon_tpu.checkpoint.CheckpointManager)
        enables step-level checkpointing: every coordinate step and every
        completed configuration is snapshotted, and a fresh ``fit`` over the
        same inputs auto-resumes from the newest snapshot, reproducing the
        uninterrupted result bit-identically.

        A fit is one request of the tracing system (docs/observability.md):
        the ``estimator.fit`` span is the root of every span below it, they
        share one trace id, and its finished tree is kept
        (``obs.recent_trees("estimator.fit")``; :func:`fit_breakdown`).
        """
        with trace_context(current_trace_id() or new_trace_id()), \
                trace_span("estimator.fit", cat="estimator",
                           rows=data.n_rows,
                           configs=len(configs)).keep_tree():
            return self._fit(data, validation_data, configs, initial_model,
                             checkpoint_manager)

    def _fit(self, data, validation_data, configs, initial_model,
             checkpoint_manager) -> list[GameFitResult]:
        if not configs:
            raise ValueError("at least one GameOptimizationConfiguration required")
        for cfg in configs:
            missing = [c for c in self.update_sequence if c not in cfg]
            if missing:
                raise ValueError(f"configuration missing coordinates {missing}")

        suite = (
            EvaluationSuite.parse(self.evaluator_specs)
            if self.evaluator_specs
            else None
        )
        if validation_data is not None and suite is None:
            raise ValueError("validation data provided but no evaluator_specs")

        prep = self._prepare_cached(data)
        validation = (
            self._prepare_validation_cached(validation_data, suite)
            if validation_data is not None
            else None
        )

        results: list[GameFitResult] = []
        start_config, descent_resume, fingerprint = 0, None, None
        if checkpoint_manager is not None:
            from photon_tpu.checkpoint import run_fingerprint

            # One identity definition (fingerprint_parts — includes
            # normalization and data configs) plus the per-call specifics;
            # the tuning path shares the same parts, so both resume checks
            # refuse the same configuration changes.
            fingerprint = run_fingerprint((
                self.fingerprint_parts(),
                [sorted((cid, repr(c)) for cid, c in cfg.items())
                 for cfg in configs],
                data.n_rows,
            ))
            payload = checkpoint_manager.load_checked("game_fit", fingerprint)
            if payload is not None:
                meta = payload["meta"]
                results = list(payload["state"].get("completed_results", []))
                if meta.get("phase") == "config_done":
                    start_config = meta["config_index"] + 1
                else:
                    start_config = meta["config_index"]
                    descent_resume = payload
                logger.info(
                    "resuming from checkpoint step %d (config %d)",
                    payload["step"], start_config,
                )
                # Zero-recompile resume (docs/robustness.md §recovery
                # time): the checkpoint's compile-store manifest reference
                # pre-warms every executable the interrupted run compiled
                # BEFORE the first resumed step dispatches — the restart
                # cost becomes artifact I/O, not XLA.
                from photon_tpu.runtime.compile_store import (
                    prewarm_from_checkpoint,
                )

                prewarm_from_checkpoint(payload, logger_=logger)

        # Each config owns steps_per_config descent steps + 1 config-done slot.
        steps_per_config = self.n_sweeps * len(self.update_sequence)
        for i, cfg in enumerate(configs):
            if i < start_config:
                continue
            if i > start_config and os.environ.get(
                "PHOTON_CLEAR_CACHES_PER_CONFIG"
            ) == "1":
                # λ-boundary executable-cache bound (VERDICT r5 weak #5):
                # a long sweep accumulates mmap'd JIT code pages jax never
                # frees in-process; opt-in (the drivers'
                # --clear-caches-per-config) because in-core sweeps whose
                # shapes repeat across λ values benefit from reuse.
                from photon_tpu.supervisor import clear_executable_caches

                clear_executable_caches(f"config boundary {i}")
            logger.info("=== configuration %d/%d ===", i + 1, len(configs))
            with trace_span("estimator.build_coordinates", cat="estimator",
                            config_index=i) as span:
                coordinates = self._build_coordinates(
                    prep, cfg, config_index=i, span=span,
                    initial_model=initial_model,
                )
            descent = CoordinateDescent(
                update_sequence=tuple(self.update_sequence),
                n_sweeps=self.n_sweeps,
            )
            model, tracker = descent.run(
                coordinates,
                n_rows=data.n_rows,
                base_offsets=jnp.asarray(data.offsets, jnp.float32),
                validation=validation,
                suite=suite,
                initial_models=dict(initial_model.models) if initial_model else None,
                checkpointer=checkpoint_manager,
                resume=descent_resume if i == start_config else None,
                step_base=i * (steps_per_config + 1),
                checkpoint_meta={"config_index": i, "kind": "game_fit",
                                 "fingerprint": fingerprint},
                extra_state={"completed_results": results},
            )
            descent_resume = None
            evaluation = None
            if validation is not None:
                with trace_span("estimator.evaluate", cat="estimator"):
                    evaluation = self._evaluate(model, validation, suite)
            results.append(GameFitResult(model, evaluation, cfg, tracker))
            if checkpoint_manager is not None:
                checkpoint_manager.save(
                    i * (steps_per_config + 1) + steps_per_config,
                    state={"completed_results": results},
                    meta={"phase": "config_done", "config_index": i,
                          "kind": "game_fit", "fingerprint": fingerprint},
                )
        if checkpoint_manager is not None:
            checkpoint_manager.wait()
        return results

    # ----------------------------------------------------------- internals

    def _intercept_for(self, shard: str) -> Optional[int]:
        if self.intercept_indices is None:
            return None
        return self.intercept_indices.get(shard)

    def _prepare_cached(self, data: GameDataBundle) -> dict:
        """Per-bundle preparation cache (size 1, identity-keyed): repeated
        fits on the same bundle — hyperparameter tuning calls fit once per
        proposed config — reuse the datasets/statistics instead of
        regrouping random effects every iteration, and the fast-path
        tables of its fixed-effect shards (``_with_tables``) instead of
        building them in every fit. All of it lives as long as the
        estimator does, or until it prepares another bundle."""
        cached = getattr(self, "_prep_cache", None)
        if cached is not None and cached[0] is data:
            return cached[1]
        if cached is not None:
            # New bundle: drop the old bundle's device pins and fast-path
            # tables before the new one is prepared (a tuning loop
            # switching datasets must not hold both residencies).
            old_cache = cached[1].get("device_cache")
            if old_cache is not None:
                old_cache.release()
            cached[1]["tables"].clear()
        with trace_span("estimator.prepare", cat="estimator",
                        rows=data.n_rows,
                        shards=len(data.features)):
            prep = self._prepare(data)
        self._prep_cache = (data, prep)
        return prep

    def _prepare_validation_cached(
        self, vdata: GameDataBundle, suite: EvaluationSuite
    ) -> ValidationData:
        cached = getattr(self, "_validation_cache", None)
        if cached is not None and cached[0] is vdata and cached[1] == suite:
            return cached[2]
        with trace_span("estimator.prepare_validation", cat="estimator",
                        rows=vdata.n_rows):
            v = self._prepare_validation(vdata, suite)
        self._validation_cache = (vdata, suite, v)
        return v

    def _prepare(self, data: GameDataBundle) -> dict:
        """Build per-coordinate datasets + per-shard normalization ONCE."""
        from photon_tpu.data.device_cache import DeviceSweepCache

        # "tables": shard -> the shard's features with their fast-path
        # tables attached, filled by the first fit that builds them.
        prep: dict = {"train": {}, "norm": {}, "batches": {}, "tables": {}}
        # One sweep cache per prepared bundle, shared across the whole
        # config sweep (same data ⇒ one upload for every λ). Mesh-attached:
        # pins shard over the entity axis (per-shard residency, per-device
        # budget × device count) instead of pinning to device 0.
        prep["device_cache"] = DeviceSweepCache(
            None if self.sweep_cache_mb is None
            else int(self.sweep_cache_mb * 1e6),
            mesh=self.mesh, entity_axis=self.data_axis,
        )
        shards_used = {
            c.feature_shard for c in self.coordinate_data_configs.values()
        }
        for shard in sorted(shards_used):
            batch = data.batch(shard)
            prep["batches"][shard] = batch
            if self.normalization != NormalizationType.NONE:
                stats = compute_feature_statistics(batch)
                prep["norm"][shard] = context_from_statistics(
                    stats, self.normalization, self._intercept_for(shard)
                )
            else:
                prep["norm"][shard] = None

        for cid, dcfg in self.coordinate_data_configs.items():
            if isinstance(dcfg, FixedEffectDataConfig):
                prep["train"][cid] = prep["batches"][dcfg.feature_shard]
            elif isinstance(dcfg, RandomEffectDataConfig):
                prep["train"][cid] = build_re_dataset_from_bundle(
                    data, dcfg, self._intercept_for(dcfg.feature_shard)
                )
            else:  # pragma: no cover - union is closed
                raise TypeError(f"unknown data config {type(dcfg)}")
        return prep

    def _build_coordinates(
        self,
        prep: dict,
        cfg: GameOptimizationConfiguration,
        config_index: int,
        span: trace_span,
        initial_model: Optional[GameModel] = None,
    ) -> dict[str, Coordinate]:
        """``span`` (the caller's ``estimator.build_coordinates``) is told
        how many fixed effects took kept fast-path tables and how many
        built theirs: ``tables_reused``, ``tables_built``."""
        # Coordinates are built for EVERY data config, not just the update
        # sequence: coordinates outside the sequence are scoring-only (locked
        # warm-start models — reference partial retraining) and use a default
        # problem that never runs.
        coordinates: dict[str, Coordinate] = {}
        tables = {"reused": 0, "built": 0}
        for cid in self.coordinate_data_configs:
            dcfg = self.coordinate_data_configs[cid]
            ocfg = cfg.get(cid, GLMOptimizationConfiguration())
            problem = ocfg.problem(self.task)
            intercept = self._intercept_for(dcfg.feature_shard)

            init_m = (
                initial_model.models.get(cid)
                if initial_model is not None and ocfg.incremental_weight > 0.0
                else None
            )
            if ocfg.incremental_weight > 0.0 and init_m is None:
                raise ValueError(
                    f"coordinate {cid!r}: incremental_weight > 0 requires an "
                    "initial_model containing this coordinate"
                )

            if isinstance(dcfg, FixedEffectDataConfig):
                batch: LabeledBatch = prep["train"][cid]
                mask = intercept_reg_mask(batch.dim, intercept)
                if mask is not None:
                    problem = dataclasses.replace(problem, reg_mask=mask)
                if init_m is not None:
                    from photon_tpu.functions.prior import PriorDistribution

                    problem = dataclasses.replace(
                        problem,
                        prior=PriorDistribution.from_model(
                            init_m.model.coefficients.means,
                            init_m.model.coefficients.variances,
                            ocfg.incremental_weight,
                        ),
                    )
                if ocfg.down_sampling_rate < 1.0:
                    # Per-(config, coordinate) derived key, reproducible.
                    key = jax.random.fold_in(
                        jax.random.fold_in(
                            jax.random.PRNGKey(self.seed), config_index
                        ),
                        len(coordinates),
                    )
                    sampler = down_sampler_for_task(
                        self.task, ocfg.down_sampling_rate
                    )
                    batch = sampler.down_sample(key, batch)
                model_axis = self.model_axis
                if (
                    model_axis is None
                    and self.mesh is not None
                    and "model" in getattr(self.mesh, "axis_names", ())
                    and batch.dim > self.auto_p3_threshold
                    # Route only configurations fit_model_parallel supports;
                    # others stay data-parallel (replicated over the model
                    # axis) instead of failing mid-sweep.
                    and problem.optimizer_type.name in ("LBFGS", "OWLQN", "TRON")
                    and problem.variance_type.name != "FULL"
                    and not (
                        prep["norm"][dcfg.feature_shard] is not None
                        and problem.prior is not None
                    )
                ):
                    model_axis = "model"
                if self.mesh is None:
                    # Single-device solve: attach the MXU-friendly sparse
                    # layouts (no-op off-accelerator; one host-side build
                    # per prepared shard, kept with the bundle). Mesh
                    # runs shard rows, which the global tables cannot
                    # follow — those keep the shardable plain formulation.
                    batch, how = self._with_tables(
                        prep, dcfg.feature_shard, batch)
                    if how is not None:
                        tables[how] += 1
                coordinates[cid] = FixedEffectCoordinate(
                    batch=batch,
                    problem=problem,
                    feature_shard=dcfg.feature_shard,
                    mesh=self.mesh,
                    data_axis=self.data_axis,
                    normalization=prep["norm"][dcfg.feature_shard],
                    model_axis=model_axis,
                )
            elif isinstance(dcfg, FactoredRandomEffectDataConfig):
                from photon_tpu.game.coordinates import (
                    FactoredRandomEffectCoordinate,
                )

                # Unsupported knobs fail loudly rather than silently no-op.
                unsupported = []
                if ocfg.incremental_weight > 0.0:
                    unsupported.append("incremental training")
                if ocfg.down_sampling_rate < 1.0:
                    unsupported.append("down-sampling")
                if ocfg.variance_type.name != "NONE":
                    unsupported.append("coefficient variances")
                if prep["norm"][dcfg.feature_shard] is not None:
                    unsupported.append("feature normalization")
                if unsupported:
                    raise ValueError(
                        f"coordinate {cid!r}: {', '.join(unsupported)} "
                        "not supported for factored random effects"
                    )
                coordinates[cid] = FactoredRandomEffectCoordinate(
                    dataset=prep["train"][cid],
                    problem=problem,
                    latent_dim=dcfg.latent_dim,
                    n_alternations=dcfg.n_alternations,
                    seed=self.seed,
                )
            else:
                dataset = prep["train"][cid]
                if ocfg.down_sampling_rate < 1.0:
                    from photon_tpu.data.random_effect import down_sample_dataset

                    key = jax.random.fold_in(
                        jax.random.fold_in(
                            jax.random.PRNGKey(self.seed), config_index
                        ),
                        len(coordinates),
                    )
                    dataset = down_sample_dataset(
                        dataset,
                        down_sampler_for_task(self.task, ocfg.down_sampling_rate),
                        key,
                    )
                mask = intercept_reg_mask(dataset.global_dim, intercept)
                priors = None
                if init_m is not None:
                    from photon_tpu.functions.prior import PriorDistribution

                    # Posterior projection is config-independent (down-sampled
                    # datasets keep the bucket structure); cache it across the
                    # sweep. Keyed by the model object itself (identity
                    # verified on hit) — an id() key could silently serve a
                    # stale projection after id reuse.
                    cache = prep.setdefault("prior_proj", {})
                    hit = cache.get(cid)
                    if hit is None or hit[0] is not init_m:
                        hit = (
                            init_m,
                            init_m.project_posteriors_to(prep["train"][cid]),
                        )
                        cache[cid] = hit
                    means, variances = hit[1]
                    priors = [
                        PriorDistribution.from_model(
                            m, v, ocfg.incremental_weight
                        )
                        for m, v in zip(means, variances)
                    ]
                coordinates[cid] = RandomEffectCoordinate(
                    dataset=dataset,
                    problem=problem,
                    mesh=self.mesh,
                    entity_axis=self.data_axis,
                    global_reg_mask=mask,
                    normalization=prep["norm"][dcfg.feature_shard],
                    priors=priors,
                    # The sweep cache pins ONLY the shared prepared dataset:
                    # a down-sampled dataset is a fresh object per config,
                    # and pinning each would stack one dead mirror per λ in
                    # device memory for the estimator's lifetime. Those
                    # configs stream per sweep — the pre-cache behavior.
                    device_cache=(
                        prep.get("device_cache")
                        if ocfg.down_sampling_rate >= 1.0 else None
                    ),
                )
        span.set(tables_reused=tables["reused"], tables_built=tables["built"])
        return coordinates

    @staticmethod
    def _with_tables(
        prep: dict, shard: str, batch: LabeledBatch
    ) -> tuple[LabeledBatch, Optional[str]]:
        """``batch`` with the fast-path tables of its sparse features
        attached, and where they came from: ``"reused"``, ``"built"`` or
        None (no tables: off the accelerator, dense features, over the
        table budget of ``data/batch.py``).

        The tables are a pure function of the feature object, so those of
        a prepared shard are built once, by the first fit that needs them,
        kept in ``prep["tables"]`` beside the batch they were built from
        and dropped with it (``_prepare_cached``). A hit is an identity,
        never a number: kept tables go only to the very feature object
        ``prep["batches"][shard]`` holds (a down-sampled batch shares it —
        sampling replaces the weights alone), and ``prep`` keeps that
        object alive for as long as it keeps the tables, so no later object
        can come by them through a reused ``id``. Any other feature object
        builds inside the call and nothing of it outlives the call."""
        prepared = batch.features is prep["batches"][shard].features
        kept = prep["tables"].get(shard) if prepared else None
        if kept is not None:
            return dataclasses.replace(batch, features=kept), "reused"
        attached = batch.with_accelerator_paths()
        if attached is batch:
            return batch, None
        if prepared:
            prep["tables"][shard] = attached.features
        return attached, "built"

    def _prepare_validation(
        self,
        vdata: GameDataBundle,
        suite: EvaluationSuite,
    ) -> ValidationData:
        """Validation rows + per-coordinate scorers + grouped-eval ids."""
        v_batches = {
            s: vdata.batch(s)
            for s in {c.feature_shard for c in self.coordinate_data_configs.values()}
        }
        scorers: dict = {}
        for cid, dcfg in self.coordinate_data_configs.items():
            if isinstance(dcfg, FixedEffectDataConfig):
                vb = v_batches[dcfg.feature_shard]
                scorers[cid] = lambda m, vb=vb: m.score_batch(vb)
            else:
                v_ds = build_re_dataset_from_bundle(
                    vdata,
                    dcfg,
                    self._intercept_for(dcfg.feature_shard),
                    for_scoring=True,
                )
                scorers[cid] = lambda m, v_ds=v_ds: m.score_new_dataset(v_ds)

        group_cols = {
            ev.group_column
            for ev in suite.evaluators
            if ev.group_column is not None
        }
        gids, ngroups = {}, {}
        for col in group_cols:
            if col not in vdata.id_tags:
                raise ValueError(
                    f"grouped evaluator needs id tag column {col!r} in "
                    f"validation data; bundle has {sorted(vdata.id_tags)}"
                )
            gids[col], ngroups[col] = _factorize_group_ids(vdata.id_tags[col])

        return ValidationData(
            labels=jnp.asarray(vdata.labels, jnp.float32),
            weights=jnp.asarray(vdata.weights, jnp.float32),
            offsets=jnp.asarray(vdata.offsets, jnp.float32),
            scorers=scorers,
            group_ids_by_column=gids or None,
            num_groups_by_column=ngroups or None,
        )

    def _evaluate(
        self,
        model: GameModel,
        validation: ValidationData,
        suite: EvaluationSuite,
    ) -> EvaluationResults:
        per_coordinate = []
        for cid in model.keys():
            with trace_span("validate.score", cat="estimator",
                            coordinate=cid):
                per_coordinate.append(validation.scorers[cid](model[cid]))
        scores = validation.offsets + sum(per_coordinate)
        return suite.evaluate(
            scores,
            validation.labels,
            validation.weights,
            validation.group_ids_by_column,
            validation.num_groups_by_column,
        )


_BREAKDOWN_PARTS = {
    "estimator.prepare": "prepare",
    "estimator.prepare_validation": "prepare",
    "data.accel_tables": "tables",
    "descent.validate": "validate",
    "estimator.evaluate": "validate",
}


def fit_breakdown(tree: Sequence[tuple]) -> dict[str, float]:
    """Where one fit's seconds went, from its kept span tree
    (``obs.recent_trees("estimator.fit")[-1]``), in this order: ``fit``
    (the whole call), ``prepare`` (datasets and validation structures:
    the first fit on a bundle only), ``tables`` (the fast-path layouts:
    the first fit on a bundle that needs them only),
    one entry per trained coordinate (its steps), ``validate``, and
    ``descent``: what is left, the host work between them. The parts add
    up to ``fit``; a part that took no time is left out. Last, and no part
    (it lies inside the others): ``waited``, the seconds the fit's thread
    was blocked in a device-to-host read (``device.wait``)."""
    parts = {"prepare": 0.0, "tables": 0.0}
    waited = 0.0
    for name, _, _, start, end, args in tree:
        if name == "device.wait":
            waited += end - start
        part = (args.get("coordinate") if name == "descent.step"
                else _BREAKDOWN_PARTS.get(name))
        if part is not None:
            parts[part] = parts.get(part, 0.0) + (end - start)
    parts["validate"] = parts.pop("validate", 0.0)   # after the coordinates
    fit = tree[-1][4] - tree[-1][3]
    parts["descent"] = fit - sum(parts.values())
    return {"fit": fit, **{k: v for k, v in parts.items() if v},
            "waited": waited}


def select_best(
    results: Sequence[GameFitResult], suite: EvaluationSuite
) -> GameFitResult:
    """Pick the configuration whose final validation primary metric is best —
    the reference driver's model-selection step (SURVEY.md §3.1)."""
    scored = [r for r in results if r.evaluation is not None]
    if not scored:
        return results[0]
    best = scored[0]
    for r in scored[1:]:
        if suite.primary.better_than(r.evaluation.primary, best.evaluation.primary):
            best = r
    return best
