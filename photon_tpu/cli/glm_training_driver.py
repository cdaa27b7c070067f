"""Legacy single-GLM training driver: the pre-GAME pipeline with diagnostics.

Parity: reference ⟦photon-client/.../Driver.scala⟧ + ⟦.../diagnostics/⟧
(SURVEY.md §2.3 "Legacy GLM driver"): read training (+validation) Avro →
optional normalization → train one fixed-effect GLM per regularization
weight in the grid → validate and select → diagnostics on the selected model
(bootstrap coefficient CIs, Hosmer–Lemeshow calibration, feature importance)
→ save model + HTML fit report.

TPU-first: the per-λ fits reuse one jit-compiled solve (shapes/config are
identical across the grid, only ``reg_weight`` changes → one trace, many
executions); bootstrap replicates run as a single vmapped batch of solves.

Usage example:

    python -m photon_tpu.cli.glm_training_driver \
      --train-data data/train --validation-data data/val \
      --output-dir out --task LOGISTIC_REGRESSION \
      --regularization L2 --reg-weights 0.01 0.1 1 10 \
      --bootstrap-replicates 32
"""
from __future__ import annotations

import argparse
import json
import os
from typing import Optional, Sequence

import numpy as np

from photon_tpu.cli.params import parse_feature_shard
from photon_tpu.data.normalization import NormalizationType, context_from_statistics
from photon_tpu.data.statistics import compute_feature_statistics
from photon_tpu.data.validators import DataValidationType, sanity_check_data
from photon_tpu.types import REAL_ACCELERATOR_BACKENDS
from photon_tpu.evaluation import EvaluationSuite
from photon_tpu.functions.problem import (
    GLMOptimizationProblem,
    VarianceComputationType,
)
from photon_tpu.index.index_map import MmapIndexMap, build_mmap_index
from photon_tpu.io.data_reader import (
    AvroDataReader,
    FeatureShardConfig,
    InputColumnNames,
    build_index_from_avro,
)
from photon_tpu.io.model_io import save_game_model
from photon_tpu.optim import (
    OptimizerConfig,
    OptimizerType,
    RegularizationContext,
    RegularizationType,
)
from photon_tpu.types import TaskType
from photon_tpu.utils import PhotonLogger, Timed

SHARD = "global"


def build_arg_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="glm-training-driver",
        description="Train a single fixed-effect GLM with diagnostics "
                    "(the reference's legacy pre-GAME Driver).",
    )
    p.add_argument("--train-data", nargs="+", required=True)
    p.add_argument("--validation-data", nargs="+", default=None)
    p.add_argument("--output-dir", required=True)
    p.add_argument("--task", required=True, choices=[t.name for t in TaskType])
    p.add_argument("--feature-shard", default="global:features",
                   metavar="SHARD[:BAG+BAG][:no-intercept]",
                   help="single feature-shard spec (shard name must be "
                        f"'{SHARD}')")
    p.add_argument("--optimizer", default="LBFGS",
                   choices=[o.name for o in OptimizerType])
    p.add_argument("--regularization", default="L2",
                   choices=[r.name for r in RegularizationType])
    p.add_argument("--elastic-net-alpha", type=float, default=0.5)
    p.add_argument("--reg-weights", nargs="+", type=float, default=[1.0],
                   help="regularization-weight grid (reference's λ list)")
    p.add_argument("--max-iterations", type=int, default=80)
    p.add_argument("--tolerance", type=float, default=1e-7)
    p.add_argument("--normalization", default="NONE",
                   choices=[n.name for n in NormalizationType])
    p.add_argument("--data-validation", default="VALIDATE_FULL",
                   choices=[v.name for v in DataValidationType])
    p.add_argument("--evaluators", nargs="+", default=None,
                   help="evaluator specs; first is primary; defaults per task")
    p.add_argument("--variance", default="SIMPLE",
                   choices=[v.name for v in VarianceComputationType],
                   help="coefficient variances saved with the model")
    p.add_argument("--index-dir", default=None)
    # Diagnostics (reference ⟦.../diagnostics/⟧):
    p.add_argument("--bootstrap-replicates", type=int, default=0,
                   help="0 disables bootstrap CIs")
    p.add_argument("--bootstrap-confidence", type=float, default=0.95)
    p.add_argument("--hl-bins", type=int, default=10,
                   help="Hosmer-Lemeshow bins (logistic task only)")
    p.add_argument("--no-report", action="store_true",
                   help="skip the HTML fit report")
    p.add_argument("--offset-column", default="offset")
    p.add_argument("--weight-column", default="weight")
    p.add_argument("--response-column", default="response")
    p.add_argument("--uid-column", default="uid")
    p.add_argument("--dtype", default="float32", choices=["float32", "float64"])
    p.add_argument("--devices", type=int, default=1,
                   help="out-of-core route only: stream row chunks sharded "
                        "over this many devices (0 = all visible, 1 = single "
                        "device; the device count must divide "
                        "--row-chunk-rows) — P1 data parallelism x "
                        "out-of-core")
    p.add_argument("--row-chunk-rows", type=int, default=-1,
                   help="out-of-core training: keep the ELL arrays "
                        "host-resident in row chunks of this size and stream "
                        "them through the accelerator per optimizer pass "
                        "(datasets beyond device memory; LBFGS+L2, "
                        "normalization/variance NONE). 0 = always in-core; "
                        "-1 = auto (accelerator backends route here when the "
                        "input file size exceeds "
                        "$PHOTON_DEVICE_DATA_BUDGET_GB, default 10)")
    from photon_tpu.cli.params import (
        add_backend_policy_flag,
        add_compilation_cache_flag,
        add_telemetry_flag,
        add_trace_flag,
    )

    add_backend_policy_flag(p)
    add_compilation_cache_flag(p)
    add_telemetry_flag(p)
    add_trace_flag(p)
    return p


def _default_evaluators(task: TaskType) -> tuple[str, ...]:
    return {
        TaskType.LOGISTIC_REGRESSION: ("AUC", "LOGISTIC_LOSS"),
        TaskType.LINEAR_REGRESSION: ("RMSE",),
        TaskType.POISSON_REGRESSION: ("POISSON_LOSS",),
        TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM: ("AUC",),
    }[task]


def _save_best(args, imap, shard_cfg, best, logger) -> None:
    """Persist the selected model as a standard single-coordinate GAME model
    plus its mmap index — shared by the in-core and out-of-core routes."""
    from photon_tpu.game.coordinates import FixedEffectModel
    from photon_tpu.game.descent import GameModel

    with Timed("save model", logger):
        gm = GameModel(models={
            "fixed": FixedEffectModel(model=best, feature_shard=SHARD)
        })
        save_game_model(
            os.path.join(args.output_dir, "best"), gm,
            {SHARD: imap}, {"fixed": SHARD}, {SHARD: shard_cfg},
        )
        idir = os.path.join(args.output_dir, "index", SHARD)
        if isinstance(imap, MmapIndexMap):
            if not os.path.exists(idir):
                import shutil

                shutil.copytree(imap.store_dir, idir)
        else:
            build_mmap_index(imap, idir)


def _ooc_unsupported_flag(args):
    """``(flag, wanted, got)`` for the first flag the out-of-core route
    cannot honor, else None. ONE source of truth shared by the auto-router
    (which must fall back in-core, never error, on a config that worked
    before OOC existed) and by ``_run_out_of_core`` (which fails loudly on
    an EXPLICIT --row-chunk-rows request it cannot honor)."""
    # Optimizer↔regularization pairing mirrors the in-core rules: smooth
    # L-BFGS takes L2; orthant-wise OWL-QN takes any L1 component (or pure
    # L2). TRON stays in-core (trust-region Hessian passes).
    ok_pairs = {
        ("LBFGS", "L2"), ("OWLQN", "L1"), ("OWLQN", "ELASTIC_NET"),
        ("OWLQN", "L2"),
    }
    if (args.optimizer, args.regularization) not in ok_pairs:
        if args.optimizer not in ("LBFGS", "OWLQN"):
            return "--optimizer", "LBFGS|OWLQN", args.optimizer
        return ("--regularization",
                "L2" if args.optimizer == "LBFGS" else "L1|ELASTIC_NET|L2",
                args.regularization)
    for flag, want, got in (
        ("--normalization", "NONE", args.normalization),
        ("--variance", "NONE", args.variance),
        ("--dtype", "float32", args.dtype),
    ):
        if got != want:
            return flag, want, got
    if args.bootstrap_replicates:
        return "--bootstrap-replicates", "0", str(args.bootstrap_replicates)
    return None


def _run_out_of_core(args, task, imap, shard_cfg, chunk_rows, logger) -> dict:
    """Out-of-core fixed-effect route (optim/out_of_core.py): host-resident
    row chunks streamed per pass — for datasets a single device's memory
    cannot hold. Supports L2/LBFGS (the config-5 scale shape) and
    L1/elastic-net/OWLQN (config 2 at scale); anything needing in-core
    data (normalization, variances, bootstrap, TRON) raises loudly instead
    of silently degrading."""
    import jax.numpy as jnp

    from photon_tpu.io.streaming import StreamingAvroReader
    from photon_tpu.optim.out_of_core import (
        ChunkedGLMData,
        run_out_of_core,
        scores_out_of_core,
    )

    bad = _ooc_unsupported_flag(args)
    if bad is not None:
        flag, want, got = bad
        raise ValueError(
            f"out-of-core training supports {flag}={want} only "
            f"(got {got}); pass --row-chunk-rows 0 to force in-core"
        )

    columns = InputColumnNames(
        uid=args.uid_column,
        response=args.response_column,
        offset=args.offset_column,
        weight=args.weight_column,
    )
    sreader = StreamingAvroReader(
        {SHARD: imap}, {SHARD: shard_cfg}, columns, (),
        chunk_rows=chunk_rows, capture_uids=False,
    )
    value_dtype = os.environ.get("PHOTON_VALUE_DTYPE")
    validation = DataValidationType[args.data_validation]
    # P1 x out-of-core: chunks stream row-sharded over a data mesh
    # (--devices N / 0 = all); the device count must divide chunk_rows.
    # Checked HERE, before hours of streaming decode — the solver's own
    # check would only fire after the whole dataset is in host RAM.
    from photon_tpu.cli.params import mesh_from_flags

    mesh = mesh_from_flags(getattr(args, "devices", 1))
    if mesh is not None:
        if chunk_rows % mesh.devices.size != 0:
            raise ValueError(
                f"--row-chunk-rows {chunk_rows} must be divisible by the "
                f"{mesh.devices.size}-device data mesh (--devices) for "
                "row-sharded streaming"
            )
        logger.info("out-of-core streaming over %d-device data mesh",
                    mesh.devices.size)

    # Same --data-validation contract as the in-core path, applied to each
    # ASSEMBLED fixed-shape chunk THE MOMENT it exists (fail fast: a NaN in
    # the first chunk of a 100M-row stream raises within seconds, not after
    # the whole dataset is decoded into host RAM). Chunks share one shape,
    # so the jitted violation counts compile once per ELL width (the width
    # can grow a few times mid-stream). Padding rows carry weight 0 / ghost
    # columns, the same convention the in-core bundle batch is validated
    # under. SAMPLE mode slices HOST-side so only the sampled rows cross to
    # the device; DISABLED transfers nothing.
    from photon_tpu.data.batch import LabeledBatch, SparseFeatures
    from photon_tpu.data.validators import SAMPLE_ROWS_DEFAULT

    def _validate_chunk(i, c, lab, off, wgt):
        if validation is DataValidationType.VALIDATE_SAMPLE:
            idx, val = c.idx[:SAMPLE_ROWS_DEFAULT], c.val[:SAMPLE_ROWS_DEFAULT]
            lab = lab[:SAMPLE_ROWS_DEFAULT]
            off = off[:SAMPLE_ROWS_DEFAULT]
            wgt = wgt[:SAMPLE_ROWS_DEFAULT]
        else:
            idx, val = c.idx, c.val
        sanity_check_data(
            LabeledBatch(
                features=SparseFeatures(idx=jnp.asarray(idx),
                                        val=jnp.asarray(val),
                                        dim=len(imap)),
                labels=lab,
                offsets=off,
                weights=wgt,
            ),
            task, validation,
        )

    on_chunk = (
        None if validation is DataValidationType.VALIDATE_DISABLED
        else _validate_chunk
    )
    with Timed("stream training data (host chunks, validated)", logger):
        data = ChunkedGLMData.from_stream(
            sreader.iter_chunks(args.train_data), SHARD, len(imap),
            chunk_rows=chunk_rows,
            value_dtype=jnp.dtype(value_dtype) if value_dtype else None,
            on_chunk=on_chunk,
        )
    logger.info(
        "out-of-core: %d rows in %d chunks, %.2f GB streamed per pass",
        data.n_rows, data.n_chunks, data.streamed_bytes_per_pass() / 1e9,
    )

    suite = EvaluationSuite.parse(
        list(args.evaluators or _default_evaluators(task))
    )
    reg = RegularizationContext(
        RegularizationType[args.regularization],
        elastic_net_alpha=args.elastic_net_alpha,
    )

    # Evaluation labels/weights: validation set in-core if given (it is
    # normally far smaller than train), else streamed train scores.
    val_batch = None
    if args.validation_data:
        reader = AvroDataReader({SHARD: imap}, {SHARD: shard_cfg},
                                columns=columns)
        with Timed("read validation data", logger):
            val_batch = reader.read(
                args.validation_data, capture_uids=False
            ).batch(SHARD)

    sweep, models, best_i = [], [], 0
    with Timed("regularization sweep (out-of-core)", logger):
        for i, lam in enumerate(args.reg_weights):
            problem = GLMOptimizationProblem(
                task=task,
                optimizer_type=OptimizerType[args.optimizer],
                optimizer_config=OptimizerConfig(
                    max_iterations=args.max_iterations,
                    tolerance=args.tolerance,
                ),
                regularization=reg,
                reg_weight=lam,
            )
            # Per-λ per-iteration checkpoint: a config-5-scale solve runs
            # for hours, so a killed driver's rerun resumes at iteration k
            # (the state fingerprint guards against data/config drift; λ
            # rides the filename).
            ck_dir = os.path.join(args.output_dir, "ooc_checkpoints")
            os.makedirs(ck_dir, exist_ok=True)
            model, result = run_out_of_core(
                problem, data,
                progress=lambda it, f, gn, p: logger.info(
                    "λ=%g iter %d: f=%.6g |g|=%.3g passes=%d", lam, it, f,
                    gn, p,
                ),
                checkpoint_path=os.path.join(ck_dir, f"lam_{lam:g}.npz"),
                mesh=mesh,
            )
            if val_batch is not None:
                scores = model.compute_score(
                    val_batch.features, val_batch.offsets
                )
                ev = suite.evaluate(scores, val_batch.labels,
                                    val_batch.weights)
            else:
                scores = scores_out_of_core(data, model.coefficients.means)
                ev = suite.evaluate(
                    scores, data.labels_np(), data.weights_np()
                )
            sweep.append({
                "reg_weight": lam,
                "iterations": int(result.iterations),
                "objective": float(result.value),
                "data_passes": int(result.data_passes),
                **{k: float(v) for k, v in ev.values.items()},
            })
            models.append(model)
            if i > 0 and suite.primary.better_than(
                ev.primary, sweep[best_i][suite.primary.name]
            ):
                best_i = i
            logger.info("λ=%g: %s", lam, sweep[-1])
    best, best_lam = models[best_i], args.reg_weights[best_i]
    logger.info("selected λ=%g (%s)", best_lam, suite.primary.name)

    _save_best(args, imap, shard_cfg, best, logger)

    summary = {
        "task": task.name,
        "mode": "out_of_core",
        "row_chunk_rows": chunk_rows,
        "n_rows": data.n_rows,
        "n_chunks": data.n_chunks,
        "streamed_gb_per_pass": round(
            data.streamed_bytes_per_pass() / 1e9, 3),
        "selected_reg_weight": best_lam,
        "sweep": sweep,
        "evaluation": sweep[best_i],
        "model_dir": os.path.join(args.output_dir, "best"),
    }
    with open(os.path.join(args.output_dir, "training-summary.json"),
              "w") as f:
        json.dump(summary, f, indent=2)
    return summary


def run(argv: Optional[Sequence[str]] = None) -> dict:
    args = build_arg_parser().parse_args(argv)
    from photon_tpu.cli.params import (
        enable_backend_guard,
        enable_compilation_cache,
        enable_telemetry,
        enable_trace,
        finish_telemetry,
        finish_trace,
    )

    # Fail-fast backend gate before anything can wedge in backend init
    # (PHOTON_BACKEND_INIT_TIMEOUT_S hard deadline; docs/robustness.md).
    enable_backend_guard(args)
    enable_compilation_cache(args.compilation_cache_dir)
    enable_telemetry(args, role="glm-training")
    enable_trace(args.trace_out)
    try:
        return _run(args)
    finally:
        finish_trace(args.trace_out)
        finish_telemetry(args)


def _run(args) -> dict:
    if args.dtype == "float64":
        import jax

        jax.config.update("jax_enable_x64", True)
    task = TaskType[args.task]
    os.makedirs(args.output_dir, exist_ok=True)
    with PhotonLogger(args.output_dir) as logger:
        shard_spec = parse_feature_shard(args.feature_shard)
        if shard_spec.shard != SHARD:
            raise ValueError(
                f"the single-GLM driver uses one shard named '{SHARD}', got "
                f"{shard_spec.shard!r}"
            )
        shard_cfg = FeatureShardConfig(
            feature_bags=shard_spec.feature_bags,
            add_intercept=shard_spec.add_intercept,
        )
        if args.index_dir:
            imap = MmapIndexMap(os.path.join(args.index_dir, SHARD))
        else:
            imap = build_index_from_avro(
                args.train_data,
                feature_bags=shard_cfg.feature_bags,
                add_intercept=shard_cfg.add_intercept,
            )
        logger.info("index: %d features", len(imap))

        ooc_rows = args.row_chunk_rows
        if ooc_rows < 0:
            import jax

            budget_gb = float(
                os.environ.get("PHOTON_DEVICE_DATA_BUDGET_GB", "10")
            )
            from photon_tpu.io.data_reader import _expand_paths

            total = sum(
                os.path.getsize(f) for f in _expand_paths(args.train_data)
            )
            # On-disk Avro bytes UNDERESTIMATE device footprint (deflate
            # blocks commonly shrink 3-5x; decoded ELL adds padding), so
            # the auto-route applies a conservative expansion factor.
            expand = float(
                os.environ.get("PHOTON_AVRO_EXPANSION_FACTOR", "4")
            )
            est = total * expand
            on_accel = jax.default_backend() in REAL_ACCELERATOR_BACKENDS
            ooc_rows = (1 << 20) if (
                on_accel and est > budget_gb * 1e9
            ) else 0
            bad = _ooc_unsupported_flag(args) if ooc_rows else None
            if bad is not None:
                # Auto-routing must never turn a formerly working in-core
                # run into a hard ValueError: any flag the OOC loop cannot
                # honor keeps the run in-core (the pre-OOC behavior — it may
                # OOM if the estimate was right, which is the same failure
                # the user had before) and says why.
                logger.warning(
                    "train data est. %.1f GB decoded exceeds device budget "
                    "%.0f GB but %s=%s requires the in-core path; staying "
                    "in-core (set %s=%s to enable out-of-core streaming — "
                    "forcing with --row-chunk-rows N also needs that flag)",
                    est / 1e9, budget_gb, bad[0], bad[2], bad[0], bad[1],
                )
                ooc_rows = 0
            if ooc_rows:
                logger.info(
                    "train data %.1f GB on disk (est. %.1f GB decoded) "
                    "exceeds device budget %.0f GB: out-of-core path "
                    "(chunk %d rows)",
                    total / 1e9, est / 1e9, budget_gb, ooc_rows,
                )
        if ooc_rows:
            return _run_out_of_core(args, task, imap, shard_cfg, ooc_rows,
                                    logger)

        reader = AvroDataReader(
            {SHARD: imap},
            {SHARD: shard_cfg},
            columns=InputColumnNames(
                uid=args.uid_column,
                response=args.response_column,
                offset=args.offset_column,
                weight=args.weight_column,
            ),
        )
        read_dtype = np.float64 if args.dtype == "float64" else np.float32
        with Timed("read training data", logger):
            # Training never reads the uid column (same memory contract as
            # the GAME training driver).
            train = reader.read(
                args.train_data, dtype=read_dtype, capture_uids=False
            )
        batch = train.batch(SHARD)
        sanity_check_data(batch, task, DataValidationType[args.data_validation])
        # No-op off-accelerator; on TPU the solves run the MXU-friendly
        # sparse layouts instead of the generic gather/scatter.
        batch = batch.with_accelerator_paths()
        val_batch = None
        if args.validation_data:
            with Timed("read validation data", logger):
                val_batch = reader.read(
                    args.validation_data, dtype=read_dtype,
                    capture_uids=False,
                ).batch(SHARD)

        import jax.numpy as jnp

        # One stats pass serves both the normalization context and the
        # feature-importance diagnostic.
        stats = compute_feature_statistics(batch)
        norm = None
        if NormalizationType[args.normalization] != NormalizationType.NONE:
            norm = context_from_statistics(
                stats, NormalizationType[args.normalization],
                imap.intercept_index,
            )

        suite = EvaluationSuite.parse(
            list(args.evaluators or _default_evaluators(task))
        )
        reg = RegularizationContext(
            RegularizationType[args.regularization],
            elastic_net_alpha=args.elastic_net_alpha,
        )
        opt_type = OptimizerType[args.optimizer]
        d = batch.features.dim
        w0 = jnp.zeros((d,), batch.labels.dtype)

        def make_problem(lam: float, variance: VarianceComputationType):
            return GLMOptimizationProblem(
                task=task,
                optimizer_type=opt_type,
                optimizer_config=OptimizerConfig(
                    max_iterations=args.max_iterations,
                    tolerance=args.tolerance,
                ),
                regularization=reg,
                reg_weight=lam,
                variance_type=variance,
            )

        eval_batch = val_batch if val_batch is not None else batch
        sweep, best_i = [], 0
        models = []
        # Sweep with variances OFF (reg_weight is a dynamic jit argument, so
        # the whole grid shares one compiled solve); the winner's variances
        # are computed once afterwards via a warm-started refit.
        with Timed("regularization sweep", logger):
            for i, lam in enumerate(args.reg_weights):
                model, result = make_problem(
                    lam, VarianceComputationType.NONE
                ).fit(batch, w0, normalization=norm)
                scores = model.compute_score(
                    eval_batch.features, eval_batch.offsets
                )
                ev = suite.evaluate(scores, eval_batch.labels, eval_batch.weights)
                sweep.append({
                    "reg_weight": lam,
                    "iterations": int(result.iterations),
                    "objective": float(result.value),
                    **{k: float(v) for k, v in ev.values.items()},
                })
                models.append(model)
                if suite.primary.better_than(
                    ev.primary, sweep[best_i][suite.primary.name]
                ) and i > 0:
                    best_i = i
                logger.info("λ=%g: %s", lam, sweep[-1])
        best = models[best_i]
        best_lam = args.reg_weights[best_i]
        logger.info("selected λ=%g (%s)", best_lam, suite.primary.name)
        variance_type = VarianceComputationType[args.variance]
        if variance_type != VarianceComputationType.NONE:
            with Timed("selected-model variances", logger):
                best, _ = make_problem(best_lam, variance_type).fit(
                    batch, best.coefficients.means, normalization=norm
                )

        # ---- diagnostics on the selected model (reference ⟦diagnostics/⟧)
        from photon_tpu.diagnostics import (
            bootstrap_coefficients,
            feature_importance,
            hosmer_lemeshow,
            write_fit_report,
        )

        boot = None
        if args.bootstrap_replicates > 0:
            with Timed("bootstrap CIs", logger):
                boot = bootstrap_coefficients(
                    make_problem(best_lam, VarianceComputationType.NONE),
                    batch, w0,
                    n_replicates=args.bootstrap_replicates,
                    confidence=args.bootstrap_confidence,
                    normalization=norm,
                )
        hl = None
        if task == TaskType.LOGISTIC_REGRESSION and args.hl_bins > 1:
            scores = best.compute_score(eval_batch.features, eval_batch.offsets)
            hl = hosmer_lemeshow(scores, eval_batch.labels, n_bins=args.hl_bins,
                                 weights=eval_batch.weights)
            logger.info("Hosmer-Lemeshow: stat=%.3f df=%d p=%.4f",
                        hl.statistic, hl.df, hl.p_value)
        imp = feature_importance(np.asarray(best.coefficients.means), stats)

        _save_best(args, imap, shard_cfg, best, logger)

        report_path = None
        if not args.no_report:
            names = [imap.get_feature(j) for j in range(len(imap))]
            report_path = write_fit_report(
                args.output_dir,
                task=task.name,
                feature_names=[f"{n}:{t}" if t else n for n, t in names],
                coefficients=np.asarray(best.coefficients.means),
                config_summary={
                    "optimizer": opt_type.name,
                    "regularization": reg.reg_type.name,
                    "selected_reg_weight": best_lam,
                    "normalization": args.normalization,
                    "dtype": args.dtype,
                    "n_rows": train.n_rows,
                    "n_features": d,
                },
                sweep_metrics=sweep,
                bootstrap=boot,
                hosmer_lemeshow=hl,
                importance=imp,
            )
            logger.info("fit report: %s", report_path)

        summary = {
            "task": task.name,
            "selected_reg_weight": best_lam,
            "sweep": sweep,
            "evaluation": sweep[best_i],
            "hosmer_lemeshow_p": None if hl is None else hl.p_value,
            "report": report_path,
            "model_dir": os.path.join(args.output_dir, "best"),
        }
        with open(os.path.join(args.output_dir, "training-summary.json"), "w") as f:
            json.dump(summary, f, indent=2)
        return summary


def main() -> None:  # pragma: no cover - console entry
    from photon_tpu.cli.params import console_main

    console_main(run)


if __name__ == "__main__":  # pragma: no cover
    main()
