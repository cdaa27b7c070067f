"""GAME training driver: the end-to-end CLI training pipeline.

Parity: reference ⟦photon-client/.../cli/game/training/GameTrainingDriver.scala⟧
(SURVEY.md §3.1): parse params → read Avro training (+validation) data through
feature index maps → optional normalization from feature statistics → data
sanity checks → GameEstimator.fit over the optimization-config sweep → select
best by the primary evaluator → save model(s) + index maps + metrics.

TPU-first: no spark-submit — a plain console entry point; the device mesh
replaces the executor fleet (``--devices`` chooses how many chips the data
axis spans). Index maps are saved next to the model so the scoring driver is
self-contained.

Usage example:

    python -m photon_tpu.cli.game_training_driver \
      --train-data data/train --validation-data data/val \
      --output-dir out --task LOGISTIC_REGRESSION \
      --feature-shard global:features \
      --coordinate "fixed:type=fixed,shard=global,reg=L2,reg_weights=0.1|1|10" \
      --coordinate "perUser:type=random,re_type=userId,shard=global,reg=L2,reg_weights=1" \
      --evaluators AUC LOGISTIC_LOSS --sweeps 2 --output-mode BEST
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
from contextlib import contextmanager
from typing import Optional, Sequence

import numpy as np

from photon_tpu.cli.params import (
    configs_from_specs,
    parse_coordinates,
    parse_feature_shard,
)
from photon_tpu.data.normalization import NormalizationType
from photon_tpu.data.validators import DataValidationType, sanity_check_data
from photon_tpu.estimators import (
    GameEstimator,
    RandomEffectDataConfig,
    fit_breakdown,
    select_best,
)
from photon_tpu.evaluation import EvaluationSuite
from photon_tpu.index.index_map import MmapIndexMap, build_mmap_index
from photon_tpu.io.data_reader import (
    AvroDataReader,
    FeatureShardConfig,
    build_index_from_avro,
)
from photon_tpu.io.model_io import save_game_model
from photon_tpu.obs import recent_trees
from photon_tpu.types import TaskType
from photon_tpu.utils import PhotonLogger, Timed, write_metrics_jsonl


def build_arg_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="game-training-driver",
        description="Train a GAME (GLMix) model on TPU.",
    )
    p.add_argument("--train-data", nargs="+", required=True,
                   help="Avro files/dirs/globs with training data")
    p.add_argument("--validation-data", nargs="+", default=None)
    p.add_argument("--output-dir", required=True)
    p.add_argument("--task", required=True,
                   choices=[t.name for t in TaskType])
    p.add_argument("--feature-shard", action="append", default=None,
                   metavar="SHARD[:BAG+BAG][:no-intercept]",
                   help="feature shard spec (repeatable); default 'global:features'")
    p.add_argument("--coordinate", action="append", required=True,
                   metavar="CID:K=V,...",
                   help="coordinate spec mini-DSL (repeatable); see cli/params.py")
    p.add_argument("--update-sequence", default=None,
                   help="comma-separated coordinate order (default: flag order)")
    p.add_argument("--sweeps", type=int, default=1,
                   help="coordinate-descent sweeps (reference coordinateDescentIterations)")
    p.add_argument("--evaluators", nargs="+", default=None,
                   help="evaluator specs; first is primary (AUC, RMSE, AUC:col, PRECISION@k:col)")
    p.add_argument("--normalization", default="NONE",
                   choices=[n.name for n in NormalizationType])
    p.add_argument("--data-validation", default="VALIDATE_FULL",
                   choices=[v.name for v in DataValidationType])
    p.add_argument("--output-mode", default="BEST", choices=["BEST", "ALL"],
                   help="save only the selected model or every swept config")
    p.add_argument("--model-input-dir", default=None,
                   help="warm-start GAME model directory (reference modelInputDirectory)")
    p.add_argument("--tuning", default=None, choices=["gp", "random"],
                   help="auto-tune per-coordinate reg weights instead of grid sweep")
    p.add_argument("--tuning-iterations", type=int, default=10)
    p.add_argument("--tuning-range", action="append", default=None,
                   metavar="CID:MIN:MAX",
                   help="reg-weight search range per coordinate (repeatable; log scale)")
    p.add_argument("--index-dir", default=None,
                   help="prebuilt per-shard mmap index maps (else built from training data)")
    p.add_argument("--checkpoint-dir", default=None,
                   help="enable step-level checkpointing; a restarted run with "
                        "the same args auto-resumes from the newest snapshot")
    p.add_argument("--devices", type=int, default=0,
                   help="data-parallel mesh size; 0 = all visible devices, 1 = no mesh")
    p.add_argument("--mesh", default=None, metavar="data=4,model=2",
                   help="explicit 2D mesh axes; a 'model' axis shards fixed-effect "
                        "coefficients/optimizer state over it (overrides --devices)")
    p.add_argument("--offset-column", default="offset")
    p.add_argument("--weight-column", default="weight")
    p.add_argument("--response-column", default="response")
    p.add_argument("--uid-column", default="uid")
    p.add_argument("--profile-dir", default=None,
                   help="write a jax.profiler trace of the run to this "
                        "directory (viewable in TensorBoard / Perfetto; "
                        "reference parity: Timed/PhotonLogger sections -> "
                        "on-device profiler, SURVEY.md §5.1)")
    p.add_argument("--debug-nans", action="store_true",
                   help="enable jax_debug_nans: any NaN produced on device "
                        "raises at the op that made it instead of "
                        "propagating (SURVEY.md §5.2 numeric guards; slows "
                        "training — debugging aid only)")
    p.add_argument("--dtype", default="float32", choices=["float32", "float64"],
                   help="training precision. float64 enables jax x64 and "
                        "matches the reference's double-precision (Breeze) "
                        "convergence semantics; float32 is the TPU-fast "
                        "default with a convergence floor around 1e-6 "
                        "relative (documented in tests/test_precision.py)")
    p.add_argument("--max-restarts", type=int, default=0,
                   help="restart the pipeline up to N times on retryable "
                        "failures (device/runtime/IO errors); pair with "
                        "--checkpoint-dir so each attempt resumes past "
                        "completed coordinate steps instead of recomputing "
                        "(the reference's Spark task-retry/lineage recovery, "
                        "SURVEY.md §5.3, as checkpoint-restart)")
    p.add_argument("--restart-backoff", type=float, default=5.0,
                   help="seconds before the first restart (doubles each time)")
    p.add_argument("--heartbeat-dir", default=None,
                   help="shared directory for multi-host liveness beacons; "
                        "each process writes a heartbeat file and restart "
                        "attempts fail fast with the dead-host list instead "
                        "of hanging in a collective (SURVEY.md §5.3)")
    p.add_argument("--ingest-workers", type=int, default=0,
                   help="decode input files with this many worker processes "
                        "(native block decoder per worker, file-sharded; "
                        "the reference's per-executor-core split decode, "
                        "SURVEY.md §2.3/§2.6); 0/1 = in-process")
    p.add_argument("--prefetch-depth", type=int, default=None,
                   help="chunks the ingest pipeline decodes AHEAD on a "
                        "background thread (io/prefetch.py): block decode of "
                        "chunk N+1 overlaps downstream work on chunk N. "
                        "Default PHOTON_PREFETCH_DEPTH (2); 0 = sequential "
                        "decode (the pre-pipeline behavior)")
    p.add_argument("--sweep-cache-mb", type=float, default=None,
                   help="device-resident sweep cache budget in MB "
                        "(data/device_cache.py): multi-sweep training pins "
                        "host-resident coordinate data on device after "
                        "sweep 0 instead of re-uploading per sweep. Default "
                        "PHOTON_SWEEP_CACHE_MB (2048); 0 disables")
    p.add_argument("--bf16-feed", action="store_true",
                   help="transfer feature VALUES host->device as bfloat16 "
                        "(half the hot-path transfer bytes); solves "
                        "accumulate in float32 via dtype promotion. Opt-in: "
                        "continuous features round to 8 mantissa bits "
                        "(tolerance documented in tests/test_prefetch.py). "
                        "Incompatible with --dtype float64")
    p.add_argument("--feature-summary", action="store_true",
                   help="write per-feature summary statistics (mean/var/min/"
                        "max/nnz) for every shard to <output-dir>/summary/"
                        "<shard>.avro (reference FeatureSummarizationResultAvro "
                        "output, SURVEY.md §3.1 feature-summarization stage)")
    from photon_tpu.cli.params import (
        add_backend_policy_flag,
        add_compilation_cache_flag,
        add_compile_store_flag,
        add_distributed_flags,
        add_fault_plan_flag,
        add_re_routing_flags,
        add_telemetry_flag,
        add_trace_flag,
    )

    add_backend_policy_flag(p)
    add_distributed_flags(p)
    add_compilation_cache_flag(p)
    add_compile_store_flag(p)
    add_fault_plan_flag(p)
    add_re_routing_flags(p)
    add_telemetry_flag(p)
    add_trace_flag(p)
    return p


@contextmanager
def _checkpointing(directory: Optional[str]):
    """Optional CheckpointManager lifecycle: close on success; on failure
    drain without masking the original error (a leaked writer thread would
    race a retrying supervisor's fresh manager on the same directory)."""
    if not directory:
        yield None
        return
    from photon_tpu.checkpoint import CheckpointManager

    mgr = CheckpointManager(directory)
    try:
        yield mgr
    except BaseException:
        try:
            mgr.close()
        except Exception:
            pass
        raise
    else:
        mgr.close()


def _load_or_build_indexes(args, shard_specs, logger):
    shard_cfgs = {
        s.shard: FeatureShardConfig(
            feature_bags=s.feature_bags, add_intercept=s.add_intercept
        )
        for s in shard_specs
    }
    index_maps = {}
    if args.index_dir:
        for shard in shard_cfgs:
            index_maps[shard] = MmapIndexMap(os.path.join(args.index_dir, shard))
            logger.info("index[%s]: loaded %d features (mmap)",
                        shard, len(index_maps[shard]))
    else:
        for shard, cfg in shard_cfgs.items():
            index_maps[shard] = build_index_from_avro(
                args.train_data,
                feature_bags=cfg.feature_bags,
                add_intercept=cfg.add_intercept,
            )
            logger.info("index[%s]: built %d features from training data",
                        shard, len(index_maps[shard]))
    return shard_cfgs, index_maps


def run(argv: Optional[Sequence[str]] = None) -> dict:
    """Run training; returns a result summary dict (also written to disk)."""
    args = build_arg_parser().parse_args(argv)
    from photon_tpu.cli.params import (
        enable_backend_guard,
        enable_compilation_cache,
        enable_compile_store,
        enable_fault_plan,
        enable_re_routing,
        enable_telemetry,
        enable_trace,
    )

    # Backend policy FIRST — the fail-fast probe (hard
    # PHOTON_BACKEND_INIT_TIMEOUT_S deadline) must gate the process before
    # anything can initialize a backend in-process and wedge.
    enable_backend_guard(args)
    enable_compilation_cache(args.compilation_cache_dir)
    # AOT compile store (after the cache flag so an explicit
    # --compilation-cache-dir stays the artifact layer): records every
    # blessed-kernel compile and pre-warms restarts/recoveries from it
    # (docs/robustness.md §"Recovery time"). On by default for every run
    # that can RESTART (supervised restarts, checkpoint resume) — the only
    # flows that re-enter compiled state — and opt-in via --compile-store
    # for one-shot runs.
    if args.compile_store or args.checkpoint_dir or args.max_restarts > 0:
        enable_compile_store(args, output_dir=args.output_dir)
    enable_fault_plan(args.fault_plan)
    enable_re_routing(args, output_dir=args.output_dir)
    # Fleet role + trace-shard placement BEFORE the collector installs:
    # the anchor event is stamped at install (docs/observability.md
    # §"Fleet view").
    enable_telemetry(args, role="training")
    enable_trace(args.trace_out)
    # Join the multi-host runtime first (no-op single-process) so
    # jax.devices() below sees the whole pod slice (SURVEY.md §5.8).
    # Bring-up failure is never silent: classified + journaled, and
    # --distributed-policy decides exit-2 vs degrade-to-single-host
    # (docs/scaling.md §"Multi-host mesh").
    from photon_tpu.parallel.distributed import initialize_distributed
    from photon_tpu.supervisor import RecoveryJournal

    os.makedirs(args.output_dir, exist_ok=True)
    initialize_distributed(
        policy=args.distributed_policy,
        journal=RecoveryJournal(
            os.path.join(args.output_dir, "recovery.jsonl")),
    )
    if args.dtype == "float64":
        import jax

        jax.config.update("jax_enable_x64", True)
    if args.debug_nans:
        import jax

        jax.config.update("jax_debug_nans", True)
    task = TaskType[args.task]
    os.makedirs(args.output_dir, exist_ok=True)
    profiling = False
    if args.profile_dir:
        import jax.profiler

        os.makedirs(args.profile_dir, exist_ok=True)
        jax.profiler.start_trace(args.profile_dir)
        profiling = True

    from photon_tpu.supervisor import Heartbeat, RestartPolicy, RunSupervisor

    heartbeat = None
    # SLO rules (docs/observability.md §SLO) ride the beat loop when a
    # config is provided: judged against the global registry snapshot
    # from the daemon thread, at most once a minute, surviving a wedged
    # main thread. The heartbeat IS the training driver's evaluation
    # point, so a config without a heartbeat dir must warn, not go
    # silent — silence is indistinguishable from "all SLOs passing".
    slo_watchdog = None
    slo_path = os.environ.get("PHOTON_SLO_CONFIG")
    if slo_path and not args.heartbeat_dir:
        import logging

        logging.getLogger("photon_tpu").warning(
            "PHOTON_SLO_CONFIG=%s is set but --heartbeat-dir is not: the "
            "training driver judges SLOs on the heartbeat loop, so this "
            "run will evaluate none of them", slo_path)
    if args.heartbeat_dir:
        if slo_path:
            from photon_tpu.obs.analysis.slo import SloConfig, SloWatchdog

            slo_watchdog = SloWatchdog(
                SloConfig.from_file(slo_path), min_interval_s=60.0)
        # Short interval: a retry must be able to tell "peer died with me"
        # from "peer is fine", so the staleness window (3x interval) has to
        # fit inside a restart backoff, not dwarf it. Every beat also
        # refreshes host_beacon_age_seconds{host=...} for the whole pod, so
        # the fleet view shows a dead host as a climbing gauge without
        # anyone reading beacon files (docs/observability.md §Fleet view).
        import jax

        heartbeat = Heartbeat(
            args.heartbeat_dir, interval_seconds=2.0,
            slo_watchdog=slo_watchdog,
            peer_gauges=range(jax.process_count()),
        ).start()

    def attempt(i: int) -> dict:
        if i > 0 and heartbeat is not None:
            import time as _time

            import jax

            # Let a freshly-dead peer's last beat age past the staleness
            # window before judging: the check runs backoff seconds after
            # our failure, so top up to 3x the beat interval if needed.
            settle = max(
                0.0, 3.0 * heartbeat.interval_seconds - args.restart_backoff
            )
            if settle:
                _time.sleep(settle)
            report = heartbeat.check_peers(range(jax.process_count()))
            if not report.healthy:
                raise RestartsUselessError(
                    f"peer hosts dead={report.dead} missing={report.missing}; "
                    "restart the job (checkpoint resume will fast-forward)"
                )
        watchdog = None
        if heartbeat is not None:
            import jax

            # Attempt-epoch barrier: a host may only (re-)enter the solve
            # once EVERY peer advertises the same attempt index. A lone
            # retrier would otherwise issue collectives that mismatch a peer
            # still blocked in the previous attempt's psum — all hosts then
            # hang with perfectly fresh heartbeats, invisible to both the
            # dead-peer check above and the liveness watchdog below.
            heartbeat.set_epoch(i)
            if i > 0 and jax.process_count() > 1:
                # (attempt 0 needs no barrier: the jax.distributed runtime
                # bring-up already synchronized process start.)
                laggards = heartbeat.wait_for_epoch(
                    range(jax.process_count()), i,
                    timeout_seconds=max(30.0, 3 * args.restart_backoff),
                )
                if laggards:
                    raise RestartsUselessError(
                        f"peer hosts {laggards} never reached attempt epoch "
                        f"{i} (wedged in a previous attempt's collective?); "
                        "restart the job (checkpoint resume will "
                        "fast-forward)"
                    )
            if jax.process_count() > 1:
                # LIVE detection (round-3 scope note closed): a psum whose
                # peer died blocks the main thread in C++ forever, so the
                # between-attempts check above can never run while an attempt
                # is wedged. Armed ONLY around the attempt body: between
                # attempts the graceful check_peers path (and the retry
                # loop's diagnostics) stay reachable. The watchdog aborts
                # from a daemon thread (exit 43) and hands recovery to the
                # scheduler restart + checkpoint resume.
                import logging

                watchdog = heartbeat.watchdog(
                    range(jax.process_count()),
                    logger=logging.getLogger("photon_tpu.supervisor"),
                ).start()
        try:
            return _run_inner(args, task)
        finally:
            if watchdog is not None:
                watchdog.stop()

    try:
        if args.max_restarts > 0:
            import logging

            # RunSupervisor (docs/robustness.md §recovery journal): same
            # RestartPolicy/backoff contract as run_with_recovery, plus
            # classified causes, run_restarts_total{cause=...}, recovery.*
            # trace events, and an append-only JSONL journal next to the
            # model — and under --backend-policy failover, a backend-level
            # failure re-probes between attempts and re-enters on CPU
            # instead of burning the whole budget on a wedged grant.
            supervisor = RunSupervisor(
                RestartPolicy(
                    max_restarts=args.max_restarts,
                    backoff_seconds=args.restart_backoff,
                ),
                journal=os.path.join(args.output_dir, "recovery.jsonl"),
                logger=logging.getLogger("photon_tpu.supervisor"),
                failover_policy=args.backend_policy,
            )
            return supervisor.run(attempt)
        return attempt(0)
    finally:
        if heartbeat is not None:
            heartbeat.stop()
        if profiling:
            import jax.profiler

            jax.profiler.stop_trace()
        from photon_tpu.cli.params import finish_telemetry, finish_trace

        finish_trace(args.trace_out)
        finish_telemetry(args)


class RestartsUselessError(Exception):
    """A peer host is gone: in-process retry cannot succeed, so this escapes
    the retry loop (it is not a retryable type) and fails the job fast; the
    outer scheduler restarts all hosts and checkpoint resume takes over."""


def _run_inner(args, task) -> dict:
    with PhotonLogger(args.output_dir) as logger:
        specs = parse_coordinates(args.coordinate)
        data_configs, configs = configs_from_specs(specs)
        update_sequence = (
            tuple(s.strip() for s in args.update_sequence.split(","))
            if args.update_sequence
            else tuple(c.cid for c in specs)
        )
        shard_specs = [
            parse_feature_shard(s)
            for s in (args.feature_shard or ["global:features"])
        ]
        needed = {c.feature_shard for c in data_configs.values()}
        have = {s.shard for s in shard_specs}
        if needed - have:
            raise ValueError(
                f"coordinates use feature shards {sorted(needed - have)} with no "
                f"--feature-shard spec (have {sorted(have)})"
            )

        shard_cfgs, index_maps = _load_or_build_indexes(args, shard_specs, logger)

        id_tags = sorted(
            {
                c.re_type
                for c in data_configs.values()
                if isinstance(c, RandomEffectDataConfig)
            }
            | {
                ev.group_column
                for ev in (
                    EvaluationSuite.parse(args.evaluators).evaluators
                    if args.evaluators
                    else ()
                )
                if ev.group_column
            }
        )
        from photon_tpu.io.data_reader import InputColumnNames

        reader = AvroDataReader(
            index_maps,
            shard_cfgs,
            columns=InputColumnNames(
                uid=args.uid_column,
                response=args.response_column,
                offset=args.offset_column,
                weight=args.weight_column,
            ),
            id_tag_columns=id_tags,
        )

        read_dtype = np.float64 if args.dtype == "float64" else np.float32
        if args.bf16_feed and args.dtype == "float64":
            raise ValueError(
                "--bf16-feed narrows the device feed below float32; it "
                "cannot honor --dtype float64 (pick one)"
            )
        feed_dtype = "bfloat16" if args.bf16_feed else None

        # ONE streaming reader for every pipelined read: its compiled decode
        # programs + per-shard probe tables are config-determined and reused
        # across the train AND validation reads (the old AvroDataReader path
        # made the same guarantee via its cached self._streaming).
        from photon_tpu.io.streaming import StreamingAvroReader

        stream_reader = StreamingAvroReader(
            index_maps, shard_cfgs, reader.columns, id_tags,
            capture_uids=False,
        )

        def read_data(paths):
            from photon_tpu.io.prefetch import (
                default_prefetch_depth,
                read_bundle_pipelined,
            )
            from photon_tpu.io.streaming import Unsupported

            depth = (default_prefetch_depth() if args.prefetch_depth is None
                     else max(0, args.prefetch_depth))
            # Training never reads the uid column; skipping it keeps host
            # memory at the numeric floor (10^8 uid strings would dwarf the
            # ELL arrays themselves).
            try:
                # Pipelined ingest→device path (io/prefetch.py): background
                # block decode (+ the worker pool under --ingest-workers)
                # overlapped with bundle assembly and the device upload;
                # --bf16-feed narrows feature values on the host first.
                return read_bundle_pipelined(
                    index_maps, shard_cfgs, reader.columns, id_tags, paths,
                    dtype=read_dtype, depth=depth,
                    workers=args.ingest_workers, capture_uids=False,
                    feed_dtype=feed_dtype, reader=stream_reader,
                )
            except Unsupported as e:
                logger.info("pipelined ingest unavailable (%s); "
                            "per-record read", e)
            bundle = reader.read(paths, dtype=read_dtype, capture_uids=False)
            if feed_dtype is not None:
                logger.info("--bf16-feed inactive on the per-record "
                            "fallback reader (values stay %s)", read_dtype)
            return bundle

        with Timed("read training data", logger) as t:
            train = read_data(args.train_data)
        logger.info("training rows: %d", train.n_rows)
        validation = None
        if args.validation_data:
            with Timed("read validation data", logger):
                validation = read_data(args.validation_data)
            logger.info("validation rows: %d", validation.n_rows)

        vtype = DataValidationType[args.data_validation]
        with Timed("data validation", logger):
            for shard in needed:
                sanity_check_data(train.batch(shard), task, vtype)

        if args.feature_summary:
            from photon_tpu.data.statistics import compute_feature_statistics
            from photon_tpu.io.model_io import save_feature_summary

            with Timed("feature summarization", logger):
                for shard in sorted(needed):
                    stats = compute_feature_statistics(train.batch(shard))
                    save_feature_summary(
                        os.path.join(args.output_dir, "summary",
                                     f"{shard}.avro"),
                        index_maps[shard], stats,
                    )
                    logger.info("feature summary[%s]: %d features", shard,
                                stats.dim)

        initial_model = None
        if args.model_input_dir:
            from photon_tpu.io.model_io import load_game_model

            with Timed("load warm-start model", logger):
                initial_model, _ = load_game_model(
                    args.model_input_dir, index_maps, dtype=read_dtype
                )

        from photon_tpu.cli.params import mesh_from_flags

        mesh = mesh_from_flags(args.devices, args.mesh)
        if mesh is not None:
            logger.info("mesh: %s", mesh)
        model_axis = (
            "model" if mesh is not None and "model" in mesh.shape else None
        )

        estimator = GameEstimator(
            task=task,
            coordinate_data_configs=data_configs,
            update_sequence=update_sequence,
            n_sweeps=args.sweeps,
            evaluator_specs=tuple(args.evaluators or ()),
            normalization=NormalizationType[args.normalization],
            intercept_indices={
                s: im.intercept_index for s, im in index_maps.items()
            },
            mesh=mesh,
            model_axis=model_axis,
            sweep_cache_mb=args.sweep_cache_mb,
        )

        if args.tuning:
            if not (args.evaluators and validation is not None):
                raise ValueError("--tuning needs --evaluators and --validation-data")
            if not args.tuning_range:
                raise ValueError("--tuning needs at least one --tuning-range CID:MIN:MAX")
            if args.tuning_iterations < 1:
                raise ValueError(
                    f"--tuning-iterations must be >= 1, got {args.tuning_iterations}"
                )
            if len(configs) > 1:
                raise ValueError(
                    "--tuning replaces the reg-weight grid sweep; remove the "
                    "multi-value reg_weights axes from --coordinate specs"
                )
            from photon_tpu.hyperparameter import tune_regularization

            ranges = {}
            for spec in args.tuning_range:
                cid, lo, hi = spec.split(":")
                ranges[cid] = (float(lo), float(hi))
            with _checkpointing(args.checkpoint_dir) as tuning_ckpt, \
                    Timed("hyperparameter tuning", logger) as fit_timer:
                tuning = tune_regularization(
                    estimator, train, validation, configs[0], ranges,
                    n_iterations=args.tuning_iterations,
                    strategy=args.tuning, seed=0,
                    initial_model=initial_model,
                    checkpoint_manager=tuning_ckpt,
                )
            logger.info(
                "tuning best params %s -> %.6g",
                dict(zip(sorted(ranges), tuning.best_params)),
                tuning.search.best_value,
            )
            # The best config's model was already trained during the search.
            results = [tuning.best_result]
        else:
            with _checkpointing(args.checkpoint_dir) as ckpt, \
                    Timed("fit", logger) as fit_timer:
                results = estimator.fit(
                    train,
                    validation if args.evaluators else None,
                    configs,
                    initial_model=initial_model,
                    checkpoint_manager=ckpt,
                )
            # Where the fit's seconds went, from its own span tree.
            parts = fit_breakdown(recent_trees("estimator.fit", last=1)[0])
            fit_s, waited = parts.pop("fit"), parts.pop("waited")
            logger.info(
                "fit %.2f s = %s; host waited on the device %.2f", fit_s,
                " + ".join(f"{name} {seconds:.2f}"
                           for name, seconds in parts.items()), waited)

        suite = (
            EvaluationSuite.parse(args.evaluators) if args.evaluators else None
        )
        best = select_best(results, suite) if suite else results[0]
        # Identity, not equality: results hold JAX arrays whose __eq__ is
        # elementwise, so list.index would raise on any non-first best.
        best_i = next(i for i, r in enumerate(results) if r is best)

        shard_by_coordinate = {
            cid: c.feature_shard for cid, c in data_configs.items()
        }
        saved = {}
        with Timed("save models", logger):
            if args.output_mode == "ALL":
                for i, r in enumerate(results):
                    mdir = os.path.join(args.output_dir, "models", str(i))
                    save_game_model(mdir, r.model, index_maps,
                                    shard_by_coordinate, shard_cfgs)
                    saved[str(i)] = mdir
            bdir = os.path.join(args.output_dir, "best")
            save_game_model(bdir, best.model, index_maps,
                            shard_by_coordinate, shard_cfgs)
            saved["best"] = bdir
            for shard, im in index_maps.items():
                idir = os.path.join(args.output_dir, "index", shard)
                if isinstance(im, MmapIndexMap):
                    # already a store on disk: copy it so the output dir is a
                    # self-contained scoring input
                    if not os.path.exists(idir):
                        import shutil

                        shutil.copytree(im._dir, idir)
                else:
                    build_mmap_index(im, idir)

        summary = {
            "task": task.name,
            "n_configs": len(results),
            "best_config_index": best_i,
            "best_config": {
                cid: dataclasses.asdict(best.config[cid])
                for cid in best.config
            },
            "evaluation": dict(best.evaluation.values) if best.evaluation else None,
            "fit_seconds": fit_timer.seconds,
            "model_dirs": saved,
        }
        # enums are not JSON-serializable through asdict
        summary = json.loads(json.dumps(summary, default=lambda o: getattr(o, "name", str(o))))
        with open(os.path.join(args.output_dir, "training-summary.json"), "w") as f:
            json.dump(summary, f, indent=2)
        write_metrics_jsonl(
            os.path.join(args.output_dir, "metrics.jsonl"),
            (
                {
                    "config": i,
                    "sweep": rec.sweep,
                    "coordinate": rec.coordinate_id,
                    "seconds": rec.seconds,
                    **(rec.convergence or {}),
                    **(rec.validation.values if rec.validation else {}),
                }
                for i, r in enumerate(results)
                for rec in r.tracker
            ),
        )
        logger.info("done; best config %d, evaluation %s", best_i, summary["evaluation"])
        return summary


def main() -> None:  # pragma: no cover - console entry
    from photon_tpu.cli.params import console_main

    console_main(run)


if __name__ == "__main__":  # pragma: no cover
    main()
