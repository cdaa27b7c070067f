"""Coordinate descent: the GAME outer loop.

Parity: reference ⟦photon-api/.../algorithm/CoordinateDescent.scala⟧ (SURVEY.md
§3.3): for each sweep, for each coordinate in the update sequence — remove the
coordinate's own score from the total, train against the residual as offset,
add the new score back; evaluate on validation after every coordinate update
and keep the best model seen.

TPU-first: per-coordinate scores are plain [N] device arrays in a fixed global
sample order, so the reference's score-RDD zip/joins are elementwise adds, and
"subtract own score" is literally ``total - scores[cid]`` (SURVEY.md §2.6 P7).
"""
from __future__ import annotations

import dataclasses
import logging
from typing import Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from photon_tpu.evaluation import EvaluationResults, EvaluationSuite
from photon_tpu.faults import fault_point
from photon_tpu.game.coordinates import Coordinate, DatumScoringModel
from photon_tpu.obs import device_wait, instant, trace_span

Array = jax.Array

logger = logging.getLogger("photon_tpu.game")


@dataclasses.dataclass(frozen=True)
class GameModel:
    """Composite model keyed by coordinate id — reference ⟦GameModel⟧."""

    models: Mapping[str, DatumScoringModel]

    def __getitem__(self, cid: str) -> DatumScoringModel:
        return self.models[cid]

    def keys(self):
        return self.models.keys()


@dataclasses.dataclass
class CoordinateStepRecord:
    """One (sweep, coordinate) step of the tracker — reference
    ⟦OptimizationStatesTracker⟧ + per-step validation logging."""

    sweep: int
    coordinate_id: str
    seconds: float
    validation: Optional[EvaluationResults] = None
    # Solver outcome of the step: {iterations, data_passes, reasons}, and
    # for a TRON step {hvp, cg_steps, rejected} — see ``_solver_outcome``.
    convergence: Optional[dict] = None


def _solver_outcome(result, tron_counters: Optional[dict] = None
                    ) -> Optional[dict]:
    """Host-side summary of one step's ``OptimizerResult``(s): a fixed
    effect returns one, a random effect one per bucket with ``[E]`` leaves.
    ``reasons`` counts solves per convergence-reason name; ``iterations`` is
    the longest solve; ``data_passes`` sums the on-device pass counters. A
    fixed effect solved by TRON adds that solver's own three counters
    (``tron_counters``, as ``_tron_counters`` read them)."""
    from photon_tpu.optim.base import CONVERGENCE_REASON_NAMES

    results = result if isinstance(result, (list, tuple)) else [result]
    if not results or not all(
            hasattr(r, "converged_reason") for r in results):
        return None
    with device_wait("solver_outcome"):
        reasons = np.concatenate(
            [np.asarray(r.converged_reason).ravel() for r in results])
        iterations = int(max(
            np.asarray(r.iterations).max() for r in results))
        data_passes = int(sum(
            np.asarray(r.data_passes).sum() for r in results))
    codes, counts = np.unique(reasons, return_counts=True)
    return {
        "iterations": iterations,
        "data_passes": data_passes,
        "reasons": {CONVERGENCE_REASON_NAMES[int(c)]: int(n)
                    for c, n in zip(codes, counts)},
        **(tron_counters or {}),
    }


def _tron_counters(result) -> dict:
    """``{hvp, cg_steps, rejected}`` of a step solved by TRON (the
    solver's own on-device counters, read to the host); empty for any other
    solve, so that only a TRON step carries them."""
    if getattr(result, "hvp", None) is None:
        return {}
    with device_wait("tron_counters"):
        return {k: int(np.asarray(getattr(result, k)))
                for k in ("hvp", "cg_steps", "rejected")}


@dataclasses.dataclass(frozen=True)
class ValidationData:
    """Validation rows + per-coordinate scorers.

    ``scorers[cid](model) -> [n_rows]`` raw coordinate scores on the
    validation rows (fixed effect: matvec on the validation batch; random
    effect: cross-dataset projection). Built by the estimator.
    """

    labels: Array
    weights: Array
    offsets: Array
    scorers: Mapping[str, object]
    group_ids_by_column: Optional[Mapping[str, Array]] = None
    num_groups_by_column: Optional[Mapping[str, int]] = None


@dataclasses.dataclass(frozen=True)
class CoordinateDescent:
    """Run block-coordinate descent over an ordered update sequence."""

    update_sequence: Sequence[str]
    n_sweeps: int = 1

    def run(
        self,
        coordinates: Mapping[str, Coordinate],
        n_rows: int,
        base_offsets: Optional[Array] = None,
        validation: Optional[ValidationData] = None,
        suite: Optional[EvaluationSuite] = None,
        initial_models: Optional[Mapping[str, DatumScoringModel]] = None,
        checkpointer=None,
        resume: Optional[dict] = None,
        step_base: int = 0,
        checkpoint_meta: Optional[dict] = None,
        extra_state: Optional[dict] = None,
    ) -> tuple[GameModel, list[CoordinateStepRecord]]:
        """``checkpointer`` (a ``photon_tpu.checkpoint.CheckpointManager``)
        snapshots the full descent state after every coordinate step
        (SURVEY.md §5.4 rebuild note); ``resume`` is a payload from
        ``load_latest`` whose position is fast-forwarded past. Resumed runs
        reproduce the uninterrupted run bit-identically.
        """
        with trace_span("descent.run", cat="descent", sweeps=self.n_sweeps,
                        coordinates=len(self.update_sequence)):
            return self._run(
                coordinates, n_rows, base_offsets, validation, suite,
                initial_models, checkpointer, resume, step_base,
                checkpoint_meta, extra_state)

    def _run(self, coordinates, n_rows, base_offsets, validation, suite,
             initial_models, checkpointer, resume, step_base,
             checkpoint_meta, extra_state):
        for cid in self.update_sequence:
            if cid not in coordinates:
                raise ValueError(f"update sequence names unknown coordinate {cid!r}")
        if validation is not None and suite is None:
            raise ValueError("validation data provided without an evaluation suite")

        base = (
            jnp.zeros((n_rows,), jnp.float32)
            if base_offsets is None
            else jnp.asarray(base_offsets)
        )

        resumed_pos = None
        if resume is not None:
            st = resume["state"]
            models = dict(st["models"])
            scores = dict(st["scores"])
            total = st["total"]
            v_cache = dict(st["v_cache"])
            best_metric = st["best_metric"]
            best_models = st["best_models"]
            tracker = list(st["tracker"])
            resumed_pos = (resume["meta"]["sweep"], resume["meta"]["coord_index"])
            logger.info(
                "resuming after sweep %d coordinate %d",
                resumed_pos[0], resumed_pos[1],
            )
        else:
            models = dict(initial_models or {})
            scores = {}
            # Initial scores from warm-start models, else zero. Models OUTSIDE
            # the update sequence are "locked" coordinates (reference partial
            # retraining): scored so residuals are right, never retrained,
            # kept in the output model.
            for cid in self.update_sequence:
                if cid in models:
                    scores[cid] = coordinates[cid].score(models[cid])
                else:
                    scores[cid] = jnp.zeros((n_rows,), base.dtype)
            for cid in sorted(set(models) - set(self.update_sequence)):
                if cid not in coordinates:
                    raise ValueError(
                        f"initial model {cid!r} is outside the update sequence "
                        "and has no coordinate to score it (locked coordinates "
                        "need a coordinate for residual bookkeeping)"
                    )
                scores[cid] = coordinates[cid].score(models[cid])
            total = base + sum(scores.values())
            tracker = []
            best_metric = None
            best_models = None
            # Validation scores cached per coordinate — only the coordinate
            # just trained is re-scored (random-effect cross-dataset
            # projection is host-side work, so re-scoring every coordinate
            # each step is O(C²)).
            v_cache = {
                cid: validation.scorers[cid](models[cid])
                for cid in models
                if validation is not None
            }
        if validation is not None:
            need = set(self.update_sequence) | set(models)
            missing = sorted(c for c in need if c not in validation.scorers)
            if missing:
                raise ValueError(
                    f"validation scorers missing for coordinates {missing}"
                )

        # Retrace-sentinel contract for the RE bucket solvers: sweep 0
        # compiles the whole blessed shape ladder (full-bucket shapes,
        # chunk-ladder shapes, calibration probes — all closed sets), so
        # after the first sweep the kernels are marked warm and ANY further
        # compile is a watched retrace-after-warmup. A new run() (new
        # config / new λ) legitimately re-compiles, so warm state is
        # cleared on entry.
        from photon_tpu.obs import retrace as _retrace

        for k in _retrace.RE_SOLVER_KERNELS:
            _retrace.clear_warm(k)

        step = step_base
        # Device-loss recovery clears the RE kernels' warm marks along with
        # the executable caches; the sentinel re-arms only after the NEXT
        # fully-executed sweep (the recovery sweep's remainder legitimately
        # recompiles shapes whose executables were purged).
        rearm_sweep = None
        for sweep in range(self.n_sweeps):
            # Manual span, not ``with`` (the inner loop body is long): on a
            # mid-sweep exception it is still open when ``descent.run``
            # exits, which ends it there with the error (obs/trace.py).
            sweep_span = trace_span("descent.sweep", cat="descent",
                                    sweep=sweep).__enter__()
            for ci, cid in enumerate(self.update_sequence):
                if resumed_pos is not None and (sweep, ci) <= resumed_pos:
                    step += 1
                    continue
                # Chaos hook: a preemption delivered here kills the attempt
                # between steps — after the previous step's checkpoint, before
                # this one's work — the exact window resume must cover.
                fault_point(
                    "descent.step", sweep=sweep, coordinate=cid, step=step
                )
                coord = coordinates[cid]
                # In-run device-loss recovery (docs/robustness.md): the step
                # body COMMITS (total/scores/models mutate) only after the
                # D2H sync proves the device work completed, so a device
                # loss anywhere inside leaves the pre-step state intact and
                # the step simply re-runs after recovery — bit-identically,
                # because the step is a pure function of that state.
                recoveries = 0
                while True:
                    try:
                        with trace_span(
                            "descent.step", cat="descent", sweep=sweep,
                            coordinate=cid, step=step,
                        ) as step_span:
                            # Chaos hook: error="device_lost" here drives
                            # the in-run path (vs descent.step, whose
                            # preemption kills the whole attempt).
                            fault_point("descent.device", sweep=sweep,
                                        coordinate=cid, step=step)
                            residual_offset = total - scores[cid]
                            model, solve_result = coord.train(
                                residual_offset, models.get(cid))
                            with trace_span("descent.score", cat="descent",
                                            coordinate=cid):
                                new_score = coord.score(model)
                            new_total = residual_offset + new_score
                            # Tiny D2H fetch: the step record (and span) must
                            # report COMPLETED compute, not async dispatch
                            # (without this the tracker claimed ~4s of a 70s
                            # fit); the read forces completion. The data
                            # dependency
                            # new_score <- model <- solve forces the whole
                            # step — and is the commit gate above.
                            with device_wait("step"):
                                np.asarray(new_score[:1])
                            # The step is done: what TRON counted on the
                            # device goes on the span (no other solver's
                            # step carries these arguments), with what the
                            # coordinate says of the data it stepped over.
                            tron_counters = _tron_counters(solve_result)
                            step_span.set(
                                **tron_counters,
                                **getattr(coord, "span_arguments", dict)())
                        total = new_total
                        scores[cid] = new_score
                        models[cid] = model
                        break
                    except Exception as e:  # noqa: BLE001 - classified below
                        from photon_tpu.runtime import backend_guard as _bg

                        if (not _bg.is_device_lost(e)
                                or recoveries >= _bg.max_inrun_recoveries()):
                            raise
                        recoveries += 1
                        # Checkpoint FIRST (pre-step state is still exact),
                        # then clear-and-reenter; a failing snapshot means
                        # the device state is unfetchable and the loss must
                        # escalate to the supervisor restart instead.
                        if checkpointer is not None:
                            try:
                                checkpointer.save(
                                    step,
                                    state={
                                        "models": models,
                                        "scores": scores,
                                        "total": total,
                                        "v_cache": v_cache,
                                        "best_metric": best_metric,
                                        "best_models": best_models,
                                        "tracker": tracker,
                                        **(extra_state or {}),
                                    },
                                    meta={
                                        "phase": "recovery",
                                        "sweep": sweep,
                                        # pre-step state == "resume after
                                        # the previous coordinate"
                                        "coord_index": ci - 1,
                                        **(checkpoint_meta or {}),
                                    },
                                )
                                checkpointer.wait()
                            except KeyboardInterrupt:
                                raise  # a user abort is never "recovery"
                            except Exception:
                                raise e
                        logger.warning(
                            "device lost in sweep %d coord %s (%s: %s); "
                            "in-run recovery %d/%d, re-running the step",
                            sweep, cid, type(e).__name__, e, recoveries,
                            _bg.max_inrun_recoveries(),
                        )
                        _bg.recover_from_device_loss(
                            f"descent sweep {sweep} coord {cid}",
                            logger=logger,
                        )
                        rearm_sweep = sweep + 1
                # Close the supervisor's restart→first-step clock on the
                # FIRST committed step of a supervised attempt (no-op when
                # no clock is armed — runtime/compile_store.py).
                from photon_tpu.runtime.compile_store import note_first_step

                note_first_step("descent.step")
                dt = step_span.seconds

                record = CoordinateStepRecord(
                    sweep, cid, dt,
                    convergence=_solver_outcome(solve_result, tron_counters))
                if validation is not None:
                    with trace_span("descent.validate", cat="descent",
                                    coordinate=cid):
                        with trace_span("validate.score", cat="descent",
                                        coordinate=cid):
                            v_cache[cid] = validation.scorers[cid](model)
                        v_scores = sum(v_cache.values())
                        record.validation = suite.evaluate(
                            validation.offsets + v_scores,
                            validation.labels,
                            validation.weights,
                            validation.group_ids_by_column,
                            validation.num_groups_by_column,
                        )
                        primary = record.validation.primary
                        # Only a complete model (every coordinate trained at
                        # least once) is eligible for best-model tracking —
                        # a partial GameModel would break scoring downstream.
                        complete = all(
                            c in models for c in self.update_sequence)
                        if complete and (
                            best_metric is None
                            or suite.primary.better_than(primary, best_metric)
                        ):
                            best_metric = primary
                            best_models = dict(models)
                    logger.info(
                        "sweep %d coord %s: %s (%.2fs)",
                        sweep, cid, record.validation, dt,
                    )
                else:
                    logger.info("sweep %d coord %s done (%.2fs)", sweep, cid, dt)
                tracker.append(record)

                if checkpointer is not None:
                    checkpointer.save(
                        step,
                        state={
                            "models": models,
                            "scores": scores,
                            "total": total,
                            "v_cache": v_cache,
                            "best_metric": best_metric,
                            "best_models": best_models,
                            "tracker": tracker,
                            **(extra_state or {}),
                        },
                        meta={
                            "phase": "step",
                            "sweep": sweep,
                            "coord_index": ci,
                            **(checkpoint_meta or {}),
                        },
                    )
                step += 1
            sweep_span.__exit__(None, None, None)
            # Sweep-cache residency marker (data/device_cache.py): the
            # timeline shows per sweep whether the dataset was device-pinned
            # (sweep 1+ re-uploading here is the regression the cache
            # exists to kill — docs/scaling.md §"Data path").
            from photon_tpu.obs.metrics import REGISTRY as _REG

            instant(
                "cache.sweep_residency", cat="ingest", sweep=sweep,
                resident_bytes=_REG.gauge("sweep_cache_bytes").value(),
                spilled_bytes=_REG.gauge("sweep_cache_spilled_bytes").value(),
            )
            # Arm after the first sweep that executed EVERY coordinate step
            # (a resumed run's first sweep may be partial, leaving later
            # coordinates' shapes uncompiled — warming then would turn their
            # legitimate first compiles into false retrace alarms). An
            # in-run device-loss recovery pushes the arming point out the
            # same way: its cache purge makes every shape recompile once
            # more across the remainder of that sweep.
            first_full = (0 if resumed_pos is None else resumed_pos[0] + 1)
            arm_at = (first_full if rearm_sweep is None
                      else max(first_full, rearm_sweep))
            if sweep == arm_at:
                for k in _retrace.RE_SOLVER_KERNELS:
                    _retrace.mark_warm(k)

        final = best_models if best_models is not None else models
        return GameModel(dict(final)), tracker
