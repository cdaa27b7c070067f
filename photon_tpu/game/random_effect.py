"""Random-effect training: vmapped per-entity solves, sharded over the mesh.

Parity: reference ⟦photon-api/.../algorithm/RandomEffectCoordinate.scala⟧ +
⟦SingleNodeOptimizationProblem⟧ (SURVEY.md §3.5): thousands of independent
per-entity GLM solves. The reference runs one Breeze L-BFGS per entity inside
``mapPartitions``; here each bucket of same-shape entities is ONE
``vmap``-batched masked solve (entities converge at different iterations —
``lax.while_loop`` under vmap runs until every lane's convergence flag is
set, which is exactly the masked-convergence semantics SURVEY.md §7
hard-part #1 calls for), compiled once and sharded across chips over the
mesh's entity axis with zero communication in the inner loop (SPMD ≙ the
reference's embarrassing parallelism, without the shuffle).
"""
from __future__ import annotations

import dataclasses
import functools
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from photon_tpu.data.random_effect import EntityBucket, RandomEffectDataset
from photon_tpu.faults import fault_point
from photon_tpu.functions.problem import GLMOptimizationProblem
from photon_tpu.obs import device_wait, trace_span
from photon_tpu.parallel.mesh import (
    axes_size,
    batch_sharding,
    note_sharded_bytes,
)
from photon_tpu.optim.base import OptimizerResult
from photon_tpu.types import TaskType

Array = jax.Array

# Per-bucket record of the MOST RECENT train_random_effects call:
# [{bucket, entities, entities_padded, row_slots, local_dim, solver, chunk,
#   routing, compile_seconds, compile_by_solver, calibration_seconds,
#   calibrated}]. Module-level on purpose: tests and bench.py read the
# routing after a fit without threading a collector through the estimator
# stack. Every field costs nothing (compile time is host-synchronous
# dispatch wall, no device sync needed — see obs.retrace.compile_watch).
# Seconds per bucket are the ``optim.re_bucket`` span's; completed compute
# is ``descent.step``'s, which ends in the device-to-host read.
LAST_BUCKET_TIMINGS: list = []

# Process-global routing/compile counters (obs registry → /metrics): the
# bench and rehearsal artifacts read deltas of these around a fit to report
# "fraction of RE rows on a history-free solver" and "RE compile seconds"
# without threading a collector through the estimator stack.
from photon_tpu.obs.metrics import REGISTRY as _OBS_REGISTRY  # noqa: E402

_RE_ROWS_ROUTED = _OBS_REGISTRY.counter(
    "re_rows_routed_total",
    "Random-effect row SLOTS (entities x padded rows-per-entity) dispatched "
    "per bucket solver",
)
_RE_COMPILE_SECONDS = _OBS_REGISTRY.counter(
    "re_solver_compile_seconds_total",
    "Wall seconds of RE bucket-solver dispatches that included a first-trace "
    "XLA compile (compile/solve split; obs.retrace.compile_watch)",
)
_RE_CALIBRATION_SECONDS = _OBS_REGISTRY.counter(
    "re_calibration_seconds_total",
    "Wall seconds spent in solver-routing calibration races "
    "(game/solver_routing.py)",
)
_RE_PROJECTION_PLANS = _OBS_REGISTRY.counter(
    "re_projection_plans_total",
    "Projections of a per-entity model into another dataset, by whether "
    "the projection built its plan or reused the one the dataset kept",
)


@dataclasses.dataclass(frozen=True)
class RandomEffectModel:
    """Per-entity GLMs for one random-effect coordinate.

    Parity: reference ⟦RandomEffectModel(modelsRDD: RDD[(REId, GLM)])⟧ — here
    a list of per-bucket coefficient stacks ``[E, P]`` in each entity's local
    feature subspace, plus the projection/slot structure to interpret them.
    Unseen entities score 0 (the reference's fallback to the zero model).
    """

    re_type: str
    task: TaskType
    bucket_coefs: Sequence[Array]               # per bucket: [E, P]
    bucket_proj: Sequence[Array]                # per bucket: [E, P] -> global col
    bucket_entity_ids: Sequence[Array]          # per bucket: [E] dense REId
    entity_keys: Sequence                       # dense REId -> original key
    entity_to_slot: dict                        # dense REId -> (bucket, lane)
    global_dim: int
    bucket_variances: Optional[Sequence[Array]] = None

    @property
    def n_entities(self) -> int:
        return len(self.entity_keys)

    @functools.cached_property
    def _key_to_dense(self) -> dict:
        return {k: i for i, k in enumerate(self.entity_keys)}

    def _sparse_for(
        self, entity_key, stacks: Sequence[Sequence[Array]]
    ) -> tuple[np.ndarray, list[np.ndarray]]:
        """(global_indices, [values per stack]) for one entity — one slot
        lookup and proj gather shared by means/variances export."""
        dense = self._key_to_dense.get(entity_key)
        if dense is None:
            return np.zeros(0, np.int64), [
                np.zeros(0, np.float32) for _ in stacks
            ]
        b, lane = self.entity_to_slot[dense]
        proj = np.asarray(self.bucket_proj[b][lane])
        valid = proj < self.global_dim
        return proj[valid].astype(np.int64), [
            np.asarray(s[b][lane])[valid] for s in stacks
        ]

    def coefficients_for(self, entity_key) -> tuple[np.ndarray, np.ndarray]:
        """(global_indices, values) sparse coefficient vector for one entity
        (host-side; for model export and cross-dataset scoring)."""
        gi, (gv,) = self._sparse_for(entity_key, [self.bucket_coefs])
        return gi, gv

    def variances_for(self, entity_key) -> Optional[tuple[np.ndarray, np.ndarray]]:
        """Sparse posterior variances for one entity (same index set as
        ``coefficients_for``), or None if variances were not computed."""
        if self.bucket_variances is None:
            return None
        gi, (gv,) = self._sparse_for(entity_key, [self.bucket_variances])
        return gi, gv

    def export_for(
        self, entity_key
    ) -> tuple[np.ndarray, np.ndarray, Optional[np.ndarray]]:
        """(indices, means, variances-or-None) in one slot lookup — the model
        export path's per-entity gather."""
        if self.bucket_variances is None:
            gi, (gv,) = self._sparse_for(entity_key, [self.bucket_coefs])
            return gi, gv, None
        gi, (gv, vv) = self._sparse_for(
            entity_key, [self.bucket_coefs, self.bucket_variances]
        )
        return gi, gv, vv

    def score_dataset(self, dataset: RandomEffectDataset) -> Array:
        """Scores for every row of the dataset this model was trained on
        (or any dataset with identical bucket structure)."""
        per_bucket = [
            b.scores(c) for b, c in zip(dataset.buckets, self.bucket_coefs)
        ]
        return dataset.scatter_scores(per_bucket)

    @functools.cached_property
    def _slots(self) -> tuple[np.ndarray, np.ndarray]:
        """(bucket, lane) of every dense REId, as two arrays."""
        bucket = np.full(len(self.entity_keys), -1, np.int64)
        lane = np.zeros(len(self.entity_keys), np.int64)
        for dense, (b, ln) in self.entity_to_slot.items():
            bucket[dense], lane[dense] = b, ln
        return bucket, lane

    def projection_plan(self, dataset: RandomEffectDataset) -> "ProjectionPlan":
        """The plan of a projection from this model's structure into
        ``dataset``: the one the dataset keeps if it was built for this
        structure (every model trained on one dataset shares it, whatever
        its coefficients), else a new one, which the dataset then keeps in
        its place. Counted in ``re_projection_plans_total{outcome}``."""
        kept = dataset.kept_projection
        plan = kept.get("plan")
        if plan is not None and plan.fits(self):
            outcome = "reused"
        else:
            plan = kept["plan"] = self._build_plan(dataset)
            outcome = "built"
        kept["outcome"] = outcome
        _RE_PROJECTION_PLANS.inc(outcome=outcome)
        return plan

    def _build_plan(self, dataset: RandomEffectDataset) -> "ProjectionPlan":
        """Match ``dataset``'s local subspaces against this model's: the
        reference's model-RDD join by REId (SURVEY.md §3.6), on the host
        with no per-entity Python. The entities of one (new bucket,
        trained bucket) pair are matched together, each new local column
        against its entity's trained columns (ascending, as the builders
        write them) by ONE ``searchsorted`` over keys ``lane * stride +
        column``. A hit becomes the flat index of its trained slot in this
        model's per-bucket ``[E, P]`` stacks laid end to end; an entity or
        column absent from this model, a ghost column and a padded lane
        stay -1."""
        key_to_dense = self._key_to_dense
        with device_wait("project_stacks"):
            old_proj = [np.asarray(p) for p in self.bucket_proj]
        start = np.cumsum([0] + [p.size for p in old_proj])
        if start[-1] >= np.iinfo(np.int32).max:
            raise ValueError(f"{start[-1]} trained slots overflow an int32 index")
        slot_bucket, slot_lane = self._slots
        # dense REId of ``dataset`` -> dense REId here, -1 = never trained
        old_of = np.fromiter(
            (key_to_dense.get(k, -1) for k in dataset.entity_keys),
            np.int64, len(dataset.entity_keys))
        stride = self.global_dim + 1
        index = []
        for b in dataset.buckets:
            with device_wait("project_stacks"):
                proj = np.asarray(b.proj)
                eids = np.asarray(b.entity_ids)
            idx = np.full(proj.shape, -1, np.int32)
            dense_old = np.where(eids >= 0, old_of[np.maximum(eids, 0)], -1)
            from_bucket = np.where(
                dense_old >= 0, slot_bucket[np.maximum(dense_old, 0)], -1)
            for bo in np.unique(from_bucket[from_bucket >= 0]):
                lanes = np.flatnonzero(from_bucket == bo)
                from_lanes = slot_lane[dense_old[lanes]]
                width = old_proj[bo].shape[1]
                row = np.arange(len(lanes), dtype=np.int64)[:, None] * stride
                # ascending: a lane's trained columns, its ghosts last
                have = (old_proj[bo][from_lanes] + row).ravel()
                want = proj[lanes] + row
                pos = np.minimum(np.searchsorted(have, want.ravel()),
                                 len(have) - 1).reshape(want.shape)
                hit = (have[pos] == want) & (proj[lanes] < self.global_dim)
                lane_at, col = np.nonzero(hit)
                at = pos[hit]
                idx[lanes[lane_at], col] = (
                    start[bo] + from_lanes[at // width] * width + at % width)
            index.append(idx)
        return ProjectionPlan(
            index=tuple(jax.device_put(index)),
            shapes=tuple(p.shape for p in old_proj),
            bucket_proj=tuple(self.bucket_proj),
            entity_keys=self.entity_keys,
            entity_to_slot=self.entity_to_slot,
            global_dim=self.global_dim,
        )

    def project_to(self, dataset: RandomEffectDataset) -> list[Array]:
        """Coefficient stacks re-projected into a *different* dataset's local
        subspaces (validation / scoring data); entities unseen at training
        time get the zero model: one gather on the device through
        ``projection_plan(dataset)``."""
        return self.projection_plan(dataset).gather(
            [self.bucket_coefs], [0.0])[0]

    def project_posteriors_to(
        self, dataset: RandomEffectDataset
    ) -> tuple[list[Array], list[Array]]:
        """(means, variances) per-bucket stacks projected into ``dataset`` in
        one entity pass — the raw material for incremental-training priors.
        Unseen entities/columns get the N(0, 1) default posterior."""
        if self.bucket_variances is not None:
            means, variances = self.projection_plan(dataset).gather(
                [self.bucket_coefs, self.bucket_variances], [0.0, 1.0])
        else:
            means = self.project_to(dataset)
            variances = [jnp.ones_like(m) for m in means]
        return means, variances

    def project_prior_to(
        self, dataset: RandomEffectDataset, incremental_weight: float = 1.0
    ) -> list:
        """Per-bucket PriorDistribution pytrees ([E, P] leaves) for
        incremental training on ``dataset`` (reference ⟦PriorDistribution⟧)."""
        from photon_tpu.functions.prior import PriorDistribution

        means, variances = self.project_posteriors_to(dataset)
        return [
            PriorDistribution.from_model(m, v, incremental_weight)
            for m, v in zip(means, variances)
        ]

    def score_new_dataset(self, dataset: RandomEffectDataset) -> Array:
        """Scores for a dataset built from different rows (e.g. validation)."""
        coef_stacks = self.project_to(dataset)
        per_bucket = [
            b.scores(c) for b, c in zip(dataset.buckets, coef_stacks)
        ]
        return dataset.scatter_scores(per_bucket)


@dataclasses.dataclass(frozen=True)
class ProjectionPlan:
    """Where every slot of a target dataset's per-bucket ``[E', P']``
    stacks takes its value from, for one model structure: its flat index
    in the model's per-bucket ``[E, P]`` stacks laid end to end (ravel
    order), -1 for the fill. Built on the host once for a pair of
    structure and dataset (``RandomEffectModel.projection_plan``) and held
    on the device; a projection is then one gather there."""

    index: tuple                     # per target bucket: int32 [E', P']
    shapes: tuple                    # the model's per-bucket [E, P]
    bucket_proj: tuple               # the structure it was built for:
    entity_keys: Sequence            # held, and compared by identity
    entity_to_slot: dict
    global_dim: int

    def fits(self, model: RandomEffectModel) -> bool:
        """``model`` has the structure this plan was built for: the same
        objects (``coordinates.py`` ``_same_structure``'s test), so no
        value needs reading to know it."""
        return (model.entity_keys is self.entity_keys
                and model.entity_to_slot is self.entity_to_slot
                and model.global_dim == self.global_dim
                and len(model.bucket_proj) == len(self.bucket_proj)
                and all(p is q for p, q in
                        zip(model.bucket_proj, self.bucket_proj)))

    def gather(self, sources: Sequence[Sequence[Array]],
               fills: Sequence[float]) -> list[list[Array]]:
        """Each source's stacks (shaped as the model's) projected into the
        target's buckets, ``fill`` where the plan holds -1."""
        for src in sources:
            if tuple(np.shape(s) for s in src) != self.shapes:
                raise ValueError(
                    f"stacks {[np.shape(s) for s in src]} are not the "
                    f"{list(self.shapes)} this plan was built for")
        return _gather_stacks(tuple(tuple(src) for src in sources),
                              self.index, tuple(float(f) for f in fills))


@functools.partial(jax.jit, static_argnames="fills")
def _gather_stacks(sources, index, fills):
    """One program a projection: a source's stacks raveled end to end (and
    one element more, so that the operand is never empty), then every
    target bucket's stack one gather of them."""
    out = []
    for stacks, fill in zip(sources, fills):
        dtype = stacks[0].dtype if stacks else jnp.float32
        flat = jnp.concatenate(
            [s.ravel() for s in stacks] + [jnp.zeros((1,), dtype)])
        out.append([jnp.where(i >= 0, flat[jnp.maximum(i, 0)], fill)
                    for i in index])
    return out


class EntityScorer:
    """A per-entity coordinate's validation scorer: a model's scores on
    ``dataset``'s rows (``score_new_dataset``) through the plan the dataset
    keeps, so that the models of every step and of every fit over one
    training dataset reuse the plan the first of them built. ``plan`` is
    what the last call did: ``"built"`` or ``"reused"``."""

    def __init__(self, dataset: RandomEffectDataset):
        self.dataset = dataset
        self.plan: Optional[str] = None

    def __call__(self, model) -> Array:
        scores = model.score_new_dataset(self.dataset)
        self.plan = self.dataset.kept_projection["outcome"]
        return scores


def _pad_bucket(
    bucket: EntityBucket, multiple: int, n_rows: int, global_dim: int
) -> EntityBucket:
    """Pad the entity axis to a multiple of the mesh axis size with inert
    lanes: weight-0 rows, ghost row_ids (so no score scatters anywhere),
    ghost proj columns, and entity_id −1. Host numpy buckets stay host
    numpy (np.pad) so a subsequent SHARDED device_put streams each shard
    straight to its device instead of round-tripping through device 0."""
    e = bucket.n_entities
    r = (-e) % multiple
    if r == 0:
        return bucket

    def pad(a, fill):
        widths = [(0, r)] + [(0, 0)] * (a.ndim - 1)
        if isinstance(a, np.ndarray):
            return np.pad(a, widths, constant_values=fill)
        return jnp.pad(a, widths, constant_values=fill)

    return EntityBucket(
        idx=pad(bucket.idx, bucket.local_dim),      # local ghost column
        val=pad(bucket.val, 0),
        labels=pad(bucket.labels, 0),
        weights=pad(bucket.weights, 0),
        train_weights=pad(bucket.train_weights, 0),
        row_ids=pad(bucket.row_ids, n_rows),        # global ghost row
        proj=pad(bucket.proj, global_dim),          # global ghost column
        entity_ids=pad(bucket.entity_ids, -1),
    )


@partial(jax.jit, static_argnums=0)
def _fit_bucket_jitted(problem, batches, w0, local_mask, local_norm, local_prior):
    """One vmapped bucket solve; static problem key keeps the XLA executable
    cached across coordinate-descent sweeps (same config + bucket shapes).
    ``local_norm`` / ``local_prior`` are per-entity pytrees (leaves [E, P])
    or None."""
    from photon_tpu.obs import retrace

    retrace.note_trace("fit_bucket_vmapped")  # 1 trace == 1 XLA compile
    return jax.vmap(
        lambda b, w, m, nm, pr: problem.run(
            b, w, reg_mask=m, normalization=nm, prior=pr
        ),
        in_axes=(0, 0, 0, 0, 0),
    )(batches, w0, local_mask, local_norm, local_prior)


def _plan_desc(solver: str, chunk) -> str:
    return f"{solver}@{'full' if chunk is None else chunk}"


def _oom_next_tier(solver: str, chunk, e: int,
                   vmapped_chunkable: bool = True, multiple_of: int = 1):
    """The next-cheaper (solver, chunk) plan below ``(solver, chunk)`` for
    an E-entity bucket, or None when the degradation ladder is exhausted.
    ``chunk`` None means the full-bucket solve (effective chunk = E).

    Order (docs/robustness.md §"Memory pressure"): the SAME solver one
    blessed chunk tier down — PR 4's chunked==full equivalence keeps the
    result unchanged — until the smallest tier, then the vmapped fallback
    (chunked when the bucket outgrows the smallest blessed size), then
    nothing: an OOM below the cheapest plan is a real capacity wall.
    ``vmapped_chunkable=False`` (a per-entity normalization context is in
    play — it is NOT sliced by ``fit_bucket_in_chunks``) restricts the
    vmapped fallback to the full-bucket dispatch. ``multiple_of`` (the
    entity-axis mesh size) keeps every chunked tier mesh-divisible."""
    from photon_tpu.game.newton_re import chunk_ladder

    ladder = [c for c in chunk_ladder() if c % max(1, multiple_of) == 0]
    eff = e if chunk is None else chunk
    smaller = [c for c in ladder if c < eff]
    if solver != "vmapped_lbfgs":
        if smaller:
            return solver, max(smaller)
        if vmapped_chunkable and ladder and e > ladder[0]:
            return "vmapped_lbfgs", ladder[0]
        return "vmapped_lbfgs", None
    if smaller and vmapped_chunkable:
        return "vmapped_lbfgs", max(smaller)
    return None


def _apply_sticky_plan(plan, sticky, e: int, vmapped_chunkable: bool = True,
                       multiple_of: int = 1):
    """Clamp a static plan to the run's sticky OOM downshift (the proven-
    too-big tiers are skipped outright instead of re-OOMing per sweep).
    Under a mesh (``multiple_of`` > 1) the clamped chunk snaps DOWN to the
    nearest mesh-divisible blessed size so the sharded dispatch stays
    even; a cap below every divisible size keeps the cap verbatim only
    when it divides (else the smallest divisible tier — still cheaper per
    device than the plan that OOM'd)."""
    if not sticky:
        return plan
    solver, chunk = plan
    if sticky.get("solver"):
        solver = sticky["solver"]
    cap = sticky.get("chunk")
    if cap:
        eff = e if chunk is None else chunk
        if eff > cap:
            chunk = cap
            if multiple_of > 1 and chunk % multiple_of:
                from photon_tpu.game.newton_re import chunk_ladder

                div = [c for c in chunk_ladder()
                       if c % multiple_of == 0]
                under = [c for c in div if c <= cap]
                # No mesh-divisible blessed size at all (a device count
                # that divides no ladder entry): honor the cap with an
                # off-ladder multiple rather than degrading to None — a
                # FULL-bucket dispatch above the cap that just OOM'd would
                # invert the sticky clamp into an unbounded solve.
                chunk = (max(under) if under
                         else (min(div) if div
                               else max(multiple_of,
                                        cap - cap % multiple_of)))
    if solver == "vmapped_lbfgs" and not vmapped_chunkable:
        chunk = None
    return solver, chunk


def _solve_bucket(problem, bucket, batches, w0, local_mask, local_norm,
                  local_prior, normalization, mesh=None,
                  entity_axis="data"):
    """Pick and dispatch one bucket's solver; ``(models, result, info)``.

    Under a mesh the bucket runs ENTITY-SHARDED: full-bucket dispatches
    place every per-entity array row-sharded over ``entity_axis``, and the
    chunked Newton tiers — no longer skipped under mesh — slice blessed
    mesh-divisible chunks host-side and fan each chunk's ``device_put``
    out per shard (each device owns chunk/n lanes of every chunk), with
    chunk N+1's transfer double-buffered behind chunk N's solve. Budget
    gates price the PER-DEVICE slice, so a mesh widens what Newton admits.
    Measured routing and the OOM ladder run under the mesh too; the cost
    table keys carry the device count (``solver_routing.shape_class``).

    Smooth solves take a history-free batched Newton fast path
    (game/newton_re.py): primal dense Newton for small local dims,
    span-reduced (dual) Newton for the canonical few-rows-in-a-wide-
    subspace regime. Both replace the vmapped L-BFGS while_loop whose
    O(E·m·P) history traffic dominates the RE step (VERDICT r4 weak #3;
    measured: halving m halves the step). Same optimum, same result
    pytree; the gates fall back for L1/normalization/etc.

    A bucket whose FULL-bucket footprint busts the memory budget no longer
    surrenders straight to vmapped L-BFGS: the entity axis is sub-batched
    into blessed chunk sizes and solved through the same jitted Newton
    kernels (``fit_bucket_in_chunks``). Under ``PHOTON_RE_ROUTING=measured``
    (and no mesh — chunk slicing would break the entity-axis sharding
    contract) the static preference ladder is replaced by the measured
    cost table + calibration race in ``game/solver_routing.py``.

    ``info``: {solver, chunk, routing, compile_seconds, compile_by_solver,
    calibration_seconds, calibrated}. ``compile_seconds`` is the wall time
    of dispatches in which the retrace sentinel saw a new trace — jit
    tracing + XLA compilation run synchronously before dispatch returns,
    so this splits compile from solve without any blocking device sync
    (``obs.retrace.compile_watch``).
    """
    from photon_tpu.game import solver_routing
    from photon_tpu.game.newton_re import (
        dual_chunk_size,
        dual_eligible,
        dual_precheck,
        fit_bucket_in_chunks,
        fit_bucket_newton,
        fit_bucket_newton_dual,
        newton_chunk_size,
        newton_eligible,
        penalty_terms,
        solve_form,
        u_max_for,
    )
    from photon_tpu.obs.retrace import compile_watch

    compile_by_solver: dict = {}

    def watched(name, fit_fn, record_fn=None):
        """Accumulate compile time of every dispatch, PER solver — under
        measured routing the calibration race compiles every candidate, and
        charging the losers' compiles to the winner's label would corrupt
        the per-solver compile split the counters exist to report.

        ``record_fn(*args)`` runs once per detected compile: it records the
        compiled signature into the AOT compile store
        (runtime/compile_store.py) so restarts and device-loss recoveries
        pre-warm the blessed kernel set instead of re-tracing cold. Not
        under a mesh — sharded avals would not replay to the same HLO."""
        def run(*args):
            with compile_watch() as cw:
                out = fit_fn(*args)
            if cw.compile_seconds:
                compile_by_solver[name] = (
                    compile_by_solver.get(name, 0.0) + cw.compile_seconds)
                if record_fn is not None:
                    record_fn(*args)
            return out
        return run

    if mesh is not None:
        rec_primal = rec_dual = rec_vmapped = None
    else:
        from photon_tpu.runtime.compile_store import record_if_active

        def rec_primal(b, w, m, pr):
            record_if_active("fit_bucket_newton", fit_bucket_newton,
                             (problem, b, w, m, pr))

        def rec_dual(b, w, m, pr):
            record_if_active("fit_bucket_newton_dual", fit_bucket_newton_dual,
                             (problem, b, w, m, pr, get_u_max()))

        def rec_vmapped(b, w, m, pr):
            record_if_active("fit_bucket_vmapped", _fit_bucket_jitted,
                             (problem, b, w, m, local_norm, pr))

    fit_primal = watched(
        "newton_primal",
        lambda b, w, m, pr: fit_bucket_newton(problem, b, w, m, pr),
        record_fn=rec_primal)
    fit_vmapped = watched(
        "vmapped_lbfgs",
        lambda b, w, m, pr: _fit_bucket_jitted(
            problem, b, w, m, local_norm, pr),
        record_fn=rec_vmapped)

    # u_max is a device reduction + blocking D2H sync per bucket — memoized
    # and computed LAZILY, so it is only paid once a bucket actually
    # consults a dual gate (a primal-routed bucket syncing here would
    # serialize the streaming loop's transfer/compute overlap for nothing).
    # The count uses the shared penalty_terms definition so the gate's
    # zeros and the dual solver's D⁺ can never disagree on which columns
    # are unpenalized.
    u_max_cell = [None]

    def get_u_max() -> int:
        if u_max_cell[0] is None:
            u_max_cell[0] = (
                u_max_for(penalty_terms(problem, local_mask, local_prior)[3])
                if dual_precheck(problem, bucket, normalization) else -1
            )
        return u_max_cell[0]

    fit_dual = watched(
        "newton_dual",
        lambda b, w, m, pr: fit_bucket_newton_dual(
            problem, b, w, m, pr, get_u_max()),
        record_fn=rec_dual)

    def finish(models, result, **info):
        info.setdefault("chunk", None)
        # How the bucket's Newton systems were solved (the span's ``solve``).
        info["solve"] = (solve_form() if info["solver"].startswith("newton")
                         else None)
        info.setdefault("routing", "static")
        info.setdefault("calibration_seconds", 0.0)
        info.setdefault("calibrated", False)
        info["compile_seconds"] = round(sum(compile_by_solver.values()), 3)
        info["compile_by_solver"] = {
            k: round(v, 3) for k, v in compile_by_solver.items()}
        return models, result, info

    from photon_tpu.runtime import memory_guard as _mg

    fits = {"newton_primal": fit_primal, "newton_dual": fit_dual,
            "vmapped_lbfgs": fit_vmapped}

    # Entity-axis sharding (tentpole: chunked tiers run UNDER the mesh).
    # ``place`` device_puts a pytree row-sharded over the entity axis —
    # full-bucket dispatches place once (memoized), chunked dispatches
    # place per chunk with the transfer double-buffered behind the solve.
    if mesh is not None:
        n_shards = axes_size(mesh, entity_axis)
        _sharding = batch_sharding(mesh, entity_axis)

        def place(tree):
            return jax.tree.map(
                lambda leaf: jax.device_put(leaf, _sharding), tree)
    else:
        n_shards = 1
        place = None

    if place is not None and local_norm is not None:
        # Only the full-bucket vmapped dispatch consumes the normalization
        # context (the chunked gates exclude it) — place it sharded once.
        local_norm = place(local_norm)

    _full_placed = [None]

    def full_args():
        """(batches, w0, mask, prior) for a FULL-bucket dispatch — placed
        entity-sharded once per bucket under a mesh (every ladder retry
        and the calibration race reuse the same placed arrays)."""
        if place is None:
            return batches, w0, local_mask, local_prior
        if _full_placed[0] is None:
            _full_placed[0] = place(
                (batches, w0, local_mask, local_prior))
            note_sharded_bytes("random_effect_bucket", _full_placed[0][0])
        return _full_placed[0]

    def dispatch(solver, chunk):
        """One (solver, chunk) plan; ``chunk`` None = full bucket."""
        fit = fits[solver]
        if mesh is not None:
            # Chaos hook: error="device_lost" here simulates losing ONE
            # shard of the mesh mid-dispatch; train_random_effects
            # redistributes the bucket's entities over the surviving
            # devices instead of restarting the world.
            fault_point("re.shard", solver=solver, shards=n_shards,
                        chunk=0 if chunk is None else chunk)
        if chunk is None:
            b, w, m, pr = full_args()
            return fit(b, w, m, pr)
        return fit_bucket_in_chunks(
            fit, chunk, batches, w0, local_mask, local_prior,
            put=place, ahead=1 if place is not None else 0)

    def run_ladder(solver, chunk, downshifted=False):
        """Dispatch with the OOM degradation ladder (docs/robustness.md
        §"Memory pressure"): an ``oom``-classified failure retries at the
        next-cheaper plan — one blessed chunk tier down, then the vmapped
        fallback — bounded per run and STICKY (later buckets/sweeps start
        at the surviving tier; re-promotion only on a fresh run's cost-
        table race). Anything else propagates untouched. ``downshifted``
        starts True when the plan was sticky-clamped on entry: a degraded
        plan's first compile of a new shape class — possibly after the
        descent loop marked the kernels warm — is deliberate, not an
        alarm."""
        while True:
            try:
                # Chaos hook: error="device_oom" here drives this ladder
                # deterministically on CPU (sibling of descent.device's
                # device_lost).
                fault_point("re.solve", solver=solver,
                            chunk=0 if chunk is None else chunk)
                if downshifted:
                    # The cheaper tier may compile a shape first seen
                    # after the warm mark — deliberate, not an alarm.
                    with _retrace_mod.expected_compiles():
                        models, result = dispatch(solver, chunk)
                else:
                    models, result = dispatch(solver, chunk)
                return models, result, solver, chunk
            except Exception as err:  # noqa: BLE001 - classified below
                if not _mg.is_oom(err):
                    raise
                nxt = _oom_next_tier(solver, chunk, int(w0.shape[0]),
                                     vmapped_chunkable=local_norm is None,
                                     multiple_of=n_shards)
                before = _plan_desc(solver, chunk)
                if nxt is None:
                    _mg.journal_event(
                        "oom_exhausted", site="re.solve", cause="oom",
                        plan=before,
                        reason=f"no cheaper plan below {before}")
                    raise
                if not _mg.downshifter("re.solve").absorb(
                        err, before=before, after=_plan_desc(*nxt)):
                    raise
                solver, chunk = nxt
                _mg.set_sticky_plan("re.solve", {
                    "chunk": chunk,
                    "solver": (solver if solver == "vmapped_lbfgs"
                               else None),
                })
                downshifted = True

    from photon_tpu.obs import retrace as _retrace_mod

    sticky = _mg.sticky_plan("re.solve")

    measured_oom = None
    if (solver_routing.routing_mode() == "measured" and sticky is None):
        def sync(out):
            with device_wait("routing_sync"):
                np.asarray(out[1].value[:1])  # tiny D2H (repo-standard sync)

        try:
            # Same chaos hook as the static ladder: an injected
            # device_oom here drives the measured-plan demotion below.
            fault_point("re.solve", routing="measured")
            models, result, info = solver_routing.solve_measured(
                problem, bucket, batches, w0, local_mask, local_prior,
                normalization, get_u_max(), fits.__getitem__, sync,
                shards=n_shards, place=place,
            )
            return finish(models, result, **info)
        except Exception as err:  # noqa: BLE001 - classified below
            if not _mg.is_oom(err):
                raise
            # The measured plan (or its calibration race) OOM'd. The
            # downshift tier is computed from the STATIC plan below — the
            # plan that will actually run next — not guessed from the
            # (unknown) measured winner, so the absorbed downshift can
            # never be a no-op or an up-shift.
            measured_oom = err

    # Static preference ladder (now expressed as a plan): full primal ->
    # full dual -> chunked primal -> chunked dual -> vmapped. Under a mesh
    # the full tiers gate on the PER-DEVICE footprint and the chunked
    # tiers pick mesh-divisible blessed sizes (each chunk itself sharded),
    # so every tier runs under the mesh instead of being skipped.
    plan = ("vmapped_lbfgs", None)
    if newton_eligible(problem, bucket, normalization, shards=n_shards):
        plan = ("newton_primal", None)
    else:
        u_max = get_u_max()
        if u_max >= 0 and dual_eligible(problem, bucket, normalization,
                                        u_max, shards=n_shards):
            plan = ("newton_dual", None)
        else:
            chunk = newton_chunk_size(problem, bucket, normalization,
                                      shards=n_shards)
            if chunk:
                plan = ("newton_primal", chunk)
            else:
                chunk = (dual_chunk_size(problem, bucket, normalization,
                                         u_max, shards=n_shards)
                         if u_max >= 0 else None)
                if chunk:
                    plan = ("newton_dual", chunk)

    clamped = _apply_sticky_plan(plan, sticky, int(w0.shape[0]),
                                 vmapped_chunkable=local_norm is None,
                                 multiple_of=n_shards)
    if measured_oom is not None:
        # Demote one tier below the static plan and make it sticky, so
        # later buckets skip the measured winner that cannot fit.
        nxt = _oom_next_tier(*clamped, int(w0.shape[0]),
                             vmapped_chunkable=local_norm is None,
                             multiple_of=n_shards)
        before = f"measured({_plan_desc(*clamped)})"
        if nxt is None:
            _mg.journal_event(
                "oom_exhausted", site="re.solve", cause="oom", plan=before,
                reason=f"no cheaper plan below {before}")
            raise measured_oom
        if not _mg.downshifter("re.solve").absorb(
                measured_oom, before=before, after=_plan_desc(*nxt)):
            raise measured_oom
        clamped = nxt
        _mg.set_sticky_plan("re.solve", {
            "chunk": clamped[1],
            "solver": (clamped[0] if clamped[0] == "vmapped_lbfgs"
                       else None),
        })
    models, result, solver, chunk = run_ladder(
        *clamped, downshifted=clamped != plan)
    return finish(models, result, solver=solver, chunk=chunk)


# ------------------------------------------------------ shard-loss recovery

_RE_SHARD_LOSSES = _OBS_REGISTRY.counter(
    "re_shard_losses_total",
    "Mesh shards lost mid-RE-solve and absorbed by entity redistribution "
    "(docs/robustness.md §shard loss)",
)


def _alive_devices(devices, want: int):
    """The first ``want`` devices that answer a trivial device_put probe —
    after a real shard loss the dead device must not land in the degraded
    mesh. Cheap (one tiny put + D2H fetch per device, stops at ``want``).
    The fetch IS the sync: the repo-standard tiny D2H read forces
    completion, so a dead device cannot pass the probe."""
    alive = []
    for d in devices:
        try:
            with device_wait("device_probe"):
                np.asarray(jax.device_put(np.zeros((1,), np.float32), d))
            alive.append(d)
        except Exception:  # noqa: BLE001 - a dead device is the point
            continue
        if len(alive) >= want:
            break
    return alive


def _degrade_mesh(mesh, entity_axis):
    """The next-smaller entity mesh after a shard loss, or None when no
    degradation exists (single device). The surviving size is the LARGEST
    PROPER DIVISOR of the current axis size (8 → 4): the already-padded
    entity axes and the blessed pow-2 chunk ladder stay evenly divisible,
    so the redistributed re-solve reuses the same chunk contract. The
    choice is STICKY for the run (``memory_guard`` sticky plan ``re.shard``)
    — later buckets and sweeps start degraded instead of re-failing."""
    from photon_tpu.parallel.mesh import axes_size as _axes_size
    from photon_tpu.parallel.mesh import axis_tuple, make_mesh
    from photon_tpu.runtime import memory_guard as _mg

    n = _axes_size(mesh, entity_axis)
    if n <= 1:
        return None
    m = next(n // k for k in range(2, n + 1) if n % k == 0)
    devices = list(np.asarray(mesh.devices).flat)
    alive = _alive_devices(devices, m)
    if len(alive) < m:
        return None  # not enough survivors for an even degraded mesh
    axis = axis_tuple(entity_axis)[-1]
    _mg.set_sticky_plan("re.shard", {"shards": m})
    return make_mesh({axis: m}, devices=alive), axis


def _effective_mesh(mesh, entity_axis):
    """Apply the run's sticky shard degradation (a shard lost earlier in
    this run) to a caller-supplied mesh before any solve dispatches."""
    from photon_tpu.parallel.mesh import axes_size as _axes_size
    from photon_tpu.parallel.mesh import axis_tuple, make_mesh
    from photon_tpu.runtime import memory_guard as _mg

    sticky = _mg.sticky_plan("re.shard")
    if not sticky:
        return mesh, entity_axis
    m = int(sticky.get("shards") or 0)
    n = _axes_size(mesh, entity_axis)
    if m <= 0 or m >= n:
        return mesh, entity_axis
    devices = list(np.asarray(mesh.devices).flat)
    alive = _alive_devices(devices, m)
    if len(alive) < m:
        return mesh, entity_axis
    axis = axis_tuple(entity_axis)[-1]
    return make_mesh({axis: m}, devices=alive), axis


def _shard_lost_recover(err, **ctx) -> None:
    """One absorbed shard loss: classified recovery-journal row (via the
    supervisor-registered journal when one is active, else the trace
    instant), metric bump, and the shared device-loss recovery step
    (executable-cache purge + sweep-cache release + compile-store prewarm
    — ``backend_guard.recover_from_device_loss``)."""
    import logging

    from photon_tpu.runtime import backend_guard as _bg
    from photon_tpu.runtime import memory_guard as _mg

    log = logging.getLogger("photon_tpu.game")
    cause = _bg.classify_backend_error(err)
    _RE_SHARD_LOSSES.inc()
    _mg.journal_event(
        "shard_lost", site="re.shard", cause=cause,
        error=f"{type(err).__name__}: {str(err)[:200]}", **ctx)
    log.warning(
        "mesh shard lost mid-RE-solve (%s: %s) — redistributing bucket %s "
        "entities over %s devices (recovery %s)", type(err).__name__, err,
        ctx.get("bucket"), ctx.get("devices_after"), ctx.get("recovery"))
    _bg.recover_from_device_loss(
        f"re shard loss (bucket {ctx.get('bucket')})", logger=log)


def train_random_effects(
    problem: GLMOptimizationProblem,
    dataset: RandomEffectDataset,
    offsets: Array,
    mesh=None,
    entity_axis="data",  # one mesh axis or a tuple (mesh.AxisSpec),
                         # e.g. ("dcn", "data") on a multi-slice mesh
    global_reg_mask: Optional[Array] = None,
    init_coefs: Optional[Sequence[Array]] = None,
    normalization=None,
    priors: Optional[Sequence] = None,
) -> tuple[RandomEffectModel, list[OptimizerResult]]:
    """Fit one GLM per entity; returns the model + per-bucket solver results.

    ``offsets`` is the global per-sample residual score from the other GAME
    coordinates (reference: dataset offsets updated by CoordinateDescent).
    ``global_reg_mask`` (e.g. 0 on the intercept column) is projected into
    each entity's local subspace, as is the shard-level ``normalization``
    context (reference: one NormalizationContext per feature shard applies to
    every per-entity solve too). ``priors`` is an optional per-bucket list of
    PriorDistribution pytrees ([E, P] leaves — see
    ``RandomEffectModel.project_prior_to``) for incremental training.
    """
    from photon_tpu.data.normalization import project_context

    coefs_out, var_out, results = [], [], []
    want_var = problem.variance_type.name != "NONE"
    LAST_BUCKET_TIMINGS.clear()

    # A shard lost earlier in this run degraded the mesh stickily; apply it
    # before any placement so this call never re-discovers the dead device.
    if mesh is not None:
        mesh, entity_axis = _effective_mesh(mesh, entity_axis)
    shard_recoveries = 0

    for b_i, bucket in enumerate(dataset.buckets):
        orig_e = bucket.n_entities
        if mesh is not None:
            axis_size = axes_size(mesh, entity_axis)
            bucket = _pad_bucket(bucket, axis_size, dataset.n_rows, dataset.global_dim)

        p = bucket.local_dim
        e = bucket.n_entities
        # The bucket's inputs, all eager dispatches from the host (the
        # starting point, the mask's and the offsets' gathers), in a span
        # of their own beside the solve's.
        with trace_span("optim.re_inputs", cat="optim",
                        re_type=dataset.re_type, bucket=b_i):
            if init_coefs is not None:
                w0 = jnp.asarray(init_coefs[b_i], bucket.val.dtype)
                if w0.shape[0] < e:  # mesh padding added inert lanes
                    w0 = jnp.pad(w0, ((0, e - w0.shape[0]), (0, 0)))
            else:
                w0 = jnp.zeros((e, p), bucket.val.dtype)

            # Project the global regularization mask into each local
            # subspace. Ghost slots get mask 1 (their coefficients stay 0
            # regardless).
            if global_reg_mask is not None:
                ext = jnp.concatenate(
                    [global_reg_mask.astype(bucket.val.dtype), jnp.ones((1,), bucket.val.dtype)]
                )
                local_mask = ext[bucket.proj]
            else:
                local_mask = jnp.ones((e, p), bucket.val.dtype)

            batches = bucket.local_batches(offsets)
            local_norm = (
                project_context(normalization, bucket.proj, dataset.global_dim)
                if normalization is not None
                else None
            )
            local_prior = priors[b_i] if priors is not None else None
            if local_prior is not None and local_prior.means.shape[0] < e:
                # mesh padding added inert lanes: extend with
                # zero-precision rows
                pad = e - local_prior.means.shape[0]
                local_prior = jax.tree.map(
                    lambda a: jnp.pad(a, ((0, pad), (0, 0))), local_prior
                )

        # Placement now happens INSIDE _solve_bucket (full-bucket plans
        # place once; chunked plans slice host-side and fan each chunk's
        # device_put out per shard with the transfer double-buffered).

        # What the bucket's padding costs, on the span: real rows (as the
        # dataset's builder counted them) beside the row slots solved.
        row_slots = int(bucket.max_samples) * orig_e
        re_span = trace_span(
            "optim.re_bucket", cat="optim", re_type=dataset.re_type,
            bucket=b_i, entities=orig_e, local_dim=p,
            padded_rows=int(bucket.max_samples), row_slots=row_slots, rows=int(dataset.bucket_rows[b_i]),
        ).__enter__()
        info = {"solver": None}
        # Span closes on dispatch, not completed compute (the async
        # dispatcher overlaps buckets on purpose); descent's step-level
        # D2H sync bounds the whole step. Explicit except (not
        # finally+exc_info, which could pick up an unrelated exception a
        # caller is mid-handling) so a failing bucket lands in the
        # timeline error-tagged and a clean one never does.
        while True:
            try:
                models, result, info = _solve_bucket(
                    problem, bucket, batches, w0, local_mask, local_norm,
                    local_prior, normalization, mesh=mesh,
                    entity_axis=entity_axis,
                )
                break
            except KeyboardInterrupt:
                raise  # a user abort is never a shard loss
            except BaseException as _err:
                # Single-shard device loss under a mesh (docs/robustness.md
                # §"Shard loss"): redistribute this bucket's entities over
                # the surviving devices and re-solve — don't restart the
                # world. Anything else (or an exhausted recovery budget)
                # propagates with the span error-tagged.
                from photon_tpu.runtime import backend_guard as _bg

                degraded = (
                    _degrade_mesh(mesh, entity_axis)
                    if (mesh is not None and _bg.is_device_lost(_err)
                        and shard_recoveries < _bg.max_inrun_recoveries())
                    else None
                )
                rehosted = None
                if degraded is not None:
                    # The retry must not read solve inputs sharded over
                    # the OLD mesh (a cache-mirror bucket has a shard ON
                    # the dead device): pull everything to host numpy
                    # first. If the pull itself fails, the source data
                    # died with the device — the bucket is unrecoverable
                    # in-process, so escalate to the caller's checkpoint-
                    # based recovery (descent re-enters from the host
                    # originals) instead of burning the recovery budget
                    # on re-reads that can never succeed.
                    try:
                        with device_wait("shard_rehost"):
                            rehosted = jax.tree.map(
                                np.asarray,
                                (bucket, batches, w0, local_mask, local_prior),
                            )
                    except Exception:  # noqa: BLE001 - data lost with device
                        degraded = None
                if degraded is None:
                    import sys as _sys

                    re_span.set(solver=info["solver"]).__exit__(
                        *_sys.exc_info())
                    raise
                bucket, batches, w0, local_mask, local_prior = rehosted
                shard_recoveries += 1
                old_n = axes_size(mesh, entity_axis)
                mesh, entity_axis = degraded
                _shard_lost_recover(
                    _err, bucket=b_i, coordinate=dataset.re_type,
                    entities=orig_e, devices_before=old_n,
                    devices_after=axes_size(mesh, entity_axis),
                    recovery=shard_recoveries,
                )
        # Compile/solve split on the span (VERDICT r5 weak #6: decision-
        # grade artifacts need first-call XLA compile separated out).
        re_span.set(
            solver=info["solver"], chunk=info["chunk"],
            solve=info["solve"], routing=info["routing"],
            compile_seconds=info["compile_seconds"],
            calibration_seconds=info["calibration_seconds"],
        ).__exit__(None, None, None)
        _RE_ROWS_ROUTED.inc(row_slots, solver=info["solver"])
        # Per-solver attribution: under measured routing the calibration
        # race compiles every candidate — the losers' compiles must land on
        # their own labels, not the winner's.
        for _cs_solver, _cs in info.get("compile_by_solver", {}).items():
            _RE_COMPILE_SECONDS.inc(_cs, solver=_cs_solver)
        if info["calibration_seconds"]:
            _RE_CALIBRATION_SECONDS.inc(info["calibration_seconds"])
        coefs_out.append(models.coefficients.means[:orig_e])
        if want_var:
            var_out.append(models.coefficients.variances[:orig_e])
        results.append(jax.tree.map(lambda a: a[:orig_e], result))
        LAST_BUCKET_TIMINGS.append({
            "bucket": b_i,
            "entities": orig_e,
            "entities_padded": e,
            # SLOTS, not rows: [E, S] includes per-entity padding (weight-0
            # rows); the true row count would need a reduction over weights.
            "row_slots": row_slots,
            "local_dim": p,
            "solver": info["solver"],
            "chunk": info["chunk"],
            "routing": info["routing"],
            # Compile + calibration walls need NO sync gate: jit tracing +
            # XLA compilation are host-synchronous before dispatch returns
            # (obs.retrace.compile_watch), and calibration probes sync
            # internally — so the split is always recorded.
            "compile_seconds": info["compile_seconds"],
            "compile_by_solver": info.get("compile_by_solver", {}),
            "calibration_seconds": info["calibration_seconds"],
            "calibrated": info["calibrated"],
        })

    model = RandomEffectModel(
        re_type=dataset.re_type,
        task=problem.task,
        bucket_coefs=coefs_out,
        bucket_proj=[b.proj for b in dataset.buckets],
        bucket_entity_ids=[b.entity_ids for b in dataset.buckets],
        entity_keys=dataset.entity_keys,
        entity_to_slot=dataset.entity_to_slot,
        global_dim=dataset.global_dim,
        bucket_variances=var_out if want_var else None,
    )
    return model, results
