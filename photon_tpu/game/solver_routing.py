"""Measured cost-model routing for random-effect bucket solvers.

Why: the static eligibility gates in ``game/newton_re.py`` answer "can this
solver run here?" — they cannot answer "which solver is FASTEST here?".
VERDICT r5 weak #1 showed the cost of conflating the two: the S=512 buckets
that dominate the 50M rehearsal were budget-excluded from every Newton
variant and silently surrendered to the vmapped L-BFGS ``while_loop``, and
nothing ever measured the road not taken.

This module replaces preference-by-gate with preference-by-measurement:

* Buckets are classified by **shape class** (``S``, ``K``, ``P``, dtype —
  the entity count does not change per-entity cost, so it is deliberately
  not part of the key).
* The first time a shape class is seen, a **calibration race** times every
  feasible ``(solver, chunk)`` candidate on ONE sync-timed probe slice of
  the bucket; the XLA compile the probe pays (host-synchronous, measured
  by ``obs.retrace.compile_watch``) is subtracted so the race never
  charges a solver for its first-trace compile. Per-entity costs land in
  a process-global :class:`SolverCostTable`.
* Later buckets of the same class route straight to the measured winner —
  including every later sweep of coordinate descent, so the race is a
  one-time cost per (config, shape class).
* The table round-trips as JSON. ``PHOTON_RE_COST_TABLE=<path>`` (set by
  the drivers' ``--re-cost-table`` flag) loads the table at first use and
  persists it after every calibration, so a warm restart — the supervisor
  relaunching a preempted driver — skips calibration entirely and, just as
  important, reproduces the original run's routing decisions exactly
  (calibration is a timing race; re-racing on a restart could flip a
  winner and break bit-identical resume).

Every candidate is **chunked** at a blessed ladder size
(``newton_re.chunk_ladder()``), including the vmapped L-BFGS baseline:
probe shapes are then execution shapes, so calibration warms exactly the
executables the real solve uses (the retrace sentinel stays quiet), and
the probe's per-entity cost honestly includes the convergence-decoupling
behavior of the chunk size it recommends.

Routing mode is ``PHOTON_RE_ROUTING``: ``static`` (default — the
deterministic gate ladder in ``random_effect._solve_bucket``, now with
chunked Newton tiers) or ``measured``. Measured mode is the default for
``bench.py``'s game_scale stage and opt-in for the drivers via
``--re-routing measured``; it is intentionally NOT the library default
because a timing race is not bit-deterministic across processes unless the
table is persisted (see above).
"""
from __future__ import annotations

import dataclasses
import json
import os
import threading
import time
from typing import Callable, Optional, Sequence

import jax
import numpy as np

from photon_tpu.game import newton_re

ROUTING_ENV = "PHOTON_RE_ROUTING"
TABLE_ENV = "PHOTON_RE_COST_TABLE"

# Largest chunk the vmapped L-BFGS baseline is raced/executed at under
# measured routing (its per-entity cost is nearly chunk-flat, and probing
# full-history L-BFGS at a 16K chunk costs more than the race saves).
VMAPPED_CHUNK_CAP = 4096

_MODES = ("static", "measured")


def routing_mode() -> str:
    mode = (os.environ.get(ROUTING_ENV) or "static").strip().lower()
    if mode not in _MODES:
        raise ValueError(
            f"{ROUTING_ENV} must be one of {_MODES}, got {mode!r}"
        )
    return mode


def shape_class(bucket, shards: int = 1) -> str:
    """Bucket shape key for the cost table: rows-per-entity S, ELL width K,
    local dim P, dtype. Entity count E is EXCLUDED — per-entity solve cost
    is what the table stores, and chunking makes it E-independent.

    ``shards`` (the entity-axis mesh size) lands in the key as a ``@devN``
    suffix: a per-entity cost measured across an N-device mesh prices the
    collective dispatch + per-device slice and is NOT comparable to a
    single-device cost, so a table persisted by an 8-device run can never
    steer a 1-device restart (and vice versa) — the same refusal contract
    as the bench gate's cross-device-count comparisons."""
    _, s, k = bucket.idx.shape
    key = f"s{s}k{k}p{bucket.local_dim}:{np.dtype(bucket.val.dtype).name}"
    return key if shards <= 1 else f"{key}@dev{shards}"


@dataclasses.dataclass(frozen=True)
class Candidate:
    """One raceable (solver, chunk-size) combination."""

    solver: str   # newton_primal | newton_dual | vmapped_lbfgs
    chunk: int

    @property
    def key(self) -> str:
        return f"{self.solver}@{self.chunk}"


class SolverCostTable:
    """Thread-safe per-(shape class, candidate) measured cost store.

    Costs are seconds per PADDED entity lane at the candidate's chunk size
    (every candidate races at its own chunk, so padding waste is priced
    in). ``winner`` returns the cheapest recorded candidate that is still
    feasible for the caller's bucket.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._entries: dict = {}            # shape_key -> {cand_key: cost}

    def record(self, shape_key: str, cand: Candidate,
               seconds_per_entity: float) -> None:
        with self._lock:
            self._entries.setdefault(shape_key, {})[cand.key] = float(
                seconds_per_entity)

    def costs(self, shape_key: str) -> dict:
        with self._lock:
            return dict(self._entries.get(shape_key, {}))

    def winner(self, shape_key: str,
               feasible: Sequence[Candidate]) -> Optional[Candidate]:
        """Cheapest recorded candidate among ``feasible``, or None unless
        EVERY feasible candidate has a recorded cost (the caller then
        calibrates the missing ones). Requiring full coverage matters: a
        table persisted by a run whose budget/ladder admitted fewer
        candidates must not permanently pin routing to the only solver it
        happened to measure — the unraced candidate could be the winner."""
        by_key = {c.key: c for c in feasible}
        with self._lock:
            entries = self._entries.get(shape_key)
            if not entries:
                return None
            hits = [(cost, k) for k, cost in entries.items() if k in by_key]
        if len(hits) < len(by_key):
            return None
        return by_key[min(hits)[1]]

    def to_json(self) -> dict:
        with self._lock:
            return {"version": 1,
                    "entries": {k: dict(v) for k, v in self._entries.items()}}

    def load_json(self, payload: dict) -> None:
        if payload.get("version") != 1:
            raise ValueError(
                f"unsupported cost-table version {payload.get('version')!r}"
            )
        entries = payload.get("entries", {})
        with self._lock:
            for k, v in entries.items():
                self._entries.setdefault(k, {}).update(
                    {ck: float(c) for ck, c in v.items()})

    def save(self, path: str) -> None:
        """Atomic write (tmp + rename): a preemption mid-save must not leave
        a torn table for the restarted attempt to refuse."""
        payload = self.to_json()
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
        os.replace(tmp, path)

    def load(self, path: str) -> None:
        with open(path) as f:
            self.load_json(json.load(f))

    def merge(self, other: "SolverCostTable") -> None:
        """Merge another table's measurements in (mean where both measured
        a candidate, adopt where only one did). The multi-process mesh
        story: each host races its local shard of the calibration probe
        and the driver merges per-host tables into ONE persisted table —
        keys carry the device count (``shape_class`` ``@devN`` suffix), so
        merging never averages across different mesh sizes."""
        with other._lock:
            theirs = {k: dict(v) for k, v in other._entries.items()}
        with self._lock:
            for key, cands in theirs.items():
                mine = self._entries.setdefault(key, {})
                for ck, cost in cands.items():
                    if ck in mine:
                        mine[ck] = 0.5 * (mine[ck] + float(cost))
                    else:
                        mine[ck] = float(cost)

    def reset(self) -> None:
        with self._lock:
            self._entries.clear()


_TABLE = SolverCostTable()
_loaded_paths: set = set()
_load_lock = threading.Lock()


def process_table() -> SolverCostTable:
    """The process-global table, hydrated once per distinct
    ``PHOTON_RE_COST_TABLE`` path (warm restarts skip calibration)."""
    path = os.environ.get(TABLE_ENV)
    if path:
        with _load_lock:
            if path not in _loaded_paths:
                _loaded_paths.add(path)
                if os.path.exists(path):
                    _TABLE.load(path)
    return _TABLE


def _persist(table: SolverCostTable) -> None:
    path = os.environ.get(TABLE_ENV)
    if path:
        table.save(path)


def reset_process_table() -> None:
    """Forget measurements and load history (tests)."""
    with _load_lock:
        _TABLE.reset()
        _loaded_paths.clear()


def merge_host_tables(paths, out_path: str) -> SolverCostTable:
    """Fold several per-host cost tables into one persisted table.

    The multi-host run writes ``solver_costs.host-<id>.json`` per host;
    the coordinator folds them here into ``solver_costs.merged.json`` so a
    warm restart of ANY host (pointing ``PHOTON_RE_COST_TABLE`` at the
    merged file) skips calibration outright. ``merge`` means overlapping
    measurements and the ``@devN`` shape-class suffix keeps entries from a
    different local-mesh topology inert, so folding is always safe.
    Unreadable shards are skipped — a torn per-host file must not poison
    the merged table."""
    merged = SolverCostTable()
    for p in paths:
        other = SolverCostTable()
        try:
            other.load(p)
        except (OSError, ValueError, KeyError):
            continue
        merged.merge(other)
    merged.save(out_path)
    return merged


def candidates_for(problem, bucket, normalization, u_max: int,
                   shards: int = 1) -> list:
    """Feasible chunked candidates for this bucket, Newton variants first.

    The primal candidate is admitted up to ``NEWTON_CHUNK_MAX_P`` (wider
    than the static gate): in (64, 128] the dense Hessian may or may not
    beat L-BFGS depending on S — exactly the call the race exists to make.
    The vmapped baseline is always feasible and always raced, so "Newton
    by default" is a measured claim, not an assumption. ``shards`` > 1
    restricts chunks to mesh-divisible blessed sizes and prices the
    per-device slice (``newton_re.newton_chunk_size``).
    """
    out = []
    c = newton_re.newton_chunk_size(
        problem, bucket, normalization, max_p=newton_re.NEWTON_CHUNK_MAX_P,
        shards=shards)
    if c:
        out.append(Candidate("newton_primal", c))
    # u_max < 0 means the caller's dual precheck already refused the bucket
    # (so the device-synced unpenalized-column count was never computed).
    c = (newton_re.dual_chunk_size(problem, bucket, normalization, u_max,
                                   shards=shards)
         if u_max >= 0 else None)
    if c:
        out.append(Candidate("newton_dual", c))
    if out:
        # Baseline races (and, if it wins, executes) at a capped chunk:
        # probing full-history L-BFGS at a 16K-entity chunk would cost more
        # than the race saves, and its per-entity cost is nearly flat in
        # chunk size. Probe shape == execution shape either way. Under a
        # mesh the cap rounds down to a shard-divisible size.
        cap = VMAPPED_CHUNK_CAP
        if shards > 1:
            cap = max(shards, cap - cap % shards)
        out.append(Candidate(
            "vmapped_lbfgs", min(max(cand.chunk for cand in out), cap)))
    return out


def solve_measured(
    problem,
    bucket,
    batches,
    w0,
    local_mask,
    local_prior,
    normalization,
    u_max: int,
    fit_for: Callable[[str], Callable],
    sync: Callable,
    table: Optional[SolverCostTable] = None,
    shards: int = 1,
    place: Optional[Callable] = None,
):
    """Route one bucket through the measured cost table.

    ``fit_for(solver) -> fit_one(batches, w0, mask, prior)`` supplies the
    per-solver chunk closures (built by ``random_effect._solve_bucket`` so
    this module stays import-cycle-free); ``sync`` forces one leaf of a
    solve output to the host (the repo-standard tiny D2H read, which
    forces completion).

    Under a mesh (``shards`` > 1, ``place`` the entity-sharded device_put)
    the calibration probes dispatch SHARDED — every device races its slice
    of the probe chunk concurrently, so one timed probe IS the per-device
    calibration, merged by construction — and costs land under the
    ``@devN``-suffixed shape key (``shape_class``), persisted with the
    device count so cross-mesh routing can never cross-read.

    Returns ``(models, result, info)`` with ``info`` carrying the routing
    decision and the calibration cost:
    ``{solver, chunk, routing, calibration_seconds, calibrated}``.
    """
    table = table if table is not None else process_table()
    key = shape_class(bucket, shards)
    cands = candidates_for(problem, bucket, normalization, u_max,
                           shards=shards)
    info = {"routing": "measured", "calibration_seconds": 0.0,
            "calibrated": False}

    if not any(c.solver != "vmapped_lbfgs" for c in cands):
        # Calibration refused every Newton variant (non-smooth objective,
        # normalization context, S+U over the dual cap AND P over the
        # chunked-primal cap, or nothing fits the budget): nothing to race
        # — the general vmapped path solves the whole bucket unchunked,
        # exactly as static routing would.
        args = (batches, w0, local_mask, local_prior)
        if place is not None:
            args = place(args)
        models, result = fit_for("vmapped_lbfgs")(*args)
        info.update(solver="vmapped_lbfgs", chunk=None)
        return models, result, info

    win = table.winner(key, cands)
    if win is None:
        from photon_tpu.obs.retrace import compile_watch

        t0 = time.perf_counter()
        cal_compile = 0.0
        e = w0.shape[0]
        recorded = table.costs(key)
        for cand in cands:
            if cand.key in recorded:
                continue  # incremental race: only unmeasured candidates pay
            fit_one = fit_for(cand.solver)
            probe_e = min(e, cand.chunk)
            probe_args = (
                newton_re._slice_pad_batches(batches, 0, probe_e, cand.chunk),
                newton_re._slice_pad_lanes(w0, 0, probe_e, cand.chunk),
                newton_re._slice_pad_lanes(local_mask, 0, probe_e,
                                           cand.chunk, fill=1),
                (jax.tree.map(
                    lambda a: newton_re._slice_pad_lanes(
                        a, 0, probe_e, cand.chunk), local_prior)
                 if local_prior is not None else None),
            )
            if place is not None:
                # Probe shape == execution shape INCLUDING the sharding:
                # the race times the sharded dispatch the real solve uses.
                probe_args = place(probe_args)
            # ONE sync-timed probe per candidate; the XLA compile it pays
            # (host-synchronous before dispatch returns) is measured by the
            # sentinel watch and subtracted, so the recorded cost is the
            # executable's — which the real solve reuses (same blessed
            # shape) — without a second full probe solve.
            t1 = time.perf_counter()
            with compile_watch() as cw:
                out = fit_one(*probe_args)
            sync(out)
            exec_s = max(time.perf_counter() - t1 - cw.compile_seconds,
                         1e-9)
            cal_compile += cw.compile_seconds
            table.record(key, cand, exec_s / cand.chunk)
        # The probes' first-trace compiles are already accounted under
        # compile_seconds (the caller's watched dispatch wrappers saw the
        # same traces) — subtract them here so the two columns partition
        # the wall instead of double-counting it.
        info["calibration_seconds"] = round(
            max(time.perf_counter() - t0 - cal_compile, 0.0), 3)
        info["calibrated"] = True
        _persist(table)
        win = table.winner(key, cands)

    fit_one = fit_for(win.solver)
    models, result = newton_re.fit_bucket_in_chunks(
        fit_one, win.chunk, batches, w0, local_mask, local_prior,
        put=place, ahead=1 if place is not None else 0)
    info.update(solver=win.solver, chunk=win.chunk)
    return models, result, info
