"""Fail-fast backend health probe, error classification, failure policy.

Why this module exists: an accelerator that cannot be initialized blocks
``jax.devices()`` inside C++ — minutes, sometimes for good — and JAX left
to itself then falls to the CPU without a word, so a run meant for the chip
trains on the wrong hardware and still exits 0. Upstream photon-ml never had
this problem class — Spark re-schedules a lost executor and lineage replays
its partition — so the rebuild needs an explicit contract where the
reference had a runtime.

Three pieces:

* :func:`probe_backend` — a SUBPROCESS-isolated backend init with a hard
  deadline (``PHOTON_BACKEND_INIT_TIMEOUT_S``, default 120 s). No in-process
  timeout can interrupt a blocked backend init, so the probe must be a
  child process the parent can kill: SIGTERM first, SIGKILL as the
  backstop. The child claims the chip and has exited before the parent
  initializes JAX — a chip belongs to one process at a time, and ownership
  passes cleanly from one process to the next.
* :func:`classify_backend_error` — maps backend failures onto the causes
  the recovery layers act on: ``init_unavailable`` (UNAVAILABLE / init
  hang / no accelerator found), ``compile_error``, ``device_lost`` (mid-run
  loss: the only in-run-recoverable cause), ``host_lost`` and ``oom``.
  Everything else is ``unknown`` — never guessed.
* :func:`ensure_backend` — the ``--backend-policy`` contract shared by
  every CLI driver:

  ========== ==============================================================
  policy     on probe failure
  ========== ==============================================================
  strict     raise :class:`BackendUnusable` (classified cause; driver
             exits nonzero) — the default: never silently train on the
             wrong hardware. A probe that comes back on the CPU when the
             CPU was not asked for (``JAX_PLATFORMS`` / ``jax_platforms``
             does not name it) IS a failure.
  failover   re-enter on the next available backend (CPU), stamping the
             swap into :func:`guard_snapshot` so provenance can never
             mistake a failover round for an accelerator number
  cpu-only   pin the CPU backend up front; no probe, no accelerator
  ========== ==============================================================

In-run recovery (device loss mid-sweep) lives here too —
:func:`recover_from_device_loss` is the shared checkpoint-then-clear-then-
resume step ``game/descent.py`` and ``optim/out_of_core.py`` call; see
docs/robustness.md for the full ladder.
"""
from __future__ import annotations

import dataclasses
import os
import re
import time
from typing import Optional

__all__ = [
    "BACKEND_POLICIES",
    "CAUSE_INIT_UNAVAILABLE",
    "CAUSE_COMPILE_ERROR",
    "CAUSE_DEVICE_LOST",
    "CAUSE_HOST_LOST",
    "CAUSE_OOM",
    "CAUSE_UNKNOWN",
    "BackendProbeResult",
    "BackendUnusable",
    "backend_init_timeout_s",
    "classify_backend_error",
    "ensure_backend",
    "guard_snapshot",
    "is_device_lost",
    "max_inrun_recoveries",
    "probe_backend",
    "record_failover",
    "recover_from_device_loss",
    "reset_guard",
]

BACKEND_POLICIES = ("strict", "failover", "cpu-only")

CAUSE_INIT_UNAVAILABLE = "init_unavailable"
CAUSE_COMPILE_ERROR = "compile_error"
CAUSE_DEVICE_LOST = "device_lost"
CAUSE_HOST_LOST = "host_lost"
CAUSE_OOM = "oom"
CAUSE_UNKNOWN = "unknown"


def backend_init_timeout_s(default: float = 120.0) -> float:
    """Hard deadline for backend init (``PHOTON_BACKEND_INIT_TIMEOUT_S``).

    A healthy accelerator comes up in seconds, so anything past two minutes
    is a hang, not a slow success. Malformed/negative values fall back
    to ``default`` (a typo'd override must degrade the deadline, never
    disable fail-fast)."""
    try:
        v = float(os.environ.get("PHOTON_BACKEND_INIT_TIMEOUT_S", default))
    except (TypeError, ValueError):
        return float(default)
    return v if v > 0 else float(default)


def max_inrun_recoveries(default: int = 2) -> int:
    """Bound on in-run device-loss recoveries per scope
    (``PHOTON_DEVICE_LOST_MAX_RECOVERIES``): past it the error escalates to
    the :class:`~photon_tpu.supervisor.RunSupervisor` restart path."""
    try:
        return max(0, int(os.environ.get(
            "PHOTON_DEVICE_LOST_MAX_RECOVERIES", default)))
    except (TypeError, ValueError):
        return int(default)


# Ordered classification: FIRST match wins, so the ordering is part of the
# contract. ``init_unavailable`` outranks ``compile_error`` because an
# init-phase failure reads "UNAVAILABLE: TPU backend setup/compile error" —
# it merely mentions compilation, and restart-with-backoff (not a code
# change) is its remedy.
_CAUSE_PATTERNS: tuple = (
    # ``host_lost`` first: a dead PEER HOST often surfaces through the same
    # transport noise a dead local device does ("connection reset" from the
    # coordinator, a collective that never completes) — when the message
    # names a peer host / missed beacon / mesh barrier, the whole-host
    # protocol (mesh shrink, parallel/distributed.MeshMembership) owns the
    # recovery, not the single-device ``recover_from_device_loss`` path.
    (CAUSE_HOST_LOST, re.compile(
        r"peer host|host\W{0,3}(was\s+)?lost|missed beacon"
        r"|beacon.{0,30}stale|mesh barrier.{0,30}(timed? ?out|timeout)"
        r"|collective.{0,40}waiting for host",
        re.IGNORECASE)),
    (CAUSE_OOM, re.compile(
        r"RESOURCE_EXHAUSTED|out of memory|\bOOM\b|hbm.{0,20}exhausted",
        re.IGNORECASE)),
    (CAUSE_DEVICE_LOST, re.compile(
        r"device\W{0,3}(was\s+)?lost|DEVICE_LOST|device is in an invalid"
        r"|socket closed|connection reset|broken pipe.{0,40}device",
        re.IGNORECASE)),
    (CAUSE_INIT_UNAVAILABLE, re.compile(
        r"UNAVAILABLE|[Uu]nable to initialize backend"
        r"|[Ff]ailed to initialize|[Nn]o visible device"
        r"|backend init.{0,30}(timed? ?out|deadline)"
        r"|probe hung|no accelerator",
    )),
    (CAUSE_COMPILE_ERROR, re.compile(
        r"XlaCompile|compilation (error|failure|failed)"
        r"|compile (error|failed)|lowering (error|failed)|Mosaic failed",
        re.IGNORECASE)),
)


def classify_backend_error(err) -> str:
    """One of the cause constants for an exception (or message text).

    Exception *types* outrank message text: an injected
    :class:`~photon_tpu.faults.DeviceLostError` or a real ``MemoryError``
    classifies by what it is, not what it says."""
    text = err if isinstance(err, str) else f"{type(err).__name__}: {err}"
    if not isinstance(err, str):
        from photon_tpu.faults import DeviceLostError, DeviceOomError

        if isinstance(err, DeviceLostError):
            return CAUSE_DEVICE_LOST
        if isinstance(err, (MemoryError, DeviceOomError)):
            return CAUSE_OOM
        if isinstance(err, (OSError, ConnectionError)):
            # A plain I/O error whose MESSAGE happens to say "connection
            # reset" / "socket closed" (an NFS hiccup, a dropped HTTP
            # peer) is NOT a device loss: it must take the io-retry /
            # supervisor path, never the in-run recovery's
            # executable-cache purge. Real device losses surface as
            # XlaRuntimeError (a RuntimeError), which still classifies by
            # text below.
            return CAUSE_UNKNOWN
    for cause, pattern in _CAUSE_PATTERNS:
        if pattern.search(text):
            return cause
    return CAUSE_UNKNOWN


def is_device_lost(err) -> bool:
    """Is this the one cause the in-run recovery path may absorb?"""
    return classify_backend_error(err) == CAUSE_DEVICE_LOST


class BackendUnusable(RuntimeError):
    """The backend failed its health probe under ``--backend-policy
    strict``: carries the classified ``cause`` and the probe's ``reason``
    so the driver's nonzero exit is diagnosable from the one-line error."""

    def __init__(self, cause: str, reason: str):
        self.cause = cause
        self.reason = reason
        super().__init__(f"backend unusable [{cause}]: {reason}")


@dataclasses.dataclass(frozen=True)
class BackendProbeResult:
    """Outcome of one (possibly multi-attempt) subprocess probe."""

    ok: bool
    backend: str             # jax.default_backend() seen by the probe child
    seconds: float           # wall time of the LAST attempt
    attempts: int
    cause: Optional[str] = None
    reason: Optional[str] = None

    def to_dict(self) -> dict:
        out = dataclasses.asdict(self)
        return {k: v for k, v in out.items() if v is not None}


_PROBE_MARK = "PHOTON_BACKEND="
_DEFAULT_PROBE_CODE = (
    "import jax, jax.numpy as jnp; "
    "jnp.ones((8,)).sum().block_until_ready(); "
    f"print('{_PROBE_MARK}' + jax.default_backend())"
)

def _probe_once(code: str, timeout_s: float) -> BackendProbeResult:
    import subprocess
    import sys

    t0 = time.monotonic()
    # Popen + SIGTERM grace, not subprocess.run's SIGKILL: the child gets
    # the chance to release the chip before the parent claims it.
    p = subprocess.Popen(
        [sys.executable, "-c", code],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    try:
        out, err = p.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        p.terminate()
        try:
            p.communicate(timeout=10)
        except subprocess.TimeoutExpired:
            p.kill()
            p.communicate()
        took = time.monotonic() - t0
        return BackendProbeResult(
            ok=False, backend="", seconds=took, attempts=1,
            cause=CAUSE_INIT_UNAVAILABLE,
            reason=(f"backend init timed out after {timeout_s:.0f}s "
                    "deadline — probe child killed"),
        )
    took = time.monotonic() - t0
    backend = ""
    for line in (out or "").splitlines():
        if line.startswith(_PROBE_MARK):
            backend = line[len(_PROBE_MARK):].strip()
    if p.returncode == 0 and backend:
        return BackendProbeResult(
            ok=True, backend=backend, seconds=took, attempts=1)
    tail = (err or out or "").strip()[-400:]
    reason = f"probe exited {p.returncode}: {tail}" if tail else (
        f"probe exited {p.returncode} with no output")
    return BackendProbeResult(
        ok=False, backend=backend, seconds=took, attempts=1,
        cause=classify_backend_error(tail or reason), reason=reason,
    )


def probe_backend(
    timeout_s: Optional[float] = None,
    attempts: Optional[int] = None,
    probe_code: Optional[str] = None,
) -> BackendProbeResult:
    """Subprocess-isolated backend health check under a hard deadline.

    ``probe_code`` is the test/chaos seam: recovery drills substitute a
    child that hangs or prints a canned UNAVAILABLE traceback, and the
    deadline-kill + classification path runs for real without a chip.
    ``attempts`` (``PHOTON_BACKEND_PROBE_ATTEMPTS``, default 1) retries
    the probe; attempt counts are stamped into provenance either way."""
    deadline = backend_init_timeout_s() if timeout_s is None else timeout_s
    if attempts is None:
        try:
            attempts = max(1, int(os.environ.get(
                "PHOTON_BACKEND_PROBE_ATTEMPTS", "1")))
        except (TypeError, ValueError):
            attempts = 1
    code = probe_code or _DEFAULT_PROBE_CODE
    last = None
    for i in range(attempts):
        last = _probe_once(code, deadline)
        if last.ok:
            return dataclasses.replace(last, attempts=i + 1)
    return dataclasses.replace(last, attempts=attempts)


# ------------------------------------------------------------- guard state
#
# One guard decision per process (the probe is an up-front gate, not a
# recurring cost); bench provenance and /healthz read the snapshot.

_STATE: Optional[dict] = None
_PROBED_OK = False  # per-process probe memo: one subprocess, not one per run()


def guard_snapshot() -> Optional[dict]:
    """The guard's decision record for provenance stamping, or None when
    no guard ran in this process: ``{policy, backend, backend_init_seconds,
    probe_attempts, failover}``."""
    return None if _STATE is None else dict(_STATE)


def reset_guard() -> None:
    """Test hook: forget the per-process guard decision + probe memo."""
    global _STATE, _PROBED_OK
    _STATE = None
    _PROBED_OK = False


def _jax_initialized() -> bool:
    """True when THIS process already has a live jax backend — probing a
    subprocess then proves nothing the parent doesn't already know, and
    costs seconds per driver run (tests call drivers dozens of times)."""
    import sys

    if "jax" not in sys.modules:
        return False
    try:
        from jax._src import xla_bridge as xb

        return bool(getattr(xb, "_backends", None))
    except Exception:  # noqa: BLE001 - private API; absence = not provable
        return False


def _pin_cpu() -> None:
    import jax

    jax.config.update("jax_platforms", "cpu")


def _cpu_asked_for() -> bool:
    """Did the operator name the CPU as a platform to run on? Read from
    ``jax_platforms`` once jax is imported (it follows ``JAX_PLATFORMS``
    and any in-code pin), else from the variable itself."""
    import sys

    jax = sys.modules.get("jax")
    asked = (jax.config.jax_platforms if jax is not None
             else os.environ.get("JAX_PLATFORMS")) or ""
    return "cpu" in asked.split(",")


def _refuse_unasked_cpu(probe: BackendProbeResult) -> BackendProbeResult:
    """A healthy probe on a backend that is no accelerator, when the CPU
    was not asked for, means JAX found no chip and fell back: a failure."""
    from photon_tpu.types import REAL_ACCELERATOR_BACKENDS

    if (not probe.ok or not probe.backend  # failed already, or not known
            or probe.backend in REAL_ACCELERATOR_BACKENDS
            or _cpu_asked_for()):
        return probe
    return dataclasses.replace(
        probe, ok=False, cause=CAUSE_INIT_UNAVAILABLE,
        reason=(f"no accelerator: JAX fell back to {probe.backend!r}, and "
                "JAX_PLATFORMS does not ask for it (name cpu there, or use "
                "--backend-policy cpu-only, to run on the CPU on purpose)"))


def ensure_backend(
    policy: str = "strict",
    timeout_s: Optional[float] = None,
    logger=None,
    probe_code: Optional[str] = None,
) -> dict:
    """Enforce the backend policy before any in-process jax backend init.

    Returns the guard snapshot (also kept module-global for provenance).
    Under ``strict`` a failed probe raises :class:`BackendUnusable`; under
    ``failover`` the process re-enters on CPU with the swap recorded (a
    ``backend_failovers_total{cause=...}`` counter bump + a
    ``recovery.backend_failover`` trace instant + the snapshot stamp);
    ``cpu-only`` pins CPU and never touches the accelerator."""
    global _STATE, _PROBED_OK
    if policy not in BACKEND_POLICIES:
        raise ValueError(
            f"unknown backend policy {policy!r}; known: {BACKEND_POLICIES}")
    if policy == "cpu-only":
        _pin_cpu()
        _STATE = {"policy": policy, "backend": "cpu",
                  "backend_init_seconds": 0.0, "probe_attempts": 0,
                  "failover": None}
        return dict(_STATE)

    in_process = probe_code is None and (_PROBED_OK or _jax_initialized())
    if in_process:
        # Either this process's earlier probe passed (its verdict stands,
        # backend "" = nothing new known) or jax is already live here and
        # names its backend itself: judge that without a subprocess.
        import sys

        backend = (sys.modules["jax"].default_backend()
                   if _jax_initialized() else "")
        probe = BackendProbeResult(
            ok=True, backend=backend, seconds=0.0, attempts=0)
    else:
        probe = probe_backend(timeout_s=timeout_s, probe_code=probe_code)
    probe = _refuse_unasked_cpu(probe)
    if probe.ok:
        if in_process and _STATE is not None and _STATE.get("backend"):
            # Keep the stamp of the probe that did run; refresh the rest.
            _STATE["policy"] = policy
            if probe.backend:
                _STATE["backend"] = probe.backend
        else:
            _PROBED_OK = _PROBED_OK or not in_process
            _STATE = {"policy": policy, "backend": probe.backend or None,
                      "backend_init_seconds": round(probe.seconds, 3),
                      "probe_attempts": probe.attempts, "failover": None}
        return dict(_STATE)

    from photon_tpu.obs import instant
    from photon_tpu.obs.metrics import REGISTRY

    REGISTRY.counter(
        "backend_probe_failures_total",
        "health-probe failures by classified cause (runtime/backend_guard)",
    ).inc(cause=probe.cause or CAUSE_UNKNOWN)
    instant("recovery.backend_probe_failed", cat="recovery",
            cause=probe.cause, reason=probe.reason,
            seconds=round(probe.seconds, 3), policy=policy)
    if logger is not None:
        logger.warning(
            "backend probe failed [%s] after %.1fs (attempt %d): %s",
            probe.cause, probe.seconds, probe.attempts, probe.reason)
    if policy == "strict":
        raise BackendUnusable(probe.cause or CAUSE_UNKNOWN,
                              probe.reason or "probe failed")
    return record_failover(probe, logger=logger, policy=policy)


def record_failover(
    probe: BackendProbeResult, logger=None, policy: str = "failover",
) -> dict:
    """Re-enter on the next available backend and stamp the swap.

    CPU is always initializable in-process, so it is the universal next
    rung; the swap lands in the guard snapshot (→ bench provenance), the
    ``backend_failovers_total{cause=...}`` counter, and a
    ``recovery.backend_failover`` trace instant — so a failover round can
    NEVER masquerade as an accelerator number (PR 6 per-metric backend
    provenance refuses the cross-backend comparison). Shared by
    :func:`ensure_backend` and the :class:`~photon_tpu.supervisor.
    RunSupervisor` between-attempts path."""
    global _STATE
    from photon_tpu.obs import instant
    from photon_tpu.obs.metrics import REGISTRY

    _pin_cpu()
    failover = {
        "to": "cpu",
        "cause": probe.cause or CAUSE_UNKNOWN,
        "reason": probe.reason,
        "probe_seconds": round(probe.seconds, 3),
    }
    REGISTRY.counter(
        "backend_failovers_total",
        "policy-driven backend failovers by classified cause",
    ).inc(cause=failover["cause"])
    instant("recovery.backend_failover", cat="recovery", **failover)
    if logger is not None:
        logger.warning(
            "backend policy 'failover': re-entering on CPU [%s] — artifacts "
            "will stamp backend=cpu (not comparable to accelerator rounds)",
            failover["cause"])
    _STATE = {"policy": policy, "backend": "cpu",
              "backend_init_seconds": round(probe.seconds, 3),
              "probe_attempts": probe.attempts, "failover": failover}
    return dict(_STATE)


# --------------------------------------------------------- in-run recovery


def recover_from_device_loss(
    reason: str,
    device_cache=None,
    logger=None,
    reinit_client: bool = False,
) -> dict:
    """The shared mid-run recovery step (descent / out-of-core / scorer):

    1. drop jax's compiled-executable caches AND the retrace sentinel's
       warm marks (``supervisor.clear_executable_caches`` — the recompiles
       that follow are expected, not alarms);
    2. release device-resident sweep-cache pins (``device_cache`` when the
       caller owns one, else every live ``DeviceSweepCache`` in the
       process) — their device buffers died with the device;
    3. optionally re-initialize the backend client (``reinit_client``) —
       ONLY for callers holding no live device arrays (the supervisor's
       between-attempt path); in-run callers keep their host-checkpointed
       state and re-enter through fresh uploads.

    The caller checkpoints BEFORE calling this (checkpoint → clear →
    re-init → resume is the drill order the chaos suite asserts). Emits
    ``recovery.device_lost`` + ``recovery.backend_reinit`` trace instants
    and bumps ``run_restarts_total{cause="device_lost"}`` so the recovery
    is visible in metrics and the trace timeline."""
    from photon_tpu.obs import instant
    from photon_tpu.obs.metrics import REGISTRY

    instant("recovery.device_lost", cat="recovery", reason=reason)
    REGISTRY.counter(
        "run_restarts_total",
        "training restarts/recoveries by classified cause "
        "(docs/robustness.md §recovery journal)",
    ).inc(cause=CAUSE_DEVICE_LOST)

    from photon_tpu.supervisor import clear_executable_caches

    clear_executable_caches(f"device-loss recovery: {reason}")

    released = 0
    if device_cache is not None:
        device_cache.release()
        released = 1
    else:
        from photon_tpu.data.device_cache import release_all_caches

        released = release_all_caches()

    reinit = False
    if reinit_client:
        try:
            from jax.extend.backend import clear_backends

            clear_backends()
            reinit = True
        except Exception as e:  # noqa: BLE001 - version-dependent API
            if logger is not None:
                logger.warning("backend client re-init unavailable (%s: %s); "
                               "executable caches cleared only",
                               type(e).__name__, e)

    # Repopulate from the AOT compile store (runtime/compile_store.py):
    # every executable the purge dropped loads back from the persistent
    # cache BEFORE the caller re-enters its step, so the recovery re-step
    # dispatches warm instead of recompiling the whole kernel set cold.
    # AFTER the optional client re-init on purpose — clear_backends drops
    # the client the pre-warmed executables would live in, so warming
    # first would waste the whole pass and lie in the telemetry. prewarm
    # emits its own recovery.prewarm instant; a missing/failed store
    # degrades to the pre-store behavior (recompile on dispatch).
    from photon_tpu.runtime import compile_store as _cs

    prewarm = _cs.prewarm_if_active(reason=f"device-loss recovery: {reason}",
                                    logger_=logger)
    instant("recovery.backend_reinit", cat="recovery", reason=reason,
            caches_released=released, client_reinit=reinit,
            prewarm_loaded=None if prewarm is None else prewarm["loaded"])
    if logger is not None:
        logger.warning(
            "device-loss recovery (%s): executable caches cleared, %d sweep "
            "cache(s) released%s%s — resuming from checkpointed state",
            reason, released, ", backend client re-initialized"
            if reinit else "",
            "" if prewarm is None else
            f", {prewarm['loaded']} executable(s) pre-warmed from the "
            f"compile store ({prewarm['load_seconds']:.3f}s load, "
            f"{prewarm['xla_seconds']:.3f}s xla)")
    return {"caches_released": released, "client_reinit": reinit,
            "prewarm": prewarm}
