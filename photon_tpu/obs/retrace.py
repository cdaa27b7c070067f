"""Retrace sentinel + device-memory watermark.

XLA recompilation is the serving path's silent killer: a jitted kernel that
retraces on a hot path turns a ~1 ms dispatch into a multi-second compile,
and nothing in the request path says why. The sentinel makes retraces a
first-class, *watched* metric:

* Registered kernels call :func:`note_trace` **inside their traced body**
  — the Python side-effect runs exactly once per distinct input signature,
  i.e. once per XLA compilation — bumping the process-global
  ``kernel_traces_total{kernel=...}`` counter (Prometheus-visible through
  any server's ``/metrics?format=prom``).
* After a component finishes warming its shape ladder it calls
  :func:`mark_warm`. From then on, any further trace of that kernel is a
  **retrace after warmup**: the sentinel logs a warning and emits a
  ``retrace`` instant event into the active trace, so a retrace storm shows
  up in the Perfetto timeline exactly where the latency went.

``SCORE_KERNEL_STATS`` in ``estimators.game_transformer`` is now a
back-compat alias over this module (thread-safe, resettable), and
``RowScorer.warmup`` marks the scoring kernel warm.

Also here: :func:`install_device_memory_gauges` registers callback gauges
for the accelerator's live/peak bytes (``device.memory_stats()`` where the
backend provides it — a no-op series on CPU), the watermark a capacity
planner needs next to queue depth and latency.
"""
from __future__ import annotations

import logging
import threading
from typing import Optional

from photon_tpu.obs import trace as _trace
from photon_tpu.obs.metrics import REGISTRY, MetricsRegistry

__all__ = [
    "RE_SOLVER_KERNELS",
    "compile_watch",
    "expected_compiles",
    "note_trace",
    "mark_warm",
    "clear_warm",
    "traces",
    "retraces_after_warmup",
    "all_traces",
    "reset",
    "install_device_memory_gauges",
]

# The registered random-effect bucket-solver kernels (game/newton_re.py +
# game/random_effect.py). One place so the compile/solve timing split, the
# descent-loop warmup marking, and the tests all watch the same names.
RE_SOLVER_KERNELS = (
    "fit_bucket_newton",
    "fit_bucket_newton_dual",
    "fit_bucket_vmapped",
)

logger = logging.getLogger("photon_tpu.obs")

_lock = threading.Lock()
_warm: set[str] = set()
_tls = threading.local()

_TRACES = REGISTRY.counter(
    "kernel_traces_total",
    "XLA compilations per registered jitted kernel (traced-body count)",
)
_RETRACES = REGISTRY.counter(
    "kernel_retraces_after_warmup_total",
    "Compilations that happened AFTER the kernel was marked warm — each one "
    "stalled a hot path behind XLA",
)


class expected_compiles:
    """``with expected_compiles():`` — this THREAD's compilations are
    deliberate (a hot-swap warming a new version's shape ladder) and must
    not fire retrace warnings. Thread-local on purpose: while one thread
    warms a swap, retraces on the still-serving threads keep warning —
    disarming the sentinel process-wide would blind it during exactly the
    window a swap-induced retrace storm would start. Compile COUNTS still
    accrue; only the after-warmup warning/event/counter are skipped."""

    __slots__ = ()

    def __enter__(self) -> None:
        _tls.expected = getattr(_tls, "expected", 0) + 1

    def __exit__(self, *exc) -> None:
        _tls.expected -= 1


class compile_watch:
    """``with compile_watch() as cw: out = jitted(...)`` — split first-trace
    compile time from solve time via the sentinel's trace counters.

    Wrap the UNSYNCED dispatch only: jit tracing + XLA compilation run
    synchronously in the calling thread before dispatch returns, while
    execution is enqueued asynchronously — so when ``cw.compiled`` is
    non-empty the dispatch wall time is (to enqueue overhead, microseconds)
    the compile time, and when it is empty the wall time is pure dispatch.
    This is how ``train_random_effects`` stamps ``compile_seconds`` into
    ``LAST_BUCKET_TIMINGS`` / bench artifacts / trace spans without a
    blocking device sync per bucket.

    ``cw.seconds`` — dispatch wall. ``cw.compiled`` — {kernel: new traces}
    for watched kernels that compiled inside the block. ``cw.compile_seconds``
    — ``seconds`` if anything compiled, else 0.0.
    """

    def __init__(self, kernels=RE_SOLVER_KERNELS) -> None:
        self.kernels = tuple(kernels)
        self.seconds = 0.0
        self.compiled: dict = {}

    def __enter__(self) -> "compile_watch":
        import time as _time

        self._before = {k: int(_TRACES.value(kernel=k)) for k in self.kernels}
        self._t0 = _time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        import time as _time

        self.seconds = _time.perf_counter() - self._t0
        self.compiled = {
            k: int(_TRACES.value(kernel=k)) - b
            for k, b in self._before.items()
            if int(_TRACES.value(kernel=k)) > b
        }

    @property
    def compile_seconds(self) -> float:
        return self.seconds if self.compiled else 0.0


def note_trace(kernel: str) -> None:
    """Record one compilation of ``kernel``. Call from inside the jitted
    function body (runs only at trace time, costs nothing per dispatch)."""
    _TRACES.inc(kernel=kernel)
    if getattr(_tls, "expected", 0):
        return
    with _lock:
        warmed = kernel in _warm
    if warmed:
        _RETRACES.inc(kernel=kernel)
        logger.warning(
            "kernel %s retraced after warmup (trace #%d) — a hot-path "
            "request is paying an XLA compile; check for unstable shapes "
            "or dtypes", kernel, int(_TRACES.value(kernel=kernel)),
        )
        _trace.instant(
            "retrace", cat="warning",
            kernel=kernel, traces=int(_TRACES.value(kernel=kernel)),
        )


def mark_warm(kernel: str) -> None:
    """Declare ``kernel``'s shape ladder fully compiled; later traces warn."""
    with _lock:
        _warm.add(kernel)


def clear_warm(kernel: Optional[str] = None) -> None:
    """Forget warm state (model swap re-warms; tests)."""
    with _lock:
        if kernel is None:
            _warm.clear()
        else:
            _warm.discard(kernel)


def traces(kernel: str) -> int:
    return int(_TRACES.value(kernel=kernel))


def retraces_after_warmup(kernel: str) -> int:
    return int(_RETRACES.value(kernel=kernel))


def all_traces() -> dict:
    """kernel → compilation count, for JSON snapshots."""
    return {
        labels.get("kernel", ""): int(v)
        for labels, v in _TRACES.collect()
        if labels
    }


def reset() -> None:
    """Zero counters and warm state (tests)."""
    _TRACES.reset()
    _RETRACES.reset()
    clear_warm()


def _memory_stats() -> dict:
    """{(label_tuple): bytes} series for live + peak device memory, or {}
    when the backend exposes no stats (CPU)."""
    try:
        import jax

        out = {}
        for d in jax.local_devices():
            stats = d.memory_stats()
            if not stats:
                continue
            key = f"{d.platform}:{d.id}"
            if "bytes_in_use" in stats:
                out[(("device", key), ("kind", "in_use"))] = float(
                    stats["bytes_in_use"])
            if "peak_bytes_in_use" in stats:
                out[(("device", key), ("kind", "peak"))] = float(
                    stats["peak_bytes_in_use"])
        return out
    except Exception:  # noqa: BLE001 - a sick backend must not break /metrics
        return {}


def install_device_memory_gauges(
    registry: Optional[MetricsRegistry] = None,
) -> None:
    """Register the ``device_memory_bytes`` callback gauge (live + peak
    watermark per device). Idempotent; callers pass their own registry or
    default to the process-global one."""
    (registry or REGISTRY).gauge_fn(
        "device_memory_bytes",
        _memory_stats,
        "Device memory watermark: bytes_in_use and peak_bytes_in_use per "
        "local device (absent on backends without memory_stats)",
    )
