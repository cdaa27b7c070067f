"""Runtime-layer services: backend health, failure policy, recovery.

The reference inherited its runtime resilience from Spark (a lost executor
is rescheduled, lineage replays the partition — SURVEY.md §5.3); the
rebuild's runtime is a JAX backend client whose failure modes — init hangs,
compile errors, device loss, OOM — previously surfaced as unclassified
exceptions or, worse, silent hangs inside backend init.
``backend_guard`` makes backend failure a first-class, tested contract:
fail fast under a hard deadline, classify the cause, and recover under an
explicit policy (docs/robustness.md §"Backend-failure resilience").
``compile_store`` makes recovery CHEAP: an AOT compile-artifact store +
manifest so restarts, device-loss re-steps, and serving hot-swaps load
compiled executables instead of re-paying XLA (docs/robustness.md
§"Recovery time").
"""
from photon_tpu.runtime.backend_guard import (
    BACKEND_POLICIES,
    BackendProbeResult,
    BackendUnusable,
    backend_init_timeout_s,
    classify_backend_error,
    ensure_backend,
    guard_snapshot,
    is_device_lost,
    max_inrun_recoveries,
    probe_backend,
    recover_from_device_loss,
)
from photon_tpu.runtime.compile_store import (
    CompileStore,
    compile_split,
)
from photon_tpu.runtime.memory_guard import (
    MemoryGuard,
    OomDownshifter,
    is_oom,
    max_oom_downshifts,
)

__all__ = [
    "CompileStore",
    "compile_split",
    "MemoryGuard",
    "OomDownshifter",
    "is_oom",
    "max_oom_downshifts",
    "BACKEND_POLICIES",
    "BackendProbeResult",
    "BackendUnusable",
    "backend_init_timeout_s",
    "classify_backend_error",
    "ensure_backend",
    "guard_snapshot",
    "is_device_lost",
    "max_inrun_recoveries",
    "probe_backend",
    "recover_from_device_loss",
]
