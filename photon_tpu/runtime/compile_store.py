"""AOT compile-artifact store: zero-recompile restarts and hot-swaps.

Why this module exists: every recovery path PR 8 built — supervised
checkpoint-resume restarts, in-run device-loss recovery, serving kernel
re-warmup — pays a full XLA retrace on re-entry, because
``supervisor.clear_executable_caches`` and process restarts drop every
compiled executable (PR 4 measured 3.5 s compile + 6.4 s calibration at the
100K bucket shape on the CPU, and in a restart storm that cost recurs per
attempt). Upstream photon-ml never had this failure mode —
Spark re-JITs Scala closures for free — so the rebuild's recovery-time
story is only honest once compilation stops being the dominant term in
MTTR (ROADMAP item 4).

The store has two layers:

* **Artifact bytes** — JAX's persistent compilation cache
  (``jax_compilation_cache_dir``): every XLA executable serializes to disk
  keyed by its HLO digest, so a re-compile after a cache clear or a process
  restart is a disk LOAD, not an XLA compile. The store forces the cache on
  (where :func:`compilation_cache_dir` puts it) with a
  zero min-compile-time floor — recovery cares about every kernel in the
  closed set, not just the slow ones.
* **The manifest** (``<root>/manifest.json`` + one pickled abstract
  signature per entry) — the piece the raw cache lacks: an enumerable
  record of every (kernel, abstract shapes, dtype, static config, backend,
  code fingerprint) a run compiled, so a PRE-WARM pass can replay
  ``jit(...).lower(*abstract_args).compile()`` for the whole closed kernel
  set *before* an attempt goes live. ``lower().compile()`` shares the jit
  dispatch cache (verified: the subsequent real call neither re-traces nor
  re-compiles), so a pre-warmed attempt starts solving in milliseconds.

The closed kernel set (the only record sites): the blessed chunk-ladder RE
solvers (``fit_bucket_newton``, ``fit_bucket_newton_dual``,
``fit_bucket_vmapped``), ``glm_fit``, and ``additive_score_rows``.
Recording is best-effort by contract — a signature that will not pickle is
skipped with a debug log, never an error in the training path.

Wired through the recovery stack (docs/robustness.md §"Recovery time"):

* :class:`~photon_tpu.supervisor.RunSupervisor` pre-warms the next attempt
  between restarts and journals a ``prewarm`` row (mirrored once as a
  ``recovery.prewarm`` trace instant, emitted here) with compile-vs-load
  seconds;
* :func:`~photon_tpu.runtime.backend_guard.recover_from_device_loss`
  repopulates from the store right after ``clear_executable_caches`` so the
  in-run recovery re-step loads instead of recompiling cold;
* checkpoints stamp :func:`manifest_ref_if_active` into their metadata so a
  checkpoint-resume restart knows exactly which artifacts to pre-warm
  (:func:`prewarm_from_checkpoint`);
* ``game/descent.py`` stamps :func:`note_first_step` after its first
  committed step, closing the ``restart_to_first_step_seconds`` clock the
  supervisor arms per attempt.

Compile-vs-load accounting rides ``jax.monitoring``: each compile request
either MISSES the persistent cache (the ``backend_compile_duration`` is XLA
time) or HITS it (the duration is artifact-load I/O). The split feeds the
``xla_compile_seconds_total`` / ``xla_cache_load_seconds_total`` counters
and the CI assertion that a warm restart's XLA share sits below its I/O
share.
"""
from __future__ import annotations

import hashlib
import json
import logging
import os
import pickle
import threading
import time
from typing import Optional, Sequence

__all__ = [
    "CompileStore",
    "active",
    "arm_first_step_clock",
    "compilation_cache_dir",
    "compile_split",
    "configure",
    "deactivate",
    "enable_compilation_cache",
    "install_accounting",
    "manifest_ref_if_active",
    "note_compilation",
    "note_first_step",
    "prewarm_from_checkpoint",
    "prewarm_if_active",
    "process_has_compiled",
    "record_if_active",
]

logger = logging.getLogger("photon_tpu.runtime")

MANIFEST_NAME = "manifest.json"
MANIFEST_VERSION = 1

# ------------------------------------------------------ compile/load split
#
# jax.monitoring event stream, observed per compile request:
#   miss: .../compile_requests_use_cache, .../cache_misses,
#         backend_compile_duration          -> XLA compile time
#   hit:  .../compile_requests_use_cache, .../cache_hits,
#         cache_retrieval_time_sec, backend_compile_duration
#                                            -> artifact-load I/O time
# The marker event and the duration arrive on the same thread in order, so
# a thread-local "last marker" attributes each duration correctly.

_acc_lock = threading.Lock()
_acc_installed = False
_acc_available: Optional[bool] = None  # None until first install attempt
_acc_tls = threading.local()


def accounting_available() -> bool:
    """Did the compile-vs-load listeners actually install? Pre-warm uses
    this to classify honestly: with no accounting, an entry that silently
    paid a cold compile must never be reported as a load."""
    install_accounting()
    return bool(_acc_available)


def install_accounting() -> bool:
    """Install the process-wide XLA compile-vs-load listeners (idempotent).

    Returns False when ``jax.monitoring`` is unavailable — the counters
    then stay at zero and :class:`compile_split` reports empty deltas, but
    nothing in the store's record/prewarm contract breaks."""
    global _acc_installed, _acc_available
    with _acc_lock:
        if _acc_installed:
            return bool(_acc_available)
        try:
            from jax._src import monitoring
        except Exception as e:  # noqa: BLE001 - version-dependent API
            logger.debug("compile accounting unavailable: %s", e)
            _acc_installed = True
            _acc_available = False
            return False
        from photon_tpu.obs.metrics import REGISTRY

        hits = REGISTRY.counter(
            "xla_cache_hits_total",
            "compile requests served from the persistent compilation cache "
            "(artifact load, not an XLA compile)",
        )
        misses = REGISTRY.counter(
            "xla_cache_misses_total",
            "compile requests that paid a real XLA backend compile",
        )
        xla_s = REGISTRY.counter(
            "xla_compile_seconds_total",
            "wall seconds inside XLA backend compiles (cache misses)",
        )
        io_s = REGISTRY.counter(
            "xla_cache_load_seconds_total",
            "wall seconds loading compiled executables from the persistent "
            "cache (cache hits)",
        )

        def on_event(name: str, **kw) -> None:
            if name.endswith("/cache_hits"):
                _acc_tls.last = "hit"
                hits.inc()
            elif name.endswith("/cache_misses"):
                _acc_tls.last = "miss"
                misses.inc()

        def on_duration(name: str, secs: float, **kw) -> None:
            if name.endswith("backend_compile_duration"):
                # No marker (cache disabled / unknown) counts as a miss:
                # without a persistent cache every compile IS XLA time.
                if getattr(_acc_tls, "last", "miss") == "hit":
                    io_s.inc(max(float(secs), 0.0))
                else:
                    xla_s.inc(max(float(secs), 0.0))
                _acc_tls.last = "miss"  # marker consumed

        monitoring.register_event_listener(on_event)
        monitoring.register_event_duration_secs_listener(on_duration)
        _acc_installed = True
        _acc_available = True
        return True


class compile_split:
    """``with compile_split() as cs: ...`` — per-block deltas of the XLA
    compile-vs-load accounting: ``cs.hits``/``cs.misses`` (compile requests
    by outcome) and ``cs.xla_seconds``/``cs.io_seconds``."""

    def __init__(self) -> None:
        self.hits = 0
        self.misses = 0
        self.xla_seconds = 0.0
        self.io_seconds = 0.0
        self.available = False  # did the jax.monitoring listeners install?

    def _values(self) -> tuple:
        from photon_tpu.obs.metrics import REGISTRY

        return (
            REGISTRY.counter("xla_cache_hits_total").value(),
            REGISTRY.counter("xla_cache_misses_total").value(),
            REGISTRY.counter("xla_compile_seconds_total").value(),
            REGISTRY.counter("xla_cache_load_seconds_total").value(),
        )

    def __enter__(self) -> "compile_split":
        self.available = install_accounting()
        self._before = self._values()
        return self

    def __exit__(self, *exc) -> None:
        h, m, x, i = self._values()
        b = self._before
        self.hits = int(h - b[0])
        self.misses = int(m - b[1])
        self.xla_seconds = max(0.0, x - b[2])
        self.io_seconds = max(0.0, i - b[3])


# ------------------------------------------------------- signature helpers


# Any process-wide compilation (registered kernels bump this via
# obs.retrace.note_trace) — the "already compiled" detector behind the
# enable_compilation_cache late-call guard.
_compiled_flag = threading.Event()


def note_compilation() -> None:
    _compiled_flag.set()


def process_has_compiled() -> bool:
    """Best-effort "this process already compiled something": any watched
    kernel traced (retrace sentinel), or the flag was set directly."""
    if _compiled_flag.is_set():
        return True
    try:
        from photon_tpu.obs import retrace

        return any(v > 0 for v in retrace.all_traces().values())
    except Exception:  # noqa: BLE001 - detector, never a failure mode
        return False


def _abstractify(x):
    """Array-likes → ShapeDtypeStruct; everything else (statics: problem
    configs, ints, part tuples) passes through to the pickle."""
    import jax
    import numpy as np

    if isinstance(x, (jax.Array, np.ndarray)):
        return jax.ShapeDtypeStruct(tuple(x.shape), np.dtype(x.dtype))
    return x


_fp_cache: dict = {}


def _code_fingerprint(fn) -> str:
    """Digest of the kernel's defining module source — a changed kernel
    invalidates its entries (the executable they name no longer matches the
    code that would be traced)."""
    import sys

    mod = getattr(fn, "__module__", None) or ""
    cached = _fp_cache.get(mod)
    if cached is not None:
        return cached
    digest = "unknown"
    try:
        path = getattr(sys.modules.get(mod), "__file__", None)
        if path:
            with open(path, "rb") as f:
                digest = hashlib.sha256(f.read()).hexdigest()[:16]
    except OSError:
        pass
    _fp_cache[mod] = digest
    return digest


def _import_fn(ref: str):
    """``"module:qualname"`` → the (jitted) callable."""
    import importlib

    mod_name, _, qual = ref.partition(":")
    obj = importlib.import_module(mod_name)
    for part in qual.split("."):
        obj = getattr(obj, part)
    return obj


def _default_backend() -> str:
    try:
        import jax

        return jax.default_backend()
    except Exception:  # noqa: BLE001 - no backend => no store entry
        return "unknown"


# --------------------------------------------------------------- the store


class CompileStore:
    """Manifest-backed AOT compile-artifact store (module doc).

    One directory per store: ``manifest.json`` (entry metadata keyed by
    signature digest) plus one ``<key>.sig`` pickle per entry holding the
    exact ``(args, kwargs)`` tuple — statics verbatim, traced arrays as
    ``ShapeDtypeStruct`` — that :meth:`prewarm` replays through
    ``fn.lower(...).compile()``. Thread-safe; manifest writes are atomic
    (tmp + ``os.replace``) so a reader never sees a torn manifest.
    """

    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        os.makedirs(self.root, exist_ok=True)
        self._lock = threading.Lock()
        self._entries: dict = {}
        self._load_manifest()
        install_accounting()

    # ------------------------------------------------------------ manifest

    @property
    def manifest_path(self) -> str:
        return os.path.join(self.root, MANIFEST_NAME)

    def _load_manifest(self) -> None:
        try:
            with open(self.manifest_path) as f:
                data = json.load(f)
            self._entries = dict(data.get("entries", {}))
        except FileNotFoundError:
            self._entries = {}
        except (OSError, ValueError) as e:
            # A corrupt manifest must degrade to "empty store" (recompiles),
            # never take a recovery path down with it.
            logger.warning("compile store manifest unreadable (%s); "
                           "starting empty: %s", self.manifest_path, e)
            self._entries = {}

    def _write_manifest(self) -> None:
        # Caller holds self._lock.
        tmp = f"{self.manifest_path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump({"version": MANIFEST_VERSION, "entries": self._entries},
                      f, indent=1, sort_keys=True)
        os.replace(tmp, self.manifest_path)

    def entries(self) -> dict:
        with self._lock:
            return dict(self._entries)

    def manifest_digest(self) -> str:
        with self._lock:
            keys = sorted(self._entries)
        return hashlib.sha256("|".join(keys).encode()).hexdigest()[:16]

    def manifest_ref(self) -> dict:
        """Checkpoint-embeddable reference: enough for a resumed restart to
        find and pre-warm exactly this artifact set."""
        return {
            "root": self.root,
            "digest": self.manifest_digest(),
            "entries": len(self._entries),
        }

    # -------------------------------------------------------------- record

    def record(self, kernel: str, fn, args: Sequence = (),
               kwargs: Optional[dict] = None) -> bool:
        """Record one compiled signature of ``kernel`` (best-effort).

        ``args``/``kwargs`` are the EXACT call arguments of the jitted
        ``fn`` — arrays are abstracted to shape/dtype structs, statics are
        pickled verbatim so the pre-warm replay traces the identical HLO.
        Returns True when a NEW entry landed; False for duplicates or any
        recording failure (never raises into a training path)."""
        note_compilation()
        try:
            import jax

            sig = jax.tree.map(_abstractify, (tuple(args), dict(kwargs or {})))
            blob = pickle.dumps(sig, protocol=pickle.HIGHEST_PROTOCOL)
            fn_ref = f"{fn.__module__}:{fn.__qualname__}"
            backend = _default_backend()
            fp = _code_fingerprint(fn)
            key = hashlib.sha256(
                f"{kernel}|{fn_ref}|{fp}|{backend}|{jax.__version__}|".encode()
                + blob
            ).hexdigest()[:24]
        except Exception as e:  # noqa: BLE001 - recording is best-effort
            logger.debug("compile store: signature for %s not recordable "
                         "(%s: %s)", kernel, type(e).__name__, e)
            return False
        with self._lock:
            if key in self._entries:
                return False
        try:
            sig_path = os.path.join(self.root, f"{key}.sig")
            tmp = f"{sig_path}.tmp.{os.getpid()}"
            with open(tmp, "wb") as f:
                f.write(blob)
            os.replace(tmp, sig_path)
            with self._lock:
                self._entries[key] = {
                    "kernel": kernel,
                    "fn": fn_ref,
                    "backend": backend,
                    "jax_version": jax.__version__,
                    "code_fingerprint": fp,
                    "created_at": time.strftime(
                        "%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
                }
                self._write_manifest()
        except OSError as e:
            logger.debug("compile store: entry write failed (%s)", e)
            return False
        from photon_tpu.obs.metrics import REGISTRY

        REGISTRY.counter(
            "compile_store_entries_total",
            "AOT compile-store manifest entries recorded, by kernel",
        ).inc(kernel=kernel)
        return True

    # ------------------------------------------------------------- prewarm

    def prewarm(self, kernels: Optional[Sequence[str]] = None,
                logger_=None, reason: str = "") -> dict:
        """Replay every matching manifest entry through
        ``fn.lower(*abstract_args).compile()`` so the executables are live
        BEFORE the run/swap goes hot.

        With the persistent cache populated each replay is an artifact
        LOAD; a cold store (fresh machine, new code fingerprint upstream)
        compiles — and thereby populates the cache for the next restart.
        Entries for another backend/jax version/code fingerprint are
        skipped, as is anything that fails to import, unpickle, or lower —
        pre-warm can degrade to "nothing warmed", never to a new failure.

        Returns ``{entries, loaded, compiled, skipped, load_seconds,
        xla_seconds, seconds}`` and emits ONE ``recovery.prewarm`` trace
        instant (callers journaling a row pass ``_mirror=False``)."""
        from photon_tpu.obs import instant, retrace
        from photon_tpu.obs.metrics import REGISTRY

        log = logger_ or logger
        t0 = time.perf_counter()
        backend = _default_backend()
        try:
            import jax

            jax_version = jax.__version__
        except Exception:  # noqa: BLE001
            jax_version = "unknown"
        loaded = compiled = 0
        skipped: list = []
        load_s = xla_s = 0.0
        for key, meta in sorted(self.entries().items()):
            if kernels is not None and meta.get("kernel") not in kernels:
                continue
            if (meta.get("backend") != backend
                    or meta.get("jax_version") != jax_version):
                skipped.append((key, "backend/jax mismatch"))
                continue
            try:
                fn = _import_fn(meta["fn"])
                if meta.get("code_fingerprint") != _code_fingerprint(fn):
                    skipped.append((key, "stale code fingerprint"))
                    continue
                with open(os.path.join(self.root, f"{key}.sig"), "rb") as f:
                    args, kw = pickle.load(f)
            except Exception as e:  # noqa: BLE001 - entry-level isolation
                skipped.append((key, f"{type(e).__name__}: {e}"))
                continue
            try:
                # Expected compiles: a prewarm trace must never fire the
                # retrace-after-warmup alarm — it IS the warmup.
                with compile_split() as cs, retrace.expected_compiles():
                    fn.lower(*args, **kw).compile()
            except Exception as e:  # noqa: BLE001 - entry-level isolation
                skipped.append((key, f"{type(e).__name__}: {e}"))
                continue
            # Honest classification: without the monitoring listeners we
            # cannot distinguish a cache load from a cold compile, and a
            # silently-cold entry reported as "loaded" would turn the CI
            # warm-restart assertion false-green — count it as compiled.
            if cs.misses > 0 or not cs.available:
                compiled += 1
            else:
                loaded += 1
            load_s += cs.io_seconds
            xla_s += cs.xla_seconds
        took = time.perf_counter() - t0
        summary = {
            "entries": len(self._entries),
            "loaded": loaded,
            "compiled": compiled,
            "skipped": len(skipped),
            "load_seconds": round(load_s, 4),
            "xla_seconds": round(xla_s, 4),
            "seconds": round(took, 4),
            "accounting": accounting_available(),
        }
        REGISTRY.counter(
            "compile_store_prewarm_loads_total",
            "prewarmed executables that LOADED from the persistent cache",
        ).inc(loaded)
        REGISTRY.counter(
            "compile_store_prewarm_compiles_total",
            "prewarmed executables that paid a cold XLA compile",
        ).inc(compiled)
        instant("recovery.prewarm", cat="recovery", reason=reason, **summary)
        if log is not None:
            log.info(
                "compile store prewarm%s: %d loaded, %d compiled, %d skipped "
                "(load %.3fs, xla %.3fs)",
                f" ({reason})" if reason else "", loaded, compiled,
                len(skipped), load_s, xla_s)
            for key, why in skipped[:5]:
                log.debug("compile store prewarm skipped %s: %s", key, why)
        return summary


# ------------------------------------------------- process default store

_active_lock = threading.Lock()
_ACTIVE: Optional[CompileStore] = None
_DISABLED = False  # explicit opt-out pins OFF even with the env var set


def configure(root: str, enable_xla_cache: bool = True) -> CompileStore:
    """Make ``root`` this process's active compile store. With
    ``enable_xla_cache`` the persistent compilation cache is switched on
    where :func:`compilation_cache_dir` puts it (unless a directory
    is already in force), with a zero min-compile-time floor (recovery
    needs EVERY kernel in the closed set persisted, not just the slow
    ones)."""
    global _ACTIVE, _DISABLED
    store = CompileStore(root)
    if enable_xla_cache:
        _ensure_persistent_cache()
    with _active_lock:
        _ACTIVE = store
        _DISABLED = False  # an explicit configure overrides a prior opt-out
    return store


def compilation_cache_dir(flag=None):
    """Where the persistent XLA cache goes — the ONE resolution every
    driver, the compile store and chip_smoke.py share. Returns the
    directory the program must set in code, or None when it must set none:

    * ``$JAX_COMPILATION_CACHE_DIR`` set: JAX reads it by itself, so the
      answer is None. A ``--compilation-cache-dir`` naming another place is
      refused rather than silently winning (or silently losing).
    * unset: the flag's directory, else ``<checkout>/.jax_cache`` — a fixed
      path beside the package, never under an output directory or a
      temporary name: the path is part of how a later process finds what
      an earlier one compiled, so a directory that moves never hits.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        if flag and os.path.realpath(flag) != os.path.realpath(env):
            raise ValueError(
                f"--compilation-cache-dir {flag!r} conflicts with "
                f"JAX_COMPILATION_CACHE_DIR={env!r}; drop one of them")
        return None
    if flag:
        return flag
    import photon_tpu

    checkout = os.path.dirname(
        os.path.dirname(os.path.abspath(photon_tpu.__file__)))
    return os.path.join(checkout, ".jax_cache")


def enable_compilation_cache(flag=None, min_compile_secs=None):
    """Turn on jax's persistent compilation cache at
    :func:`compilation_cache_dir` and return the directory in force. Must
    run before the first jit compilation — jax only consults the cache dir
    at compile time, so everything compiled BEFORE this call is silently
    uncached and will recompile on the next restart. A late call is
    detected (any watched kernel already traced in this process) and
    warned LOUDLY, because a driver that reorders its init quietly loses
    exactly the warm-restart behavior the recovery stack depends on
    (docs/robustness.md §"Recovery time").

    ``min_compile_secs`` is the persistence floor (default
    ``PHOTON_XLA_CACHE_MIN_SECS``, else jax's own 1.0 s)."""
    import jax

    if min_compile_secs is None:
        min_compile_secs = float(
            os.environ.get("PHOTON_XLA_CACHE_MIN_SECS", "1.0"))
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", min_compile_secs)
    path = compilation_cache_dir(flag)
    if path is None or path == jax.config.jax_compilation_cache_dir:
        return jax.config.jax_compilation_cache_dir
    if process_has_compiled():
        logger.warning(
            "enable_compilation_cache(%r) called AFTER this process already "
            "compiled kernels: programs compiled before this point were NOT "
            "persisted and will recompile from scratch on the next restart "
            "(the cache handle is re-initialized now, so later compiles DO "
            "persist). Call it (or enable_compile_store) before the first "
            "jit dispatch — typically first thing in the driver, before "
            "data loading touches any jitted code.", path,
        )
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    # jax memoizes the cache handle at the process's first compile (watched
    # or not — even a stray jnp.zeros counts), so a dir set afterwards
    # would persist nothing, ever. Resetting the handle makes the call
    # effective from here on (the warning above still marks pre-call
    # compiles as lost).
    _reset_jax_cache_handle()
    return path


def _ensure_persistent_cache() -> None:
    try:
        import jax

        # The store's floor wins either way: with a compile store active,
        # recovery needs EVERY kernel in the closed set persisted — the
        # cache-only default of 1.0s (enable_compilation_cache) would drop
        # exactly the sub-second kernels a warm restart then recompiles
        # cold while the prewarm journal claims the store is working.
        min_secs = float(os.environ.get("PHOTON_XLA_CACHE_MIN_SECS", "0.0"))
        if jax.config.jax_compilation_cache_dir:
            # A directory is already in force (the variable, or the driver
            # enabled it first); layer on it, floor lowered.
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", min_secs)
            return
        enable_compilation_cache(None, min_compile_secs=min_secs)
    except Exception as e:  # noqa: BLE001 - cache layer is best-effort
        logger.warning("compile store: persistent cache unavailable (%s); "
                       "prewarm will compile instead of load", e)


def _reset_jax_cache_handle() -> None:
    """Drop jax's memoized persistent-cache handle so a cache dir set
    AFTER this process's first compile still takes effect. jax initializes
    the cache lazily at the first compile and memoizes the result — with
    no dir configured at that moment, every later ``jax_compilation_cache_
    dir`` update is silently ignored (the late-call no-op the
    enable_compilation_cache guard warns about). Private API, so failure
    degrades to the old behavior (warn-only)."""
    try:
        from jax._src import compilation_cache

        compilation_cache.reset_cache()
    except Exception as e:  # noqa: BLE001 - version-dependent private API
        logger.debug("jax compilation-cache reset unavailable: %s", e)


def active() -> Optional[CompileStore]:
    """The process's active store: configured explicitly, or lazily from
    ``$PHOTON_COMPILE_STORE``; None when neither names one or when
    :func:`disable` pinned the explicit opt-out."""
    global _ACTIVE
    with _active_lock:
        if _ACTIVE is not None:
            return _ACTIVE
        if _DISABLED:
            return None
    root = os.environ.get("PHOTON_COMPILE_STORE")
    if root:
        return configure(root)
    return None


def disable() -> None:
    """Pin the store OFF process-wide (``--compile-store off``): without
    this, a fleet-wide ``$PHOTON_COMPILE_STORE`` export would lazily
    re-activate the store — and repoint the persistent cache — on the
    first kernel compile, overriding the operator's explicit opt-out."""
    global _ACTIVE, _DISABLED
    with _active_lock:
        _ACTIVE = None
        _DISABLED = True


def deactivate() -> None:
    """Forget the active store AND any opt-out pin (tests)."""
    global _ACTIVE, _DISABLED
    with _active_lock:
        _ACTIVE = None
        _DISABLED = False


def dispatch_recorded(kernel: str, fn, args: Sequence = (),
                      kwargs: Optional[dict] = None):
    """Dispatch ``fn(*args, **kwargs)`` under a retrace compile watch and,
    when THIS dispatch compiled, record the signature into the active
    store — the shared record-site shim (``problem.fit``, the serving
    scorer, ``transform_rows``). Costs two counter reads per call when
    nothing compiles."""
    from photon_tpu.obs.retrace import compile_watch

    with compile_watch(kernels=(kernel,)) as cw:
        out = fn(*args, **(kwargs or {}))
    if cw.compiled:
        record_if_active(kernel, fn, args, kwargs)
    return out


def record_if_active(kernel: str, fn, args: Sequence = (),
                     kwargs: Optional[dict] = None) -> bool:
    """``CompileStore.record`` against the active store; no-op without one.
    Also feeds the already-compiled detector either way."""
    note_compilation()
    store = active()
    if store is None:
        return False
    return store.record(kernel, fn, args, kwargs)


def prewarm_if_active(reason: str = "", kernels=None,
                      logger_=None) -> Optional[dict]:
    """``CompileStore.prewarm`` against the active store; None without one.
    Never raises — recovery paths call this between clearing the executable
    caches and re-entering the solve."""
    store = active()
    if store is None:
        return None
    try:
        return store.prewarm(kernels=kernels, logger_=logger_, reason=reason)
    except Exception as e:  # noqa: BLE001 - prewarm must not break recovery
        (logger_ or logger).warning(
            "compile store prewarm failed (%s: %s); recovery proceeds cold",
            type(e).__name__, e)
        return None


def manifest_ref_if_active() -> Optional[dict]:
    store = active()
    return None if store is None else store.manifest_ref()


def prewarm_from_checkpoint(payload: Optional[dict],
                            logger_=None) -> Optional[dict]:
    """Pre-warm from the compile-store reference a checkpoint carries
    (``meta["compile_store"]``, stamped by ``CheckpointManager.save``), so
    a checkpoint-resume restart starts solving in milliseconds. Falls back
    to the active store when the referenced root is gone; returns None when
    neither exists."""
    ref = ((payload or {}).get("meta") or {}).get("compile_store") or {}
    root = ref.get("root")
    store = active()
    if root and os.path.isdir(root) and (store is None
                                         or store.root != os.path.abspath(root)):
        # The checkpoint's store is authoritative for ITS kernel set; warm
        # it without stealing the process's active-store slot.
        store = CompileStore(root)
    if store is None:
        return None
    try:
        return store.prewarm(logger_=logger_, reason="checkpoint-resume")
    except Exception as e:  # noqa: BLE001 - resume must not fail on this
        (logger_ or logger).warning(
            "checkpoint prewarm failed (%s: %s); resume proceeds cold",
            type(e).__name__, e)
        return None


# --------------------------------------------- restart-to-first-step clock

_clock_lock = threading.Lock()
_first_step: Optional[dict] = None


def arm_first_step_clock(attempt: int = 0, journal=None) -> None:
    """Start the restart→first-step clock (the supervisor arms one per
    attempt). The next :func:`note_first_step` stamps the elapsed seconds
    into the ``restart_to_first_step_seconds`` gauge, a
    ``recovery.first_step`` trace instant, and — when ``journal`` is a
    :class:`~photon_tpu.supervisor.RecoveryJournal` — a ``first_step``
    journal row."""
    global _first_step
    with _clock_lock:
        _first_step = {
            "t0": time.monotonic(),
            "attempt": int(attempt),
            "journal": journal,
        }


def first_step_clock_armed() -> bool:
    with _clock_lock:
        return _first_step is not None


def disarm_first_step_clock() -> None:
    """Drop an armed clock without stamping (the supervised run ended —
    success or final failure — before any step committed; a later
    unrelated step must not stamp a stale span)."""
    global _first_step
    with _clock_lock:
        _first_step = None


def note_first_step(phase: str) -> Optional[float]:
    """Close the armed clock (no-op when disarmed — callers stamp
    unconditionally after every committed step; only the first one after
    arming lands). Returns the measured seconds when it fired."""
    global _first_step
    with _clock_lock:
        st = _first_step
        _first_step = None
    if st is None:
        return None
    seconds = time.monotonic() - st["t0"]
    from photon_tpu.obs import instant
    from photon_tpu.obs.metrics import REGISTRY

    REGISTRY.gauge(
        "restart_to_first_step_seconds",
        "seconds from (re)start of the latest supervised attempt to its "
        "first committed training step (docs/robustness.md §recovery time)",
    ).set(round(seconds, 4))
    instant("recovery.first_step", cat="recovery", phase=phase,
            attempt=st["attempt"], seconds=round(seconds, 4))
    journal = st["journal"]
    if journal is not None:
        try:
            journal.record(
                "first_step", _mirror=False, attempt=st["attempt"],
                phase=phase,
                restart_to_first_step_seconds=round(seconds, 4))
        except Exception:  # noqa: BLE001 - journal is evidence, not a dep
            pass
    return seconds
