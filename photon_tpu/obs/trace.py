"""Process-wide tracing: Chrome trace-event spans with propagated context.

Dapper-style (Sigelman et al., 2010) always-on, low-overhead tracing for the
whole stack — ingest, coordinate descent, optimizer solves, the serving
path — emitting the Chrome trace-event JSON format, so one run's timeline
opens directly in Perfetto (https://ui.perfetto.dev) or ``chrome://tracing``.

Design constraints (docs/observability.md):

* **Near-zero cost when off.** Like ``faults.fault_point``, the hot-path
  check is one module-global read: :class:`trace_span` is a plain slotted
  class (no generator machinery) whose ``__exit__`` does nothing but two
  ``perf_counter`` reads when no collector is installed. Spans still
  measure wall-clock (``span.seconds``) so callers can keep using the
  measurement for records/logs whether or not tracing is on.
* **Propagated context.** Spans on a context-carrying thread inherit a
  ``trace_id``. A request's id is minted once at the edge
  (:func:`new_trace_id`) and attached via :func:`trace_context`; the
  serving micro-batcher stores the submitting request's id on the queue
  item, and the worker stamps it onto that row's queue-wait span and into
  the coalesced batch span's ``trace_ids`` list (a batch mixes several
  requests, so batch-level work — kernel, store resolve — correlates
  through that list rather than a single id).
* **One artifact.** Events buffer in memory (bounded) and
  :func:`stop_tracing` writes a single ``{"traceEvents": [...]}`` JSON
  object; ``scripts/obs_smoke.py`` validates the format in CI.
* **Causality.** Every span records the span that was open on its thread
  when it was entered (``parent_id``; ``span_id`` is taken at enter), so a
  layer's self time — its span less what its children cover — can be
  computed from a trace. A root span may ask for its finished tree to be
  kept (:meth:`trace_span.keep_tree`): a bounded ring holds the last
  trees as plain tuples, read back with :func:`recent_trees`, with no
  collector installed. ``estimator.fit`` is the one root that asks.
* **One clock with the device.** While a ``jax.profiler`` session is
  recording, an open span also holds a ``jax.profiler.TraceAnnotation`` of
  its name, so the program's spans lie in the profile's ``/host:CPU`` plane
  beside the device planes. Nothing to switch on; a process that has not
  imported ``jax`` is never made to (the serving front line's workers).
* **Where the host waits on the device.** Dispatch is asynchronous, so a
  span that ends at dispatch says what the host did. Every read that
  blocks a fit's thread until the device has a value is the leaf span
  ``device.wait`` (:func:`device_wait`, argument ``site``): seconds inside
  are the host blocked on the chip, a layer's seconds outside them the
  host's own (docs/observability.md §"A fit's span tree").
* **Mergeable across processes.** Every collector stamps a
  :data:`ANCHOR_EVENT` metadata instant at install — the wall-clock ↔
  ``perf_counter`` correspondence plus pid/hostname/role — so
  ``obs.fleet.merge_traces`` can align N per-process shards onto one
  wall-clock timeline (docs/observability.md §"Fleet view").

Span catalog (``cat`` → ``name``) is documented in docs/observability.md.
"""
from __future__ import annotations

import itertools
import json
import os
import sys
import threading
import time
import zlib
from collections import deque
from typing import Optional

__all__ = [
    "ANCHOR_EVENT",
    "ANCHOR_SCHEMA",
    "TailSampler",
    "TraceCollector",
    "install_tail_sampler",
    "uninstall_tail_sampler",
    "tail_sampler",
    "trace_span",
    "device_wait",
    "recent_trees",
    "instant",
    "process_role",
    "set_process_role",
    "start_tracing",
    "stop_tracing",
    "suspend_tracing",
    "tracing_active",
    "tracing",
    "new_trace_id",
    "current_trace_id",
    "trace_context",
]

# Common clock for all collectors in this process: microsecond timestamps
# relative to module import, so events from collectors started at different
# times still order correctly within one process.
_EPOCH = time.perf_counter()

_span_ids = itertools.count(1)
_trace_ids = itertools.count(1)
_tls = threading.local()

# Default cap on buffered events: a leaked always-on collector must not grow
# host memory without bound. Dropped events are counted and reported in the
# written artifact ("photon.trace.dropped" metadata event).
_DEFAULT_MAX_EVENTS = 1_000_000

# Default cap on the (approximate) serialized artifact size: a multi-day
# serve run with tracing on must bound disk, like PHOTON_METRICS_MAX_BYTES
# bounds the metrics JSONL. Crossing it drops further events LOUDLY — one
# "photon.trace.truncated" instant plus a log warning — never silently.
_DEFAULT_MAX_BYTES = 256 << 20

#: Per-process anchor metadata event: the wall-clock ↔ perf_counter
#: correspondence every trace shard carries so the fleet merger can align
#: clocks across processes/hosts. Stamped once at collector install.
ANCHOR_EVENT = "photon.anchor"
ANCHOR_SCHEMA = "photon-anchor/1"

# Process role stamped into the anchor (and the Perfetto process_name
# lane): "training" / "serving" / "online" / ... — set by the drivers via
# set_process_role BEFORE the collector installs.
_ROLE = os.environ.get("PHOTON_PROCESS_ROLE") or "unknown"


def set_process_role(role: str) -> None:
    """Declare this process's fleet role ("training", "serving", "online",
    ...). Call before :func:`start_tracing` — the role is stamped into the
    collector's anchor event at install and cannot retroactively rename an
    already-written shard."""
    global _ROLE
    _ROLE = str(role)


def process_role() -> str:
    return _ROLE


def _env_max_bytes() -> int:
    try:
        return int(os.environ.get("PHOTON_TRACE_MAX_BYTES",
                                  _DEFAULT_MAX_BYTES))
    except (TypeError, ValueError):
        return _DEFAULT_MAX_BYTES


def _env_sample() -> float:
    """PHOTON_TRACE_SAMPLE in (0, 1]: opt-in span sampling for long serve
    runs (1.0 = keep everything). Malformed values degrade to 1.0 — a
    typo'd knob must never kill tracing."""
    raw = os.environ.get("PHOTON_TRACE_SAMPLE")
    if not raw:
        return 1.0
    try:
        rate = float(raw)
    except (TypeError, ValueError):
        return 1.0
    if not 0.0 < rate <= 1.0:
        return 1.0
    return rate


def _approx_event_bytes(event: dict) -> int:
    """Cheap serialized-size estimate (no json.dumps on the hot path):
    fixed framing + name/cat + per-arg key and string-value lengths
    (numbers priced at a flat 12 bytes)."""
    n = 90 + len(event.get("name", "")) + len(event.get("cat", ""))
    args = event.get("args")
    if args:
        for k, v in args.items():
            n += len(k) + (len(v) if isinstance(v, str) else 12) + 6
    return n


def new_trace_id() -> str:
    """Mint a fresh trace id (process-unique, human-scannable)."""
    return f"t{os.getpid():x}.{next(_trace_ids):x}"


def current_trace_id() -> Optional[str]:
    """The trace id attached to this thread, if any."""
    return getattr(_tls, "trace_id", None)


class trace_context:
    """``with trace_context(trace_id):`` — attach a trace id to this thread.

    Used at work-handoff boundaries: the producing thread records
    ``current_trace_id()`` next to the work item, the consuming thread
    re-enters it here so spans emitted while processing the item correlate
    with the originating request. Re-entrant; restores the previous id."""

    __slots__ = ("trace_id", "_prev")

    def __init__(self, trace_id: Optional[str]):
        self.trace_id = trace_id

    def __enter__(self) -> "trace_context":
        self._prev = getattr(_tls, "trace_id", None)
        _tls.trace_id = self.trace_id
        return self

    def __exit__(self, *exc) -> None:
        _tls.trace_id = self._prev


class TraceCollector:
    """Thread-safe in-memory buffer of Chrome trace events.

    Bounds (all loud, never silent): ``max_events`` caps the buffer,
    ``max_bytes`` (env ``PHOTON_TRACE_MAX_BYTES``, default 256 MB, 0
    disables) caps the approximate serialized size — the first event over
    the cap lands one ``photon.trace.truncated`` instant plus a log
    warning, then further events drop. ``sample`` (env
    ``PHOTON_TRACE_SAMPLE``, default 1.0) keeps that fraction of SPANS —
    whole trace-id chains kept or dropped together so cross-thread /
    cross-process joins survive sampling; instants (faults, SLO verdicts,
    anchors) are never sampled out.

    The anchor metadata (``ANCHOR_EVENT`` + a Perfetto ``process_name``
    lane label) lives in :attr:`meta`, merged into the artifact at
    :meth:`to_dict` — so ``events`` stays exactly the span/instant stream.
    """

    def __init__(self, max_events: int = _DEFAULT_MAX_EVENTS,
                 max_bytes: Optional[int] = None,
                 sample: Optional[float] = None):
        self.max_events = int(max_events)
        self.max_bytes = _env_max_bytes() if max_bytes is None else int(
            max_bytes)
        self.sample = _env_sample() if sample is None else float(sample)
        self.events: list[dict] = []
        self.meta: list[dict] = []
        self.dropped = 0
        self.sampled_out = 0
        self.truncated = False
        self._approx_bytes = 0
        self._span_seen = 0
        self._lock = threading.Lock()
        self._pid = os.getpid()
        self._stamp_anchor()

    def _stamp_anchor(self) -> None:
        """The fleet-merge contract (docs/observability.md §"Fleet view"):
        wall clock and perf_counter read back-to-back at install, so a
        merger can map any event's ``ts`` to wall time via
        ``anchor.wall_time + (ts - anchor.ts) / 1e6``."""
        import socket

        pc = time.perf_counter()
        wall = time.time()
        try:
            host = socket.gethostname()
        except OSError:
            host = "unknown"
        role = process_role()
        tid = threading.get_ident() & 0xFFFFFFFF
        self.meta.append({
            "name": "process_name", "ph": "M", "ts": 0,
            "pid": self._pid, "tid": 0,
            "args": {"name": f"{role}@{host} pid={self._pid}"},
        })
        anchor = {
            "name": ANCHOR_EVENT,
            "cat": "meta",
            "ph": "i",
            "s": "p",
            "ts": round((pc - _EPOCH) * 1e6, 1),
            "pid": self._pid,
            "tid": tid,
            "args": {
                "schema": ANCHOR_SCHEMA,
                "wall_time": wall,
                "perf_counter": pc,
                "pid": self._pid,
                "hostname": host,
                "role": role,
                **({"sample": self.sample} if self.sample < 1.0 else {}),
            },
        }
        self.meta.append(anchor)

    def _note_truncation(self) -> None:
        """One loud event + warning at the size cap, then silence-by-count
        (the drop counter still lands in the artifact)."""
        import logging

        self.events.append({
            "name": "photon.trace.truncated", "cat": "meta", "ph": "i",
            "s": "p",
            "ts": round((time.perf_counter() - _EPOCH) * 1e6, 1),
            "pid": self._pid,
            "tid": threading.get_ident() & 0xFFFFFFFF,
            "args": {"max_bytes": self.max_bytes,
                     "events_kept": len(self.events)},
        })
        logging.getLogger("photon_tpu.obs").warning(
            "trace buffer hit PHOTON_TRACE_MAX_BYTES=%d after %d events — "
            "further events are DROPPED (counted in the artifact). Raise "
            "the cap, or set PHOTON_TRACE_SAMPLE<1 for long serve runs.",
            self.max_bytes, len(self.events),
        )

    def _keep_span(self, args: Optional[dict]) -> bool:
        """Sampling decision for one span: hash the trace id when present
        (whole request chains stay intact across threads AND processes —
        the id, not the process's counter, decides); fall back to a
        deterministic 1-in-N counter for context-free spans."""
        if self.sample >= 1.0:
            return True
        tid = (args or {}).get("trace_id")
        with self._lock:
            if tid is not None:
                keep = (zlib.crc32(str(tid).encode()) & 0xFFFF) / 65536.0 \
                    < self.sample
            else:
                self._span_seen += 1
                keep = int(self._span_seen * self.sample) != int(
                    (self._span_seen - 1) * self.sample)
            if not keep:
                self.sampled_out += 1
        return keep

    def add(self, event: dict) -> None:
        with self._lock:
            if self.truncated or len(self.events) >= self.max_events:
                self.dropped += 1
                return
            if self.max_bytes > 0:
                est = _approx_event_bytes(event)
                if self._approx_bytes + est > self.max_bytes:
                    self.truncated = True
                    self.dropped += 1
                    self._note_truncation()
                    return
                self._approx_bytes += est
            self.events.append(event)

    def complete(
        self,
        name: str,
        cat: str,
        t0: float,
        dur_s: float,
        args: Optional[dict] = None,
    ) -> None:
        """One 'X' (complete) event; ``t0`` is a perf_counter value."""
        tail = _TAIL
        if tail is not None and tail.intercept(name, cat, t0, dur_s, args):
            return  # buffered; promoted into this collector only if the
            # owning request breaches the tail threshold (or errors)
        if self.sample < 1.0 and not self._keep_span(args):
            return
        self.add({
            "name": name,
            "cat": cat,
            "ph": "X",
            "ts": round((t0 - _EPOCH) * 1e6, 1),
            "dur": round(dur_s * 1e6, 1),
            "pid": self._pid,
            "tid": threading.get_ident() & 0xFFFFFFFF,
            "args": args or {},
        })

    def instant(self, name: str, cat: str, args: Optional[dict] = None) -> None:
        """One 'i' (instant) event at now — fault firings, retrace warnings."""
        self.add({
            "name": name,
            "cat": cat,
            "ph": "i",
            "s": "t",
            "ts": round((time.perf_counter() - _EPOCH) * 1e6, 1),
            "pid": self._pid,
            "tid": threading.get_ident() & 0xFFFFFFFF,
            "args": args or {},
        })

    def span_count(self, cat: Optional[str] = None) -> int:
        with self._lock:
            return sum(
                1 for e in self.events
                if e["ph"] == "X" and (cat is None or e["cat"] == cat)
            )

    def to_dict(self) -> dict:
        with self._lock:
            events = self.meta + self.events
            dropped = self.dropped
            sampled_out = self.sampled_out
            truncated = self.truncated
        out = {"traceEvents": events, "displayTimeUnit": "ms"}
        if dropped:
            out["photon.trace.dropped"] = dropped
        if sampled_out:
            out["photon.trace.sampled_out"] = sampled_out
            out["photon.trace.sample"] = self.sample
        if truncated:
            out["photon.trace.truncated_at_bytes"] = self.max_bytes
        return out

    def write(self, path: str) -> str:
        """Write the trace artifact as one JSON object (Perfetto-loadable)."""
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.to_dict(), f)
        return path


class _BufferedSpan:
    """One buffered span event, shareable between requests: batch-level
    spans (kernel, store resolve) carry a ``trace_ids`` list and are
    buffered ONCE with the same object appended to every member request's
    buffer — the ``emitted`` flag makes promotion exactly-once however
    many members breach."""

    __slots__ = ("event", "emitted")

    def __init__(self, event: dict):
        self.event = event
        self.emitted = False


class TailSampler:
    """Tail-based trace sampling (docs/observability.md §"Tail sampling").

    Head sampling (``PHOTON_TRACE_SAMPLE``) decides before the request
    runs, so it keeps mostly boring traces; tail sampling decides AFTER:
    a bounded ring holds every in-flight request's span set cheaply
    (plain dicts, no serialization), and on completion the request is
    either promoted into the active collector — it breached the rolling
    latency threshold, or it errored — or discarded. Production traces
    then capture exactly the interesting tails, still under the
    collector's ``PHOTON_TRACE_MAX_BYTES``/``max_events`` bounds
    (promotion goes through :meth:`TraceCollector.add`).

    The rolling threshold is the ``quantile`` (default p95) of the last
    ``window`` request durations; until ``min_history`` requests have
    completed nothing is promoted on latency (errors always promote).
    Spans reach the sampler through :meth:`TraceCollector.complete` —
    any span whose ``trace_id`` (or ``trace_ids`` member) matches a
    request registered via :meth:`begin` is buffered instead of
    appended; everything else (training spans, instants, anchors) passes
    straight through. Enable via ``PHOTON_TRACE_TAIL=1`` (knobs:
    ``PHOTON_TRACE_TAIL_QUANTILE``, ``PHOTON_TRACE_TAIL_WINDOW``) or
    install one explicitly with :func:`install_tail_sampler`.
    """

    def __init__(self, capacity: int = 512, window: int = 256,
                 quantile: float = 0.95, min_history: int = 30,
                 max_spans_per_request: int = 64):
        if not 0.0 < quantile < 1.0:
            raise ValueError(f"tail quantile must be in (0, 1): {quantile}")
        self.capacity = max(1, int(capacity))
        self.quantile = float(quantile)
        self.min_history = max(1, int(min_history))
        self.max_spans_per_request = max(1, int(max_spans_per_request))
        self._lock = threading.Lock()
        self._inflight: dict[str, list[_BufferedSpan]] = {}
        self._order: list[str] = []  # FIFO eviction order (begin() order)
        self._durations = deque(maxlen=max(self.min_history, int(window)))
        # Loud bookkeeping, surfaced via snapshot() and the promotion
        # instant — a sampler silently eating spans would be worse than
        # no sampler.
        self.promoted = 0
        self.promoted_error = 0
        self.discarded = 0
        self.evicted = 0
        self.span_overflow = 0

    # ------------------------------------------------------------- intake

    def begin(self, trace_id: str) -> None:
        """Register one in-flight request; called at the request edge
        (``ScoringServer._score``) right after the trace id is minted.
        Beyond ``capacity`` in-flight requests the OLDEST buffer is
        evicted (its spans are unrecoverable — counted, never silent)."""
        with self._lock:
            if trace_id in self._inflight:
                return
            self._inflight[trace_id] = []
            self._order.append(trace_id)
            while len(self._order) > self.capacity:
                victim = self._order.pop(0)
                if self._inflight.pop(victim, None) is not None:
                    self.evicted += 1

    def intercept(self, name: str, cat: str, t0: float, dur_s: float,
                  args: Optional[dict]) -> bool:
        """Divert one completed span into the buffers of the in-flight
        request(s) it belongs to. Returns False — pass through to the
        collector — when no owning request is registered."""
        a = args or {}
        ids = []
        tid = a.get("trace_id")
        if tid is not None:
            ids.append(tid)
        multi = a.get("trace_ids")
        if multi:
            ids.extend(multi)
        if not ids:
            return False
        event = {
            "name": name,
            "cat": cat,
            "ph": "X",
            "ts": round((t0 - _EPOCH) * 1e6, 1),
            "dur": round(dur_s * 1e6, 1),
            "pid": os.getpid(),
            "tid": threading.get_ident() & 0xFFFFFFFF,
            # A trace_span brings its own id (taken at enter, so children
            # can name it); a bare ``complete`` call gets one here.
            "args": a if "span_id" in a else {**a,
                                              "span_id": next(_span_ids)},
        }
        span = _BufferedSpan(event)
        hit = False
        with self._lock:
            for t in ids:
                buf = self._inflight.get(t)
                if buf is None:
                    continue
                hit = True
                if len(buf) >= self.max_spans_per_request:
                    self.span_overflow += 1
                else:
                    buf.append(span)
        return hit

    # ---------------------------------------------------------- decision

    def _threshold_locked(self) -> Optional[float]:
        n = len(self._durations)
        if n < self.min_history:
            return None
        ordered = sorted(self._durations)
        return ordered[min(n - 1, int(self.quantile * n))]

    def threshold_s(self) -> Optional[float]:
        """The current promotion threshold (None while history warms up)."""
        with self._lock:
            return self._threshold_locked()

    def finish(self, trace_id: str, duration_s: float,
               error: bool = False, force: bool = False) -> bool:
        """Completion verdict for one request: promote its buffered spans
        into the active collector (threshold breach, error, or ``force``)
        or discard them. Always feeds the rolling window. Returns True
        iff promoted.

        ``force`` carries a promotion verdict made ELSEWHERE — on the
        front line the scorer process judges its half of a request's
        chain first and flags the response frame, and the worker forces
        its half so the cross-process chain promotes as a unit
        (docs/observability.md §"Tail sampling")."""
        with self._lock:
            spans = self._inflight.pop(trace_id, None)
            threshold = self._threshold_locked()
            self._durations.append(float(duration_s))
            # Strictly greater: a uniform-latency workload (everything ==
            # the p95) is the BORING case and must not promote 100%.
            promote = bool(error) or bool(force) or (
                threshold is not None and duration_s > threshold)
            if not promote:
                if spans is not None:
                    self.discarded += 1
                return False
            if spans is None:
                return False  # evicted before the verdict: already counted
            to_emit = [s for s in spans if not s.emitted]
            for s in to_emit:
                s.emitted = True
            if error:
                self.promoted_error += 1
            self.promoted += 1
        col = _ACTIVE
        if col is not None:
            for s in to_emit:
                col.add(s.event)
            col.instant("photon.trace.tail_promoted", "meta", {
                "trace_id": trace_id,
                "duration_ms": round(duration_s * 1e3, 3),
                "threshold_ms": (None if threshold is None
                                 else round(threshold * 1e3, 3)),
                "reason": "error" if error else "latency",
                "spans": len(to_emit),
            })
        return True

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "inflight": len(self._inflight),
                "capacity": self.capacity,
                "quantile": self.quantile,
                "window": len(self._durations),
                "threshold_s": self._threshold_locked(),
                "promoted": self.promoted,
                "promoted_error": self.promoted_error,
                "discarded": self.discarded,
                "evicted": self.evicted,
                "span_overflow": self.span_overflow,
            }


_ACTIVE: Optional[TraceCollector] = None
_TAIL: Optional[TailSampler] = None


def tail_sampler() -> Optional[TailSampler]:
    return _TAIL


def install_tail_sampler(sampler: Optional[TailSampler]) -> Optional[TailSampler]:
    """Install (or clear, with None) the process-wide tail sampler."""
    global _TAIL
    _TAIL = sampler
    return sampler


def uninstall_tail_sampler() -> Optional[TailSampler]:
    global _TAIL
    s = _TAIL
    _TAIL = None
    return s


def _env_tail_sampler() -> Optional[TailSampler]:
    """Build a TailSampler from the environment, or None when off.
    Malformed knob values degrade to defaults — a typo must never kill
    tracing (same contract as ``_env_sample``)."""
    raw = (os.environ.get("PHOTON_TRACE_TAIL") or "").strip().lower()
    if raw in ("", "0", "false", "off", "no"):
        return None
    try:
        q = float(os.environ.get("PHOTON_TRACE_TAIL_QUANTILE", 0.95))
    except (TypeError, ValueError):
        q = 0.95
    if not 0.0 < q < 1.0:
        q = 0.95
    try:
        window = int(os.environ.get("PHOTON_TRACE_TAIL_WINDOW", 256))
    except (TypeError, ValueError):
        window = 256
    return TailSampler(quantile=q, window=window)


def tracing_active() -> bool:
    return _ACTIVE is not None


def active_collector() -> Optional[TraceCollector]:
    return _ACTIVE


def start_tracing(max_events: int = _DEFAULT_MAX_EVENTS) -> TraceCollector:
    """Install a process-wide collector (replacing any active one).
    ``PHOTON_TRACE_TAIL=1`` also installs a tail sampler, unless one is
    already installed (explicit installs win over the env default)."""
    global _ACTIVE, _TAIL
    _ACTIVE = TraceCollector(max_events=max_events)
    if _TAIL is None:
        _TAIL = _env_tail_sampler()
    return _ACTIVE


def stop_tracing(path: Optional[str] = None) -> Optional[TraceCollector]:
    """Uninstall the active collector; write it to ``path`` if given."""
    global _ACTIVE
    col = _ACTIVE
    _ACTIVE = None
    if col is not None and path:
        col.write(path)
    return col


class suspend_tracing:
    """``with suspend_tracing():`` — temporarily uninstall any active
    collector (restored on exit). Benchmarks use this so headline numbers
    are always measured tracing-off even under ``--trace-out``."""

    __slots__ = ("_prev",)

    def __enter__(self) -> None:
        global _ACTIVE
        self._prev = _ACTIVE
        _ACTIVE = None

    def __exit__(self, *exc) -> None:
        global _ACTIVE
        _ACTIVE = self._prev


class tracing:
    """``with tracing(path) as col:`` — scoped collector install, written on
    exit (restores whatever was active before, so traces can nest in
    tests)."""

    __slots__ = ("path", "max_events", "collector", "_prev")

    def __init__(self, path: Optional[str] = None,
                 max_events: int = _DEFAULT_MAX_EVENTS):
        self.path = path
        self.max_events = max_events
        self.collector: Optional[TraceCollector] = None

    def __enter__(self) -> TraceCollector:
        global _ACTIVE
        self._prev = _ACTIVE
        self.collector = TraceCollector(max_events=self.max_events)
        _ACTIVE = self.collector
        return self.collector

    def __exit__(self, *exc) -> None:
        global _ACTIVE
        _ACTIVE = self._prev
        if self.path and self.collector is not None:
            self.collector.write(self.path)


# Kept span trees (docs/observability.md §"Kept trees"): the last roots that
# asked (:meth:`trace_span.keep_tree`), oldest dropped first. Bounded three
# ways so an always-on ring cannot grow host memory: roots held, spans held
# in all, spans of one tree (a tree over its cap says so on its root).
_KEPT_ROOTS = 256
_KEPT_SPANS = 65_536
_KEPT_SPANS_PER_TREE = 16_384
_kept: deque = deque()          # (root name, [span tuples]), oldest first
_kept_spans = 0
_kept_lock = threading.Lock()


def _keep(name: str, records: list) -> None:
    global _kept_spans
    with _kept_lock:
        _kept.append((name, records))
        _kept_spans += len(records)
        while len(_kept) > 1 and (len(_kept) > _KEPT_ROOTS
                                  or _kept_spans > _KEPT_SPANS):
            _kept_spans -= len(_kept.popleft()[1])


def recent_trees(root_name: str, last: Optional[int] = None) -> list:
    """The kept trees whose root is named ``root_name``, oldest first (the
    ``last`` of them, if given). A tree is the list of its finished spans
    in the order they ended, the root last, each a tuple ``(name, span_id,
    parent_id, start_s, end_s, args)``: seconds on this module's clock,
    ``parent_id`` None on the root, ``args`` the span's own (with its
    ``trace_id``, and ``error`` if an exception escaped it)."""
    with _kept_lock:
        trees = [records for name, records in _kept if name == root_name]
    if last is None:
        return trees
    return trees[-last:] if last > 0 else []


# ``jax.profiler.TraceAnnotation``, once this process has imported jax.
_annotation = None


def _find_annotation():
    """The profiler's annotation class if jax is already imported here,
    else None. Never imports jax: a span must not pull it into a process
    that runs without it."""
    global _annotation
    profiler = getattr(sys.modules.get("jax"), "profiler", None)
    # (None while jax is still being imported)
    _annotation = getattr(profiler, "TraceAnnotation", None)
    return _annotation


class trace_span:
    """``with trace_span("descent.step", cat="descent", sweep=0) as sp:``

    Measures wall-clock into ``sp.seconds`` ALWAYS (so instrumented code can
    drop its hand-rolled ``perf_counter`` pairs); emits a complete event only
    when a collector is active. The span's ``trace_id`` defaults to the
    thread's current context (:func:`trace_context`); pass one explicitly at
    trace roots. ``sp.set(key=value)`` adds result attributes (iteration
    counts, row counts) before exit. An escaping exception is recorded as
    ``args["error"]``.

    ``sp.span_id`` is taken at enter and ``sp.parent_id`` is the id of the
    span then open on the same thread (None at a root); both go into the
    event's ``args``. A span entered by hand and still open when an outer
    span of its thread exits (an exception unwound past it) ends there,
    with that exception as its error. While a ``jax.profiler`` session
    records, the span is also a ``TraceAnnotation`` of the same name
    (:meth:`label` adds to that one's).
    """

    __slots__ = ("name", "cat", "args", "trace_id", "seconds", "span_id",
                 "parent_id", "_t0", "_keep", "_discarded", "_records",
                 "_annotation", "_label")

    def __init__(self, name: str, cat: str = "app",
                 trace_id: Optional[str] = None, **args):
        self.name = name
        self.cat = cat
        self.args = args
        self.trace_id = trace_id
        self.seconds = 0.0
        self._keep = False
        self._discarded = False
        self._label = name

    def set(self, **args) -> "trace_span":
        self.args.update(args)
        return self

    def keep_tree(self) -> "trace_span":
        """Ask, before entering, that this span and every span entered
        under it on this thread be kept when it ends, for
        :func:`recent_trees`. (A kept root under another keeps a tree of
        its own; the outer tree does not hold it.)"""
        self._keep = True
        return self

    def label(self, text: str) -> "trace_span":
        """Ask, before entering, that the span's profiler annotation be
        called ``<name>:<text>``. An annotation carries a name and no
        arguments, so this is how a profile tells apart what the kept tree
        and the collector tell apart by an argument; their name stays."""
        self._label = f"{self.name}:{text}"
        return self

    def discard(self) -> None:
        """Call inside the span: it turned out to cover no work (a cache
        hit found only by trying). It leaves the stack as usual and is
        recorded nowhere but in a running profile, as a span of no
        length to speak of."""
        self._discarded = True

    def __enter__(self) -> "trace_span":
        try:
            stack = _tls.stack
        except AttributeError:
            stack = _tls.stack = []
        self.span_id = next(_span_ids)
        if stack:
            parent = stack[-1]
            self.parent_id = parent.span_id
            self._records = parent._records
        else:
            self.parent_id = None
            self._records = None
        if self._keep:
            self._records = []
        stack.append(self)
        annotation = _annotation
        if annotation is None and "jax" in sys.modules:
            annotation = _find_annotation()
        if annotation is not None and annotation.is_enabled():
            self._annotation = annotation(self._label)
            self._annotation.__enter__()
        else:
            self._annotation = None
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        t1 = time.perf_counter()
        self.seconds = t1 - self._t0
        stack = _tls.stack
        if stack[-1] is self:
            stack.pop()
        elif self in stack:
            while stack[-1] is not self:
                stack[-1].__exit__(exc_type, exc, tb)
            stack.pop()
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        col = _ACTIVE
        records = self._records
        if (col is None and records is None) or self._discarded:
            return
        args = self.args
        tid = self.trace_id or current_trace_id()
        if tid is not None:
            args = {"trace_id": tid, **args}
        if exc_type is not None:
            args = {**args, "error": exc_type.__name__}
        if records is not None:
            full = len(records) >= _KEPT_SPANS_PER_TREE
            if self._keep or not full:
                if full:
                    args = {**args, "truncated": True}
                records.append((self.name, self.span_id, self.parent_id,
                                self._t0 - _EPOCH, t1 - _EPOCH, args))
            if self._keep:
                _keep(self.name, records)
        if col is not None:
            ids = {"span_id": self.span_id}
            if self.parent_id is not None:
                ids["parent_id"] = self.parent_id
            col.complete(self.name, self.cat, self._t0, self.seconds,
                         {**args, **ids})


def device_wait(site: str) -> trace_span:
    """``with device_wait("step"): np.asarray(score[:1])`` — the span
    ``device.wait`` around a read that blocks this thread until the device
    has the value: every device-to-host read of a fit goes through here, so
    that a fit's tree says where its host waited on the chip. ``site`` names
    the place (an argument in the tree, ``device.wait:<site>`` in a
    profile). It wraps the read that is there and adds none: seconds inside
    it are a lower bound of the device's busy seconds (what was dispatched
    before must finish first) plus the transfer's own; seconds of a layer
    outside it are the host's, an upper bound of the device's idle ones."""
    return trace_span("device.wait", cat="device", site=site).label(site)


def instant(name: str, cat: str = "event", **args) -> None:
    """Emit an instant event (no duration) if tracing is active — fault
    firings, retrace warnings, admission rejections."""
    col = _ACTIVE
    if col is None:
        return
    tid = current_trace_id()
    if tid is not None:
        args = {"trace_id": tid, **args}
    col.instant(name, cat, args)
