"""CI smoke for the observability layer (docs/observability.md).

One in-process pass that proves the tentpole contracts hold end to end:

1. a tiny traced GAME training run (``--trace-out`` on the real driver,
   under a fault plan that fires at a descent step) emits **well-formed
   Chrome trace-event JSON** with at least one span per instrumented layer
   (ingest, descent, optimizer), one span per coordinate step, and a
   tagged instant event for every injected fault;
2. a scoring server over the trained model, driven by real HTTP requests
   under an active trace, serves ``/metrics?format=prom`` as **lintable
   Prometheus text** covering latency, throughput, queue depth, and
   per-kernel retrace counts — and the serve trace carries the request's
   trace id across the micro-batcher thread boundary.

Run by ci.sh (obs smoke stage); exits non-zero with a named failure.
"""
from __future__ import annotations

import http.client
import json
import os
import re
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

import jax  # noqa: E402

# Hermetic like ci.sh's entry check: pin the CPU whatever JAX_PLATFORMS
# says; the smoke must never claim a chip.
jax.config.update("jax_platforms", "cpu")

SCHEMA = {
    "type": "record",
    "name": "TrainingExampleAvro",
    "fields": [
        {"name": "uid", "type": ["null", "string"], "default": None},
        {"name": "response", "type": "double"},
        {"name": "offset", "type": ["null", "double"], "default": None},
        {"name": "weight", "type": ["null", "double"], "default": None},
        {"name": "features", "type": {"type": "array", "items": {
            "type": "record", "name": "FeatureAvro", "fields": [
                {"name": "name", "type": "string"},
                {"name": "term", "type": ["null", "string"], "default": None},
                {"name": "value", "type": "double"},
            ]}}},
        {"name": "metadataMap",
         "type": ["null", {"type": "map", "values": "string"}],
         "default": None},
    ],
}

# Prometheus text format (version 0.0.4) line grammar — the lint ci.sh
# promises: every non-blank line is a HELP/TYPE comment or a sample of the
# form  name{labels} value  with a float-parseable value.
_PROM_METRIC = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"            # metric name
    r"(\{[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\""  # first label
    r"(,[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\")*\})?"
    r" [^ ]+$"
)
_PROM_COMMENT = re.compile(r"^# (HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]*( .*)?$")


def fail(msg: str) -> None:
    print(f"obs_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def write_data(path: str, n_users: int = 4, rows_per_user: int = 12) -> None:
    from photon_tpu.io.avro import write_container

    rng = np.random.default_rng(11)
    recs = []
    for i in range(n_users * rows_per_user):
        u = i % n_users
        x = rng.normal(size=3)
        recs.append({
            "uid": str(i),
            "response": float(rng.random() < 0.5),
            "offset": None,
            "weight": None,
            "features": [
                {"name": "g", "term": str(j), "value": float(x[j])}
                for j in range(3)
            ],
            "metadataMap": {"userId": f"user{u}"},
        })
    write_container(path, SCHEMA, recs)


def lint_prometheus(text: str) -> int:
    n_samples = 0
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("#"):
            if not _PROM_COMMENT.match(line):
                fail(f"prometheus lint: bad comment line {line!r}")
            continue
        if not _PROM_METRIC.match(line):
            fail(f"prometheus lint: bad sample line {line!r}")
        value = line.rsplit(" ", 1)[1]
        if value not in ("NaN", "+Inf", "-Inf"):
            try:
                float(value)
            except ValueError:
                fail(f"prometheus lint: unparseable value in {line!r}")
        n_samples += 1
    if n_samples == 0:
        fail("prometheus lint: no samples")
    return n_samples


def check_trace(path: str, n_steps_expected: int, n_faults_expected: int):
    with open(path) as f:
        doc = json.load(f)
    events = doc.get("traceEvents")
    if not isinstance(events, list) or not events:
        fail(f"{path}: no traceEvents array")
    for e in events:
        for k in ("name", "ph", "ts", "pid", "tid"):
            if k not in e:
                fail(f"{path}: event missing {k!r}: {e}")
        if e["ph"] == "X" and "dur" not in e:
            fail(f"{path}: complete event missing dur: {e}")
    spans = [e for e in events if e["ph"] == "X"]
    by_cat: dict = {}
    for e in spans:
        by_cat.setdefault(e.get("cat", ""), []).append(e)
    for layer in ("ingest", "descent", "optim"):
        if not by_cat.get(layer):
            fail(f"{path}: no spans for instrumented layer {layer!r}; "
                 f"have {sorted(by_cat)}")
    steps = [e for e in spans if e["name"] == "descent.step"]
    if len(steps) != n_steps_expected:
        fail(f"{path}: expected {n_steps_expected} descent.step spans, "
             f"got {len(steps)}")
    faults = [e for e in events
              if e["ph"] == "i" and e.get("cat") == "fault"]
    if len(faults) < n_faults_expected:
        fail(f"{path}: expected >= {n_faults_expected} fault events, "
             f"got {len(faults)}")
    return events


def main() -> None:
    from photon_tpu.cli import game_training_driver
    from photon_tpu.faults import FaultPlan, FaultSpec

    td = tempfile.mkdtemp(prefix="obs-smoke-")
    train = os.path.join(td, "train.avro")
    write_data(train)

    # A plan whose spec FIRES (recorded + trace-evented) but injects only a
    # 0-second delay: the run must finish, and the timeline must show it.
    plan_path = os.path.join(td, "plan.json")
    with open(plan_path, "w") as f:
        f.write(FaultPlan(seed=3, specs=[
            FaultSpec(site="descent.step", delay_s=0.0, after=1, count=1),
        ]).to_json())

    out = os.path.join(td, "out")
    trace_path = os.path.join(td, "train-trace.json")
    n_sweeps = 2
    game_training_driver.run([
        "--train-data", train,
        "--output-dir", out,
        "--task", "LOGISTIC_REGRESSION",
        "--feature-shard", "global:features",
        "--coordinate",
        "fixed:type=fixed,shard=global,reg=L2,max_iter=10,reg_weights=1",
        "--coordinate",
        "perUser:type=random,re_type=userId,shard=global,reg=L2,"
        "max_iter=10,reg_weights=1",
        "--sweeps", str(n_sweeps),
        "--devices", "1",
        "--fault-plan", plan_path,
        "--trace-out", trace_path,
    ])
    check_trace(trace_path, n_steps_expected=2 * n_sweeps,
                n_faults_expected=1)
    print(f"obs_smoke: training trace ok ({trace_path})")

    # ---- timeline analyzer over the smoke trace (obs/analysis/) ---------
    # End-to-end contract: the analyzer must produce a critical path and
    # an overlap report from a real --trace-out artifact, and both must be
    # internally consistent — owned shares partition the wall (sum <= 1),
    # and no clamping path may ever yield a negative duration.
    from photon_tpu.obs.analysis import analyze_trace

    report = analyze_trace(trace_path)
    if report.wall_seconds <= 0 or not report.critical_path():
        fail(f"analyzer: no critical path from {trace_path}")
    share_sum = sum(report.owned_shares.values())
    if share_sum > 1.0 + 1e-6:
        fail(f"analyzer: owned shares sum {share_sum} > 1.0")
    if report.idle_seconds < 0 or any(
            secs < 0 for secs in report.owned.values()):
        fail("analyzer: negative duration in attribution")
    ov = report.overlap["compute_overlapped_fraction"]
    if ov is None:
        fail(f"analyzer: no ingest/compute overlap report "
             f"(layers: {sorted(report.layers)})")
    if not 0.0 <= ov <= 1.0:
        fail(f"analyzer: overlap fraction {ov} outside [0, 1]")
    # ISSUE 9 regression guard: the overlap REPORT must stay present and
    # well-formed (the asserts above and the verdict below) — a refactor
    # that drops the ingest spans would turn it one-sided/None and fail
    # here instead of rotting quietly. The pre-pipeline overlap value was
    # exactly 0.0 and the smoke fit is in-core (all compute after the
    # read), so there is no meaningful numeric floor to gate at this
    # scale; the pipelined data path's ≥0.5 verdict is measured where it
    # runs, in bench.py game_scale (game_scale_overlap_fraction — SLO
    # rule example in docs/observability.md).
    if report.overlap.get("verdict") not in (
            "serialized", "partially-overlapped", "overlapped"):
        fail(f"analyzer: overlap verdict missing/unknown: "
             f"{report.overlap.get('verdict')!r}")
    # The driver's ingest must have gone through the prefetch pipeline
    # (io/prefetch.py): the consumer's bounded-queue pull is span-traced,
    # so its absence means the pipelined read path silently fell back.
    with open(trace_path) as f:
        _train_events = json.load(f)["traceEvents"]
    if not any(e.get("name") == "ingest.prefetch_queue_wait"
               for e in _train_events):
        fail("training trace has no ingest.prefetch_queue_wait spans — "
             "the driver's prefetched ingest pipeline did not run")
    print(f"obs_smoke: timeline analyzer ok (bottleneck "
          f"{report.bottleneck()['cat']}:{report.bottleneck()['name']}, "
          f"ingest/compute overlap {ov}, shares sum {share_sum:.4f})")

    # ---- serving: traced requests + Prometheus exposition ----------------
    from photon_tpu.cli.params import enable_trace, finish_trace
    from photon_tpu.serving import (
        MicroBatcher, ModelRegistry, ScoringServer, ServingConfig,
    )

    serve_trace = os.path.join(td, "serve-trace.json")
    enable_trace(serve_trace)
    cfg = ServingConfig(max_batch=8, cache_entities=16, max_row_nnz=16)
    registry = ModelRegistry(os.path.join(out, "best"), cfg)
    batcher = MicroBatcher(max_batch=8, max_wait_ms=1.0)
    server = ScoringServer(registry, batcher, port=0)
    server.start()
    host, port = server.address
    try:
        conn = http.client.HTTPConnection(host, port, timeout=30)
        for i in range(12):
            conn.request("POST", "/score", body=json.dumps({
                "features": [{"name": "g", "term": "0", "value": 1.0}],
                "entities": {"userId": f"user{i % 4}"},
            }).encode(), headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            resp.read()
            if resp.status != 200:
                fail(f"/score returned {resp.status}")
        conn.request("GET", "/metrics?format=prom")
        resp = conn.getresponse()
        ctype = resp.getheader("Content-Type") or ""
        prom = resp.read().decode()
        conn.close()
        if resp.status != 200 or "text/plain" not in ctype:
            fail(f"/metrics?format=prom: status {resp.status}, "
                 f"content-type {ctype!r}")
        # ---- SLO evaluation against the live snapshot -------------------
        # One deliberately impossible rule + one trivially true rule: the
        # violation must bump slo_violations_total and land an instant in
        # the active trace; the pass must not.
        from photon_tpu.obs.analysis import SloConfig
        from photon_tpu.obs.metrics import REGISTRY

        slo = SloConfig.from_dict({"slos": [
            {"name": "smoke_p99_impossible", "metric": "latency.p99_ms",
             "op": "<=", "threshold": 0.0},
            {"name": "smoke_requests_floor", "metric": "requests",
             "op": ">=", "threshold": 1},
        ]})
        slo_report = slo.evaluate(server.metrics_snapshot(), where="smoke")
        if [r.name for r in slo_report.violations] != [
                "smoke_p99_impossible"]:
            fail(f"slo: expected exactly the impossible rule to violate, "
                 f"got {[r.to_dict() for r in slo_report.results]}")
        if REGISTRY.counter("slo_violations_total").value(
                slo="smoke_p99_impossible") < 1:
            fail("slo: violation did not bump slo_violations_total")
    finally:
        server.shutdown()
        finish_trace(serve_trace)

    n = lint_prometheus(prom)
    for needed in (
        "photon_serve_request_latency_seconds",   # latency
        "photon_serve_requests_total",            # throughput
        "photon_serve_queue_depth",               # queue depth
        "photon_kernel_traces_total",             # per-kernel retraces
    ):
        if needed not in prom:
            fail(f"prometheus exposition missing {needed}")
    print(f"obs_smoke: prometheus exposition ok ({n} samples linted)")

    with open(serve_trace) as f:
        events = json.load(f)["traceEvents"]
    names = {e["name"] for e in events}
    for needed in ("serve.request", "serve.admission", "serve.queue_wait",
                   "serve.batch", "serve.kernel"):
        if needed not in names:
            fail(f"serve trace missing {needed!r} spans; have {sorted(names)}")
    # Trace-id propagation across the batcher thread boundary: every
    # queue-wait span (emitted by the WORKER thread) must carry a trace id
    # minted by a request handler thread.
    req_ids = {e["args"]["trace_id"] for e in events
               if e["name"] == "serve.request" and "trace_id" in e["args"]}
    qw_ids = {e["args"].get("trace_id") for e in events
              if e["name"] == "serve.queue_wait"}
    if not req_ids or not qw_ids or not (qw_ids <= req_ids):
        fail(f"trace-id propagation broken: requests={len(req_ids)} ids, "
             f"queue_wait carries {qw_ids - req_ids} unknown ids")
    print(f"obs_smoke: serve trace ok ({len(events)} events, "
          f"{len(req_ids)} request traces propagated)")
    # Analyzer over the SERVE trace: the queue-wait breakdown must see the
    # batcher's cross-thread serve.queue_wait spans, and the SLO judgment
    # above must have landed exactly one violation instant in the timeline.
    serve_report = analyze_trace(serve_trace)
    qw = serve_report.queue_wait.get("serve.queue_wait")
    if not qw or qw["count"] < 1:
        fail(f"analyzer: no serve.queue_wait breakdown "
             f"(got {serve_report.queue_wait})")
    slo_events = [e for e in events if e.get("cat") == "slo"]
    viol = [e for e in slo_events if e["name"] == "slo.violation"]
    if len(viol) != 1 or viol[0]["args"].get("slo") != "smoke_p99_impossible":
        fail(f"slo: expected one slo.violation instant in the serve "
             f"trace, got {slo_events}")
    print(f"obs_smoke: analyzer queue-wait + slo instants ok "
          f"({qw['count']} waits, mean {qw['mean_ms']}ms)")
    print("obs_smoke: OK")


if __name__ == "__main__":
    main()
