"""CI smoke for the online incremental-learning loop (docs/online.md).

One in-process pass that proves the subsystem's contracts end to end:

1. train a tiny GAME model with the real training driver, serve it with
   the real scoring server;
2. replay a small JSONL event stream through the REAL online training
   driver (``cli/online_training_driver.py``) publishing deltas over HTTP
   (``POST /admin/patch``) against the live server;
3. assert: served scores CHANGE post-delta (and only via patches — the
   model version never moves), the freshness metric is present in both
   the trace (``online.publish`` spans) and the metrics registry, the
   patch journal and replay cursor advanced, ``/healthz`` reports the
   freshness watermarks, and the scoring kernel logged ZERO
   retraces-after-warmup across patch publication (the stable-shape
   contract survives delta application).

Run by ci.sh (online smoke stage); exits non-zero with a named failure.
"""
from __future__ import annotations

import http.client
import json
import os
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

N_USERS = 4


def fail(msg: str) -> None:
    print(f"online_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def write_train_data(path: str, rows_per_user: int = 12) -> None:
    from photon_tpu.io.avro import write_container

    rng = np.random.default_rng(11)
    recs = []
    for i in range(N_USERS * rows_per_user):
        u = i % N_USERS
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


def write_events(path: str, n: int = 48) -> None:
    """A skewed stream: every event is a POSITIVE label with the same
    strong feature vector, so the refreshed per-user coefficients MUST
    move away from the batch-trained ones."""
    from photon_tpu.online import OnlineEvent, append_events

    events = []
    for i in range(n):
        u = i % N_USERS
        events.append(OnlineEvent(
            entities={"userId": f"user{u}"},
            features=[{"name": "g", "term": str(j), "value": 1.5}
                      for j in range(3)],
            label=1.0,
        ))
    append_events(path, events)


def main() -> None:
    from photon_tpu.cli import game_training_driver, online_training_driver
    from photon_tpu.cli.params import enable_trace, finish_trace
    from photon_tpu.estimators.game_transformer import SCORE_KERNEL_NAME
    from photon_tpu.obs import retrace
    from photon_tpu.obs.metrics import REGISTRY
    from photon_tpu.serving import (
        MicroBatcher, ModelRegistry, ScoringServer, ServingConfig,
    )

    td = tempfile.mkdtemp(prefix="online-smoke-")
    train = os.path.join(td, "train.avro")
    write_train_data(train)
    out = os.path.join(td, "out")
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
        "--devices", "1",
    ])
    events_path = os.path.join(td, "events.jsonl")
    write_events(events_path)

    trace_path = os.path.join(td, "online-trace.json")
    enable_trace(trace_path)
    cfg = ServingConfig(max_batch=8, cache_entities=16, max_row_nnz=16)
    registry = ModelRegistry(os.path.join(out, "best"), cfg)
    batcher = MicroBatcher(max_batch=8, max_wait_ms=1.0)
    server = ScoringServer(registry, batcher, port=0)
    server.start()
    host, port = server.address

    def post(path, payload):
        conn = http.client.HTTPConnection(host, port, timeout=30)
        conn.request("POST", path, body=json.dumps(payload).encode(),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        body = json.loads(resp.read())
        conn.close()
        return resp.status, body

    def get(path):
        conn = http.client.HTTPConnection(host, port, timeout=30)
        conn.request("GET", path)
        resp = conn.getresponse()
        body = json.loads(resp.read())
        conn.close()
        return resp.status, body

    probe = {
        "features": [{"name": "g", "term": str(j), "value": 1.5}
                     for j in range(3)],
        "entities": {"userId": "user0"},
    }
    try:
        status, before = post("/score", probe)
        if status != 200:
            fail(f"/score pre-delta returned {status}: {before}")
        retraces0 = retrace.retraces_after_warmup(SCORE_KERNEL_NAME)
        fresh0 = REGISTRY.histogram("online_freshness_seconds")
        fresh_count0 = fresh0.histogram.snapshot().get("count", 0)

        online_out = os.path.join(td, "online_out")
        summary = online_training_driver.run([
            "--model-dir", os.path.join(out, "best"),
            "--events", events_path,
            "--serve-url", f"http://{host}:{port}",
            "--output-dir", online_out,
            "--window", "16",
            "--max-event-nnz", "8",
            "--refresh-batch", "2",
            "--cadence-s", "0",
            "--incremental-weight", "0.5",
            "--max-iter", "15",
        ])
        if summary["deltas"] < 2:
            fail(f"expected >= 2 published deltas, got {summary}")

        # -- served scores changed, via patches only ----------------------
        status, after = post("/score", probe)
        if status != 200:
            fail(f"/score post-delta returned {status}: {after}")
        if after["model_version"] != before["model_version"]:
            fail("model version moved — deltas must patch, not swap")
        if abs(after["score"] - before["score"]) < 1e-9:
            fail(f"served score did not change post-delta "
                 f"(before={before['score']}, after={after['score']})")
        print(f"online_smoke: served score moved "
              f"{before['score']:.4f} -> {after['score']:.4f} "
              f"(version {after['model_version']} unchanged)")

        # -- zero retraces-after-warmup across patch publication ----------
        drift = retrace.retraces_after_warmup(SCORE_KERNEL_NAME) - retraces0
        if drift != 0:
            fail(f"scoring kernel retraced {drift}x across patch "
                 "publication — the stable-shape contract broke")

        # -- freshness: /healthz watermarks + metric + trace spans --------
        status, health = get("/healthz")
        if status != 200:
            fail(f"/healthz returned {status}")
        fr = health.get("freshness") or {}
        if fr.get("patch_seq", 0) < 2 or not fr.get("last_patch_ts"):
            fail(f"/healthz freshness watermarks missing/stale: {fr}")
        if fr.get("patched_entities_total", 0) < N_USERS:
            fail(f"/healthz patched_entities_total too low: {fr}")
        status, metrics = get("/metrics")
        if metrics.get("freshness", {}).get("patch_seq") != fr["patch_seq"]:
            fail("/metrics freshness disagrees with /healthz")
        fresh_count = REGISTRY.histogram(
            "online_freshness_seconds").histogram.snapshot().get("count", 0)
        if fresh_count - fresh_count0 < N_USERS:
            fail(f"freshness histogram did not record refreshes "
                 f"({fresh_count0} -> {fresh_count})")

        # -- journal + cursor advanced ------------------------------------
        journal = os.path.join(online_out, "patch-journal.jsonl")
        with open(journal) as f:
            rows = [json.loads(x) for x in f if x.strip()]
        if len(rows) != summary["deltas"]:
            fail(f"patch journal has {len(rows)} rows, expected "
                 f"{summary['deltas']}")
        with open(os.path.join(online_out, "online-cursor.json")) as f:
            cursor = json.load(f)
        if cursor["next_seq"] != summary["events"]:
            fail(f"cursor did not advance past the published stream: "
                 f"{cursor} vs {summary['events']} events")
    finally:
        server.shutdown()
        finish_trace(trace_path)

    with open(trace_path) as f:
        events = json.load(f)["traceEvents"]
    names = {e["name"] for e in events}
    for needed in ("online.refresh", "online.solve", "online.publish"):
        if needed not in names:
            fail(f"trace missing {needed!r} spans; have {sorted(names)}")
    pubs = [e for e in events if e["name"] == "online.publish"
            and e.get("ph") == "X"]
    if not any(e.get("args", {}).get("freshness_max_s") is not None
               for e in pubs):
        fail("no online.publish span carries freshness_max_s — the "
             "freshness metric is absent from the trace")
    applied = [e for e in events if e["name"] == "serving.delta_applied"]
    if len(applied) < 2:
        fail(f"expected >= 2 serving.delta_applied instants, got "
             f"{len(applied)}")
    print(f"online_smoke: trace ok ({len(pubs)} publishes, "
          f"{len(applied)} applies, freshness present)")
    print("online_smoke: OK")


if __name__ == "__main__":
    main()
