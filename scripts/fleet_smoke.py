"""CI smoke for fleet observability (docs/observability.md §"Fleet view").

A REAL 3-process drill over the ``--telemetry-dir`` convention:

1. the training driver runs as its own process, writing its trace +
   registry shard into the shared telemetry dir;
2. the serving driver runs as its own process over the trained model;
3. the online training driver runs as a third process, replaying an
   event stream and publishing deltas to the live server over HTTP
   (the ``X-Photon-Trace-Id`` join path).

Then the aggregation layer is exercised exactly the way an operator
would: ``python -m photon_tpu.obs.analysis report <run-dir> --json``
must produce a schema-valid fleet report whose MERGED timeline carries
all three roles with >= 1 cross-process trace-id join (online publish →
serving patch apply), whose anomaly scan reports ZERO anomalies on the
clean run — and, after an injected latency level shift is appended to
the serving metrics JSONL, >= 1 anomaly on exactly that series.

Run by ci.sh (fleet smoke stage); exits non-zero with a named failure.
"""
from __future__ import annotations

import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

import jax  # noqa: E402

# Hermetic like ci.sh's entry check: pin the CPU whatever JAX_PLATFORMS
# says; the smoke must never claim a chip.
# Child driver processes are pinned via --backend-policy cpu-only.
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
ROLES_EXPECTED = {"training", "serving", "online"}


def fail(msg: str) -> None:
    print(f"fleet_smoke: FAIL: {msg}", file=sys.stderr)
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


def write_events(path: str, n: int = 32) -> None:
    from photon_tpu.online import OnlineEvent, append_events

    append_events(path, [
        OnlineEvent(
            entities={"userId": f"user{i % N_USERS}"},
            features=[{"name": "g", "term": str(j), "value": 1.5}
                      for j in range(3)],
            label=1.0,
        )
        for i in range(n)
    ])


def run_child(argv, env, timeout_s=600, name="child"):
    """One driver process, output captured; a nonzero exit names itself."""
    proc = subprocess.run(
        argv, env=env, timeout=timeout_s,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
    )
    if proc.returncode != 0:
        tail = proc.stdout.decode("utf-8", "replace")[-3000:]
        fail(f"{name} exited {proc.returncode}:\n{tail}")
    return proc


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def wait_healthy(host, port, deadline_s=120.0) -> None:
    t0 = time.monotonic()
    while time.monotonic() - t0 < deadline_s:
        try:
            conn = http.client.HTTPConnection(host, port, timeout=5)
            conn.request("GET", "/healthz")
            resp = conn.getresponse()
            resp.read()
            conn.close()
            if resp.status == 200:
                return
        except OSError:
            pass
        time.sleep(0.25)
    fail(f"serving process never became healthy on {host}:{port}")


def main() -> None:
    td = tempfile.mkdtemp(prefix="fleet-smoke-")
    telemetry = os.path.join(td, "telemetry")
    train = os.path.join(td, "train.avro")
    out = os.path.join(td, "out")
    write_train_data(train)
    events_path = os.path.join(td, "events.jsonl")
    write_events(events_path)

    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": os.pathsep.join(
            [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]
            + ([os.environ["PYTHONPATH"]]
               if os.environ.get("PYTHONPATH") else [])),
    }
    py = sys.executable

    # ---- process 1: training driver -------------------------------------
    run_child([
        py, "-m", "photon_tpu.cli.game_training_driver",
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
        "--backend-policy", "cpu-only",
        "--telemetry-dir", telemetry,
    ], env, name="training driver")
    print("fleet_smoke: training process done")

    # ---- process 2: serving driver --------------------------------------
    host, port = "127.0.0.1", free_port()
    serve_logs = os.path.join(td, "serve_logs")
    serving = subprocess.Popen([
        py, "-m", "photon_tpu.cli.serving_driver",
        "--model-dir", os.path.join(out, "best"),
        "--host", host, "--port", str(port),
        "--max-batch", "8", "--max-wait-ms", "1",
        "--cache-entities", "16", "--max-row-nnz", "16",
        "--output-dir", serve_logs,
        "--metrics-interval", "0.3",
        "--backend-policy", "cpu-only",
        "--telemetry-dir", telemetry,
    ], env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    try:
        wait_healthy(host, port)
        print(f"fleet_smoke: serving process healthy on :{port}")

        # Drive a few scores so the serving shard has request spans (and
        # the metrics JSONL a latency history).
        conn = http.client.HTTPConnection(host, port, timeout=30)
        for i in range(16):
            conn.request("POST", "/score", body=json.dumps({
                "features": [{"name": "g", "term": "0", "value": 1.0}],
                "entities": {"userId": f"user{i % N_USERS}"},
            }).encode(), headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            resp.read()
            if resp.status != 200:
                fail(f"/score returned {resp.status}")
        conn.close()

        # ---- process 3: online trainer publishing over HTTP --------------
        run_child([
            py, "-m", "photon_tpu.cli.online_training_driver",
            "--model-dir", os.path.join(out, "best"),
            "--events", events_path,
            "--serve-url", f"http://{host}:{port}",
            "--output-dir", os.path.join(td, "online_out"),
            "--window", "16", "--max-event-nnz", "8",
            "--refresh-batch", "2", "--cadence-s", "0",
            "--incremental-weight", "0.5", "--max-iter", "15",
            "--backend-policy", "cpu-only",
            "--telemetry-dir", telemetry,
        ], env, name="online driver")
        print("fleet_smoke: online process done (deltas published)")
        # Let the 0.3s metrics flusher persist a few post-patch rows.
        time.sleep(1.0)
    finally:
        # Graceful stop: SIGTERM routes through the driver's KeyboardInterrupt
        # path — batcher drained, metrics flushed, trace + registry shard
        # written in the run() finally.
        serving.send_signal(signal.SIGTERM)
        try:
            serving.wait(timeout=60)
        except subprocess.TimeoutExpired:
            serving.kill()
            fail("serving process ignored SIGTERM for 60s")
    if serving.returncode != 0:
        tail = serving.stdout.read().decode("utf-8", "replace")[-3000:]
        fail(f"serving process exited {serving.returncode}:\n{tail}")
    print("fleet_smoke: serving process stopped cleanly")

    shards = [f for f in os.listdir(telemetry) if f.startswith("trace.")]
    if len(shards) < 3:
        fail(f"expected >= 3 trace shards in {telemetry}, got {shards}")
    regs = [f for f in os.listdir(telemetry) if f.startswith("registry.")]
    if len(regs) < 3:
        fail(f"expected >= 3 registry shards in {telemetry}, got {regs}")

    # ---- the operator path: report CLI over the whole run dir -----------
    def generate(tag):
        report_path = os.path.join(td, f"report-{tag}.json")
        merged_path = os.path.join(td, f"merged-{tag}.json")
        run_child([
            py, "-m", "photon_tpu.obs.analysis", "report", td,
            "--json", report_path, "--merged-trace", merged_path,
        ], env, name="report CLI")
        with open(report_path) as f:
            return json.load(f), merged_path

    report, merged_path = generate("clean")

    # -- schema + topology -------------------------------------------------
    if report.get("schema") != "photon-fleet-report/1":
        fail(f"report schema: {report.get('schema')!r}")
    for key in ("topology", "merged_trace", "per_process", "metrics",
                "recovery_ledger", "freshness", "anomalies"):
        if key not in report:
            fail(f"report missing {key!r}")
    roles = {t["role"] for t in report["topology"]}
    if not ROLES_EXPECTED <= roles:
        fail(f"topology roles {sorted(roles)} missing "
             f"{sorted(ROLES_EXPECTED - roles)}")
    mt = report["merged_trace"]
    if not ROLES_EXPECTED <= set(mt["roles"]):
        fail(f"merged timeline lanes {mt['roles']} missing roles")
    print(f"fleet_smoke: report ok ({len(report['topology'])} processes, "
          f"{mt['spans']} merged spans)")

    # -- cross-process trace-id join: online publish -> serving apply ------
    joins = mt.get("cross_process_joins") or []
    cross = [j for j in joins
             if {"online", "serving"} <= set(j["roles"])]
    if not cross:
        fail(f"no online<->serving cross-process trace-id join in the "
             f"merged timeline (joins: {joins[:5]})")
    # The joined flow must include the publish->patch pair, visible as
    # spans on BOTH sides of the HTTP boundary in the merged doc.
    with open(merged_path) as f:
        merged_events = json.load(f)["traceEvents"]
    join_ids = {j["trace_id"] for j in cross}
    names_by_id: dict = {}
    for e in merged_events:
        tid = (e.get("args") or {}).get("trace_id")
        if tid in join_ids:
            names_by_id.setdefault(tid, set()).add(e["name"])
    if not any({"online.publish", "serve.patch"} <= names
               for names in names_by_id.values()):
        fail(f"joined flows lack the publish->patch span pair: "
             f"{ {k: sorted(v) for k, v in names_by_id.items()} }")
    print(f"fleet_smoke: {len(cross)} cross-process join(s), "
          "publish->patch flow visible")

    # -- per-process critical paths ----------------------------------------
    for key, pp in report["per_process"].items():
        if not pp.get("critical_path"):
            fail(f"per-process report {key} has no critical path")

    # -- anomaly scan: quiet on the clean run ------------------------------
    if report["anomalies"]["n_anomalies"] != 0:
        fail(f"clean run reported anomalies: {report['anomalies']}")
    print("fleet_smoke: clean run — zero anomalies")

    # -- inject a latency level shift into the serving metrics JSONL -------
    metrics_jsonl = os.path.join(serve_logs, "serving-metrics.jsonl")
    with open(metrics_jsonl) as f:
        rows = [json.loads(x) for x in f if x.strip()]
    if not rows:
        fail(f"{metrics_jsonl}: no metrics history rows")
    base = rows[-1]
    p50 = base["latency"]["p50_ms"] or 1.0
    with open(metrics_jsonl, "a") as f:
        # Pad the clean history first so the detector has full context,
        # then the regression: a sustained 8x latency level shift.
        for _ in range(12):
            f.write(json.dumps(base) + "\n")
        for _ in range(6):
            bad = json.loads(json.dumps(base))
            bad["latency"]["p50_ms"] = p50 * 8.0
            bad["latency"]["p95_ms"] = (base["latency"]["p95_ms"]
                                        or p50) * 8.0
            f.write(json.dumps(bad) + "\n")

    report2, _ = generate("injected")
    an = report2["anomalies"]
    if an["n_anomalies"] < 1:
        fail(f"injected latency regression NOT flagged: {an}")
    flagged = [s for s in an["series"] if s["anomalies"]]
    if not any("latency" in s["metric"]
               and s["file"].endswith("serving-metrics.jsonl")
               for s in flagged):
        fail(f"anomalies flagged on the wrong series: "
             f"{[(s['file'], s['metric']) for s in flagged]}")
    print(f"fleet_smoke: injected regression flagged "
          f"({an['n_anomalies']} anomalous points on "
          f"{flagged[0]['metric']})")
    print("fleet_smoke: OK")


if __name__ == "__main__":
    main()
