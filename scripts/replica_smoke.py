"""CI chaos drill for the replicated serving tier (docs/serving.md
§"Replication").

A REAL multi-process drill over the durable delta log + router:

1. the training driver fits the base model (role ``training``);
2. THREE serving drivers boot as replicas (``--delta-log``,
   ``--replica-id r0/r1/r2``), each tailing the log with its own cursor;
3. the router driver fronts them, health-checked and staleness-weighted;
4. the online training driver publishes deltas into the log (write once,
   fan out by tailing) — run in two waves;
5. between the waves replica ``r2`` is SIGKILLed. The router must keep
   serving with ZERO errors through the kill window, the second delta
   wave lands while r2 is down, and a restarted r2 (same replica id →
   same cursor) must rejoin and CONVERGE to the fleet watermark.

Then the books are audited: every replica's recovery journal must show
each published delta applied EXACTLY once (across both of r2's
incarnations), and the fleet report must render the full
router→replica→trainer topology with >= 1 online-publish → replica-apply
cross-process trace join.

Run by ci.sh (replica smoke stage); exits non-zero with a named failure.
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

from photon_tpu.replication import log_next_seq  # noqa: E402

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
REPLICA_IDS = ("r0", "r1", "r2")
ROLES_EXPECTED = {"training", "online", "replica", "router"}


def fail(msg: str) -> None:
    print(f"replica_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def write_train_data(path: str, rows_per_user: int = 12) -> None:
    from photon_tpu.io.avro import write_container

    rng = np.random.default_rng(23)
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


def append_event_wave(path: str, n: int, value: float) -> None:
    from photon_tpu.online import OnlineEvent, append_events

    append_events(path, [
        OnlineEvent(
            entities={"userId": f"user{i % N_USERS}"},
            features=[{"name": "g", "term": str(j), "value": value}
                      for j in range(3)],
            label=1.0,
        )
        for i in range(n)
    ])


def run_child(argv, env, timeout_s=600, name="child"):
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


def get_json(host, port, path, timeout=10):
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    conn.request("GET", path)
    resp = conn.getresponse()
    body = json.loads(resp.read())
    conn.close()
    return resp.status, body


def wait_healthy(host, port, deadline_s=120.0, name="process"):
    t0 = time.monotonic()
    last = None
    while time.monotonic() - t0 < deadline_s:
        try:
            status, body = get_json(host, port, "/healthz", timeout=5)
            last = body
            if status == 200:
                return body
        except OSError:
            pass
        time.sleep(0.25)
    fail(f"{name} never became healthy on {host}:{port} (last: {last})")


def score_burst(host, port, n, tag):
    """n /score requests through the router; every one must succeed."""
    ok = 0
    for i in range(n):
        conn = http.client.HTTPConnection(host, port, timeout=30)
        conn.request("POST", "/score", body=json.dumps({
            "features": [{"name": "g", "term": "0", "value": 1.0}],
            "entities": {"userId": f"user{i % N_USERS}"},
        }).encode(), headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        body = resp.read()
        conn.close()
        if resp.status != 200:
            fail(f"/score via router returned {resp.status} during "
                 f"{tag} (request {i + 1}/{n}): "
                 f"{body.decode('utf-8', 'replace')[:300]}")
        ok += 1
    print(f"replica_smoke: {ok}/{n} scores ok through router ({tag})")


def journal_rows(path):
    try:
        with open(path) as f:
            return [json.loads(x) for x in f if x.strip()]
    except OSError:
        return []


def main() -> None:
    td = tempfile.mkdtemp(prefix="replica-smoke-")
    telemetry = os.path.join(td, "telemetry")
    train = os.path.join(td, "train.avro")
    out = os.path.join(td, "out")
    events_path = os.path.join(td, "events.jsonl")
    delta_log = os.path.join(td, "delta-log.jsonl")
    write_train_data(train)

    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": os.pathsep.join(
            [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]
            + ([os.environ["PYTHONPATH"]]
               if os.environ.get("PYTHONPATH") else [])),
    }
    py = sys.executable

    # ---- the trainer: base model ----------------------------------------
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
    model_dir = os.path.join(out, "best")
    print("replica_smoke: base model trained")

    host = "127.0.0.1"
    replicas = {}     # rid -> {"port", "proc", "out"}

    def start_replica(rid):
        port = replicas.get(rid, {}).get("port") or free_port()
        rout = os.path.join(td, f"replica_{rid}")
        proc = subprocess.Popen([
            py, "-m", "photon_tpu.cli.serving_driver",
            "--model-dir", model_dir,
            "--host", host, "--port", str(port),
            "--max-batch", "8", "--max-wait-ms", "1",
            "--cache-entities", "16", "--max-row-nnz", "16",
            "--output-dir", rout,
            "--metrics-interval", "0.5",
            "--delta-log", delta_log,
            "--replica-id", rid,
            "--backend-policy", "cpu-only",
            "--telemetry-dir", telemetry,
        ], env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        replicas[rid] = {"port": port, "proc": proc, "out": rout}
        return proc

    router_proc = None
    try:
        for rid in REPLICA_IDS:
            start_replica(rid)
        for rid in REPLICA_IDS:
            wait_healthy(host, replicas[rid]["port"],
                         name=f"replica {rid}")
        print(f"replica_smoke: {len(REPLICA_IDS)} replicas healthy")

        # ---- the router ---------------------------------------------------
        router_port = free_port()
        router_proc = subprocess.Popen([
            py, "-m", "photon_tpu.cli.router_driver",
            *sum((["--replica", f"http://{host}:{replicas[rid]['port']}"]
                  for rid in REPLICA_IDS), []),
            "--host", host, "--port", str(router_port),
            "--health-interval", "0.25",
            "--retries", "2",
            "--output-dir", os.path.join(td, "router_out"),
            "--telemetry-dir", telemetry,
        ], env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        health = wait_healthy(host, router_port, name="router")
        if health.get("routable", 0) < 3:
            # The first sweep may predate a replica; give it one interval.
            time.sleep(0.6)
            _, health = get_json(host, router_port, "/healthz")
        if health.get("routable", 0) < 3:
            fail(f"router sees {health.get('routable')} routable "
                 f"replicas, want 3: {health}")
        print(f"replica_smoke: router healthy on :{router_port}, "
              "3 routable replicas")

        score_burst(host, router_port, 12, "baseline")

        # ---- delta wave 1: online trainer -> delta log --------------------
        append_event_wave(events_path, n=16, value=1.5)
        run_child([
            py, "-m", "photon_tpu.cli.online_training_driver",
            "--model-dir", model_dir,
            "--events", events_path,
            "--delta-log", delta_log,
            "--output-dir", os.path.join(td, "online_out"),
            "--window", "16", "--max-event-nnz", "8",
            "--refresh-batch", "2", "--cadence-s", "0",
            "--incremental-weight", "0.5", "--max-iter", "15",
            "--backend-policy", "cpu-only",
            "--telemetry-dir", telemetry,
        ], env, name="online driver (wave 1)")
        head1 = log_next_seq(delta_log)
        if head1 < 2:       # base marker + >= 1 delta
            fail(f"delta wave 1 published nothing (log head {head1})")
        print(f"replica_smoke: wave 1 published (log head {head1})")

        def watermarks(ids):
            marks = {}
            for rid in ids:
                _, h = get_json(host, replicas[rid]["port"], "/healthz")
                marks[rid] = (h.get("replication") or {}).get(
                    "seq_watermark")
            return marks

        def wait_converged(ids, deadline_s=60.0):
            target = log_next_seq(delta_log) - 1
            t0 = time.monotonic()
            while time.monotonic() - t0 < deadline_s:
                marks = watermarks(ids)
                if all(m == target for m in marks.values()):
                    return marks
                time.sleep(0.2)
            fail(f"replicas never converged to log watermark {target}: "
                 f"{watermarks(ids)}")

        wait_converged(REPLICA_IDS)
        print(f"replica_smoke: all replicas converged @ {head1 - 1}")

        # ---- the chaos: SIGKILL r2 mid-stream -----------------------------
        victim = replicas["r2"]["proc"]
        victim.kill()
        victim.wait(timeout=30)
        print("replica_smoke: r2 SIGKILLed")

        # The kill window: the router must absorb the corpse (connect
        # failures retry on a live replica; the health sweep drains it)
        # with ZERO client-visible errors.
        score_burst(host, router_port, 24, "kill window")

        # ---- delta wave 2 lands while r2 is down --------------------------
        append_event_wave(events_path, n=16, value=0.5)
        run_child([
            py, "-m", "photon_tpu.cli.online_training_driver",
            "--model-dir", model_dir,
            "--events", events_path,
            "--delta-log", delta_log,
            "--output-dir", os.path.join(td, "online_out"),
            "--window", "16", "--max-event-nnz", "8",
            "--refresh-batch", "2", "--cadence-s", "0",
            "--incremental-weight", "0.5", "--max-iter", "15",
            "--backend-policy", "cpu-only",
            "--telemetry-dir", telemetry,
        ], env, name="online driver (wave 2)")
        head2 = log_next_seq(delta_log)
        if head2 <= head1:
            fail(f"delta wave 2 published nothing (head {head1}->{head2})")
        marks = watermarks(("r0", "r1"))
        print(f"replica_smoke: wave 2 published (head {head2}); "
              f"live replicas at {marks}")

        # ---- rejoin-and-converge: restart r2, same identity ---------------
        start_replica("r2")
        wait_healthy(host, replicas["r2"]["port"], name="rejoined r2")
        wait_converged(REPLICA_IDS)
        print(f"replica_smoke: r2 rejoined and converged @ {head2 - 1}")
        score_burst(host, router_port, 12, "post-rejoin")

        # ---- coefficient equality: the rejoined replica must SERVE the
        # same answers, not just report the same watermark. (A replica
        # that resumed past its backlog without rebuilding state would
        # pass the seq audit while serving base-model coefficients for
        # every entity patched before the kill.)
        def replica_scores(rid):
            scores = {}
            for u in range(N_USERS):
                conn = http.client.HTTPConnection(
                    host, replicas[rid]["port"], timeout=30)
                conn.request("POST", "/score", body=json.dumps({
                    "features": [{"name": "g", "term": "0", "value": 1.0}],
                    "entities": {"userId": f"user{u}"},
                }).encode(), headers={"Content-Type": "application/json"})
                resp = conn.getresponse()
                body = resp.read()
                conn.close()
                if resp.status != 200:
                    fail(f"direct /score on {rid} returned {resp.status}: "
                         f"{body.decode('utf-8', 'replace')[:300]}")
                scores[f"user{u}"] = json.loads(body)["score"]
            return scores

        baseline_scores = replica_scores("r0")
        for rid in ("r1", "r2"):
            other = replica_scores(rid)
            for user, s in baseline_scores.items():
                if abs(other[user] - s) > 1e-6:
                    fail(f"coefficient divergence after rejoin: {rid} "
                         f"scores {user}={other[user]!r} vs r0's {s!r} "
                         "(same watermark, different state)")
        print(f"replica_smoke: post-rejoin coefficient equality ok "
              f"({N_USERS} entities x {len(REPLICA_IDS)} replicas)")

        # Router books: every routed request succeeded.
        _, rm = get_json(host, router_port, "/metrics")
        outcomes = rm["metrics"].get("router_requests_total") or {}
        bad = {k: v for k, v in outcomes.items() if k != "ok"}
        if bad:
            fail(f"router recorded non-ok outcomes: {outcomes}")
    finally:
        for rid in REPLICA_IDS:
            proc = replicas.get(rid, {}).get("proc")
            if proc is not None and proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
        if router_proc is not None and router_proc.poll() is None:
            router_proc.send_signal(signal.SIGTERM)
        for rid in REPLICA_IDS:
            proc = replicas.get(rid, {}).get("proc")
            if proc is not None:
                try:
                    proc.wait(timeout=60)
                except subprocess.TimeoutExpired:
                    proc.kill()
                    fail(f"replica {rid} ignored SIGTERM for 60s")
        if router_proc is not None:
            try:
                router_proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                router_proc.kill()
                fail("router ignored SIGTERM for 60s")
    print("replica_smoke: fleet stopped cleanly")

    # ---- exactly-once audit: the per-apply journal rows ------------------
    n_deltas = log_next_seq(delta_log) - 1   # seq 0 is the base marker
    expected = list(range(1, n_deltas + 1))
    for rid in REPLICA_IDS:
        rows = journal_rows(
            os.path.join(replicas[rid]["out"], "recovery.jsonl"))
        applied = sorted(r["seq"] for r in rows
                         if r["event"] == "replica_delta_applied")
        if applied != expected:
            fail(f"{rid}: exactly-once audit failed: applied {applied}, "
                 f"expected {expected} (kill/rejoin must not double- or "
                 f"skip-apply)")
        joins = [r for r in rows if r["event"] == "replica_joined"]
        want = 2 if rid == "r2" else 1
        if len(joins) != want:
            fail(f"{rid}: expected {want} replica_joined row(s), "
                 f"got {len(joins)}")
        # r2's second incarnation must have REBUILT its in-memory state:
        # every wave-1 delta (journaled as applied by the first
        # incarnation) re-applied as a replay, never double-counted in
        # the applied audit above.
        replayed = sorted({r["seq"] for r in rows
                           if r["event"] == "replica_delta_replayed"})
        want_replayed = list(range(1, head1)) if rid == "r2" else []
        if replayed != want_replayed:
            fail(f"{rid}: replay audit failed: replayed {replayed}, "
                 f"expected {want_replayed} (boot must rebuild the "
                 "overlay the kill destroyed)")
    print(f"replica_smoke: exactly-once audit ok "
          f"({n_deltas} deltas x {len(REPLICA_IDS)} replicas, "
          "r2 across 2 incarnations)")

    # ---- the operator path: fleet report over the run dir ----------------
    report_path = os.path.join(td, "report.json")
    merged_path = os.path.join(td, "merged.json")
    run_child([
        py, "-m", "photon_tpu.obs.analysis", "report", td,
        "--json", report_path, "--merged-trace", merged_path,
    ], env, name="report CLI")
    with open(report_path) as f:
        report = json.load(f)
    roles = {t["role"] for t in report.get("topology") or []}
    if not ROLES_EXPECTED <= roles:
        fail(f"topology roles {sorted(roles)} missing "
             f"{sorted(ROLES_EXPECTED - roles)}")
    n_replica_procs = sum(1 for t in report["topology"]
                          if t["role"] == "replica")
    # r2's FIRST incarnation died by SIGKILL — no shard, by design. The
    # surviving fleet is r0, r1, and r2's second incarnation.
    if n_replica_procs < 3:
        fail(f"expected >= 3 replica processes in topology, "
             f"got {n_replica_procs}")
    mt = report.get("merged_trace") or {}
    joins = mt.get("cross_process_joins") or []
    cross = [j for j in joins
             if {"online", "replica"} <= set(j["roles"])]
    if not cross:
        fail(f"no online->replica publish/apply trace join in the merged "
             f"timeline (joins: {joins[:5]})")
    rep = report.get("replication") or {}
    got_ids = set((rep.get("replicas") or {}).keys())
    if not set(REPLICA_IDS) <= got_ids:
        fail(f"report replication section missing replicas: "
             f"{sorted(got_ids)}")
    if not rep.get("converged"):
        fail(f"report replication section shows divergence: "
             f"{rep.get('seq_watermarks')}")
    print(f"replica_smoke: report ok ({len(report['topology'])} "
          f"processes, {len(cross)} publish->apply join(s), "
          f"replicas {sorted(got_ids)} converged)")
    print("replica_smoke: OK")


if __name__ == "__main__":
    main()
