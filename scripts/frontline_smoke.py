"""CI chaos drill for the multi-process serving front line
(docs/serving.md §"Front line").

A REAL multi-process drill over the worker↔scorer topology:

1. the training driver fits the base model (role ``training``);
2. ONE serving driver boots in front-line mode (``--workers 2``): the
   driver process owns the device + micro-batcher (role ``serving``),
   two spawned jax-free async workers (role ``frontend``) own the
   public port via SO_REUSEPORT and feed the scorer over shared-memory
   rings;
3. a live-load thread scores continuously through the public port for
   the whole drill;
4. chaos #1 — one WORKER is SIGKILLed: the surviving worker must keep
   serving (successes during the kill window), ``/healthz`` must report
   the dead worker as a degraded reason, and the supervisor must
   restart it (journaled, new pid, back to ``live``);
5. chaos #2 — the SCORER process is SIGKILLed (device loss takes the
   whole device-owning process): the orphaned workers must notice and
   exit (no zombie REUSEPORT squatters answering 503 forever), a
   restarted driver over the same ``--output-dir`` must journal the
   recovery and come back serving, and the live load must succeed again
   after the window;
6. the books are audited: the recovery journal holds worker-exit AND
   worker-joined rows spanning both scorer incarnations, and the fleet
   report renders BOTH roles (serving + frontend) with a registry shard
   per worker process.

Run by ci.sh (front-line smoke stage); exits non-zero with a named
failure.
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
import threading
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
N_WORKERS = 2


def fail(msg: str) -> None:
    print(f"frontline_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def write_train_data(path: str, rows_per_user: int = 12) -> None:
    from photon_tpu.io.avro import write_container

    rng = np.random.default_rng(31)
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


def score_once(host, port, i, timeout=30):
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    conn.request("POST", "/score", body=json.dumps({
        "features": [{"name": "g", "term": "0", "value": 1.0}],
        "entities": {"userId": f"user{i % N_USERS}"},
    }).encode(), headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    resp.read()
    conn.close()
    return resp.status


def wait_healthy(host, port, deadline_s=120.0, name="front line"):
    t0 = time.monotonic()
    last = None
    while time.monotonic() - t0 < deadline_s:
        try:
            status, body = get_json(host, port, "/healthz", timeout=5)
            last = body
            if status == 200 and body.get("status") == "ok":
                return body
        except (OSError, ValueError):
            pass
        time.sleep(0.25)
    fail(f"{name} never became healthy on {host}:{port} (last: {last})")


def read_worker_table(path):
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def journal_rows(path):
    try:
        with open(path) as f:
            return [json.loads(x) for x in f if x.strip()]
    except OSError:
        return []


class LiveLoad(threading.Thread):
    """Continuous scoring against the public port; counts per-second
    outcomes so kill windows are auditable after the fact."""

    def __init__(self, host, port):
        super().__init__(daemon=True)
        self.host, self.port = host, port
        self.ok = 0
        self.errors = 0
        self.stop_flag = threading.Event()
        self.lock = threading.Lock()

    def run(self):
        i = 0
        while not self.stop_flag.is_set():
            try:
                status = score_once(self.host, self.port, i, timeout=5)
                with self.lock:
                    if status == 200:
                        self.ok += 1
                    else:
                        self.errors += 1
            except OSError:
                with self.lock:
                    self.errors += 1
                time.sleep(0.05)
            i += 1

    def counts(self):
        with self.lock:
            return self.ok, self.errors


def wait_ok_progress(load, n, deadline_s, tag):
    """Wait until the live load banks n MORE successes."""
    ok0, _ = load.counts()
    t0 = time.monotonic()
    while time.monotonic() - t0 < deadline_s:
        ok, _ = load.counts()
        if ok - ok0 >= n:
            return
        time.sleep(0.1)
    ok, err = load.counts()
    fail(f"live load stalled during {tag}: +{ok - ok0}/{n} successes "
         f"in {deadline_s}s (totals ok={ok} errors={err})")


def main() -> None:
    td = tempfile.mkdtemp(prefix="frontline-smoke-")
    telemetry = os.path.join(td, "telemetry")
    train = os.path.join(td, "train.avro")
    out = os.path.join(td, "out")
    serve_out = os.path.join(td, "serve")
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
    proc = subprocess.run([
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
    ], env=env, timeout=600,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    if proc.returncode != 0:
        fail("training driver exited "
             f"{proc.returncode}:\n"
             f"{proc.stdout.decode('utf-8', 'replace')[-3000:]}")
    model_dir = os.path.join(out, "best")
    print("frontline_smoke: base model trained")

    host = "127.0.0.1"
    port = free_port()
    worker_table_path = os.path.join(serve_out, "frontline",
                                     "frontline-workers.json")
    journal_path = os.path.join(serve_out, "recovery.jsonl")

    def start_scorer():
        return subprocess.Popen([
            py, "-m", "photon_tpu.cli.serving_driver",
            "--model-dir", model_dir,
            "--host", host, "--port", str(port),
            "--workers", str(N_WORKERS),
            "--autotune",
            "--max-batch", "8", "--max-wait-ms", "1",
            "--cache-entities", "16", "--max-row-nnz", "16",
            "--output-dir", serve_out,
            "--metrics-interval", "0.5",
            "--backend-policy", "cpu-only",
            "--telemetry-dir", telemetry,
        ], env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)

    scorer = start_scorer()
    load = None
    try:
        body = wait_healthy(host, port)
        if body.get("role") != "frontend":
            fail(f"/healthz answered by role {body.get('role')!r}, "
                 "expected a front-end worker")
        workers = {w["worker_id"]: w for w in body.get("workers", [])}
        if len(workers) != N_WORKERS:
            fail(f"expected {N_WORKERS} workers in /healthz, got "
                 f"{sorted(workers)}")
        print(f"frontline_smoke: front line healthy on :{port} "
              f"({N_WORKERS} workers, scorer pid {scorer.pid})")

        load = LiveLoad(host, port)
        load.start()
        wait_ok_progress(load, 10, 30.0, "warmup")

        # ---- chaos #1: SIGKILL one worker --------------------------------
        table = read_worker_table(worker_table_path)
        if not table:
            fail(f"worker table missing at {worker_table_path}")
        victim = table["workers"][0]
        os.kill(victim["pid"], signal.SIGKILL)
        print(f"frontline_smoke: killed worker {victim['worker_id']} "
              f"(pid {victim['pid']})")

        # The survivor keeps the port: successes must keep banking DURING
        # the restart window (python startup is seconds on this rig).
        wait_ok_progress(load, 5, 30.0, "worker kill window")

        # /healthz must surface the dead worker as a degraded reason
        # while it is down (the restart window is seconds wide; poll
        # fast and accept that a very fast restart races this check).
        saw_degraded = None
        t0 = time.monotonic()
        while time.monotonic() - t0 < 15.0:
            try:
                _, h = get_json(host, port, "/healthz", timeout=5)
            except (OSError, ValueError):
                time.sleep(0.05)
                continue
            reasons = [d for d in h.get("degraded", [])
                       if d.startswith("frontline_worker_")]
            states = {w["worker_id"]: w for w in h.get("workers", [])}
            if reasons:
                saw_degraded = reasons
            dead = states.get(victim["worker_id"], {})
            if (dead.get("restarts", 0) >= 1
                    and dead.get("state") == "live"):
                break
            time.sleep(0.05)
        else:
            fail("worker was never restarted (table: "
                 f"{read_worker_table(worker_table_path)})")
        if saw_degraded is None:
            print("frontline_smoke: warn: restart raced the degraded "
                  "/healthz poll (restart faster than poll interval)")
        else:
            print("frontline_smoke: /healthz degraded during window: "
                  f"{saw_degraded}")
        table = read_worker_table(worker_table_path)
        new_pid = [w for w in table["workers"]
                   if w["worker_id"] == victim["worker_id"]][0]["pid"]
        if new_pid == victim["pid"]:
            fail("worker table still shows the killed pid")
        print(f"frontline_smoke: worker {victim['worker_id']} restarted "
              f"(pid {victim['pid']} -> {new_pid})")
        exits = [r for r in journal_rows(journal_path)
                 if r.get("event") == "frontline_worker_exit"]
        if not exits:
            fail("worker death not journaled in recovery.jsonl")
        wait_ok_progress(load, 10, 30.0, "post-worker-restart")

        # ---- chaos #2: scorer device loss --------------------------------
        # Device loss takes the whole device-owning process; the workers
        # must notice the orphaning and exit rather than squat the
        # REUSEPORT group answering 503s next to the replacement's
        # workers.
        joined_before = len([r for r in journal_rows(journal_path)
                             if r.get("event") == "frontline_worker_joined"])
        table = read_worker_table(worker_table_path)
        old_pids = [w["pid"] for w in table["workers"]]
        os.kill(scorer.pid, signal.SIGKILL)
        scorer.wait(timeout=30)
        print(f"frontline_smoke: killed scorer (pid {scorer.pid})")
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            alive = []
            for pid in old_pids:
                try:
                    os.kill(pid, 0)
                    alive.append(pid)
                except ProcessLookupError:
                    pass
            if not alive:
                break
            time.sleep(0.25)
        else:
            fail(f"orphaned workers still alive after scorer death: "
                 f"{alive}")
        print("frontline_smoke: orphaned workers exited")

        scorer = start_scorer()
        wait_healthy(host, port, name="restarted front line")
        wait_ok_progress(load, 10, 60.0, "post-scorer-restart")
        joined_after = len([r for r in journal_rows(journal_path)
                            if r.get("event") == "frontline_worker_joined"])
        if joined_after <= joined_before:
            fail("restarted scorer journaled no worker joins "
                 f"({joined_before} -> {joined_after})")
        print(f"frontline_smoke: recovery journaled "
              f"({joined_before} -> {joined_after} worker joins, "
              f"{len(exits)} worker exit rows)")

        load.stop_flag.set()
        load.join(timeout=10)
        ok, errors = load.counts()
        print(f"frontline_smoke: live load totals: ok={ok} "
              f"errors={errors} (errors expected only in kill windows)")
        if ok < 50:
            fail(f"live load banked only {ok} successes over the drill")

        # ---- the books: fleet report sees every process -------------------
        # Stop the box FIRST: telemetry shards (trace + registry, both
        # roles) flush on graceful exit, and the report must see the
        # scorer's shard from the surviving incarnation.
        scorer.send_signal(signal.SIGTERM)
        scorer.wait(timeout=60)
        from photon_tpu.obs.analysis.report import build_report

        frontend_shards = [f for f in os.listdir(telemetry)
                           if f.startswith("registry.frontend.")]
        if len(frontend_shards) < N_WORKERS:
            fail(f"expected >= {N_WORKERS} frontend registry shards, "
                 f"got {frontend_shards}")
        report = build_report(telemetry)
        roles = {t["role"] for t in report.get("topology", [])}
        if not {"serving", "frontend"} <= roles:
            fail(f"fleet report topology roles {sorted(roles)} missing "
                 "serving/frontend")
        print(f"frontline_smoke: fleet report roles {sorted(roles)}, "
              f"{len(frontend_shards)} frontend registry shards")
        print("frontline_smoke: PASS")
    finally:
        if load is not None:
            load.stop_flag.set()
        if scorer.poll() is None:
            scorer.send_signal(signal.SIGTERM)
            try:
                scorer.wait(timeout=20)
            except subprocess.TimeoutExpired:
                scorer.kill()


if __name__ == "__main__":
    main()
