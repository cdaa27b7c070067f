"""CI chaos drill for the closed-loop control plane (docs/control.md).

A REAL multi-process drill over the canary publication protocol and the
anomaly→action policies:

1. the training driver fits the base model;
2. replica ``r0`` boots tailing the MAIN delta log; a designated canary
   replica boots tailing the canary SIDE-CHANNEL log; a router fronts
   ``r0``;
3. the control driver ticks over the fleet, owning the main log's writer;
4. the online trainer publishes a wave into the canary log
   (``--canary-log``) — the controller soaks it against the reference
   replica and PROMOTES it into the main log, which ``r0`` then tails;
5. a POISONED delta (coefficients driven to ±80, scores saturated away
   from the reference) is appended to the canary log — the controller
   must ROLL IT BACK: swap the canary to the base model, resync the
   promoted mainline deltas, and never let the poison reach the main log;
6. a latency fault plan on a late-joining replica ``r1`` injects a level
   shift into the controller's probe series — the controller must
   mitigate with the PR 12 standby+swap lever (model_version bump).

Then the books are audited: the control ledger must tell the WHOLE story
(soak → promote → rollback → resync → rule → action → outcome), show no
lever reversal inside its cooldown window, ``r0``'s recovery journal must
show ZERO applies of the poisoned wave, and the fleet report must render
a populated "Control" section with the controller in the topology.

Run by ci.sh (control smoke stage); exits non-zero with a named failure.
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

from photon_tpu.online.delta import EntityPatch, ModelDelta  # noqa: E402
from photon_tpu.replication.log import (  # noqa: E402
    DeltaLogWriter,
    iter_log,
    log_next_seq,
)

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
PROBE_USERS = ("user0", "user1")
ROLES_EXPECTED = {"training", "online", "replica", "router", "control"}

# The drill's policy: ONE anomaly rule (the latency level shift) so every
# ledger action is attributable, plus the canary gates. z/min_run are set
# for a 1-core CI box: the injected shift is ~40x the baseline, a GC
# hiccup is not 3 consecutive 8-sigma samples.
POLICY = {
    "tick_s": 0.5,
    "max_actions_per_tick": 4,
    "rules": [{
        "name": "latency_shift", "signal": "probe_latency_ms",
        "kind": "level_shift", "action": "standby_swap",
        "z_threshold": 8.0, "window": 8, "min_history": 4, "min_run": 3,
        "cooldown_s": 30.0, "budget": 2,
    }],
    "canary": {"soak_ticks": 3, "drift_threshold": 0.35,
               "max_probe_latency_ms": 10000.0, "settle_ticks": 12},
    "autoscale": None,
}


def fail(msg: str) -> None:
    print(f"control_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def write_train_data(path: str, rows_per_user: int = 12) -> None:
    from photon_tpu.io.avro import write_container

    rng = np.random.default_rng(29)
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


def post_json(host, port, path, payload, timeout=30):
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    conn.request("POST", path, body=json.dumps(payload).encode(),
                 headers={"Content-Type": "application/json"})
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


def ledger_rows(path):
    try:
        with open(path) as f:
            return [json.loads(x) for x in f if x.strip()]
    except OSError:
        return []


def wait_ledger(path, pred, what, deadline_s=90.0):
    """Poll the control ledger until ``pred(rows)`` is truthy."""
    t0 = time.monotonic()
    while time.monotonic() - t0 < deadline_s:
        rows = ledger_rows(path)
        got = pred(rows)
        if got:
            return rows
        time.sleep(0.3)
    events = [r["event"] for r in ledger_rows(path)]
    fail(f"ledger never showed {what} within {deadline_s:.0f}s "
         f"(events so far: {events[-30:]})")


def probe_rows():
    return [{
        "features": [{"name": "g", "term": str(j), "value": 1.0}
                     for j in range(3)],
        "entities": {"userId": u},
    } for u in PROBE_USERS]


def direct_scores(host, port, name):
    out = {}
    for row in probe_rows():
        status, body = post_json(host, port, "/score", row)
        if status != 200:
            fail(f"direct /score on {name} returned {status}: {body}")
        out[row["entities"]["userId"]] = float(body["score"])
    return out


def main() -> None:
    td = tempfile.mkdtemp(prefix="control-smoke-")
    telemetry = os.path.join(td, "telemetry")
    train = os.path.join(td, "train.avro")
    out = os.path.join(td, "out")
    events_path = os.path.join(td, "events.jsonl")
    main_log = os.path.join(td, "delta-log.jsonl")
    canary_log = os.path.join(td, "delta-log.canary.jsonl")
    control_out = os.path.join(td, "control_out")
    ledger_path = os.path.join(control_out, "control-ledger.jsonl")
    write_train_data(train)

    policy_path = os.path.join(td, "policy.json")
    with open(policy_path, "w") as f:
        json.dump(POLICY, f, indent=2)
    probe_path = os.path.join(td, "probe.json")
    with open(probe_path, "w") as f:
        json.dump(probe_rows(), f)

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
    print("control_smoke: base model trained")

    host = "127.0.0.1"
    procs = {}      # name -> Popen

    def start_replica(rid, port, delta_log, fault_plan=None):
        rout = os.path.join(td, f"replica_{rid}")
        argv = [
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
        ]
        if fault_plan:
            argv += ["--fault-plan", fault_plan]
        procs[rid] = subprocess.Popen(
            argv, env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        return rout

    ports = {"r0": free_port(), "canary": free_port(), "r1": free_port()}
    try:
        r0_out = start_replica("r0", ports["r0"], main_log)
        start_replica("canary", ports["canary"], canary_log)
        for rid in ("r0", "canary"):
            wait_healthy(host, ports[rid], name=f"replica {rid}")
        print("control_smoke: r0 + canary replicas healthy")

        # ---- the router (fronts the traffic-bearing replica only) ---------
        router_port = free_port()
        procs["router"] = subprocess.Popen([
            py, "-m", "photon_tpu.cli.router_driver",
            "--replica", f"http://{host}:{ports['r0']}",
            "--host", host, "--port", str(router_port),
            "--health-interval", "0.25",
            "--output-dir", os.path.join(td, "router_out"),
            "--telemetry-dir", telemetry,
        ], env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        wait_healthy(host, router_port, name="router")
        status, body = post_json(host, router_port, "/score",
                                 probe_rows()[0])
        if status != 200:
            fail(f"baseline /score via router returned {status}: {body}")
        print(f"control_smoke: router healthy on :{router_port}")

        # ---- the controller (r1 is declared but not yet booted: its
        # unreachable-observation rows are part of the drill) ---------------
        procs["control"] = subprocess.Popen([
            py, "-m", "photon_tpu.cli.control_driver",
            "--replica", f"http://{host}:{ports['r0']}",
            "--replica", f"http://{host}:{ports['r1']}",
            "--canary", f"http://{host}:{ports['canary']}",
            "--delta-log", main_log,
            "--canary-log", canary_log,
            "--model-dir", model_dir,
            "--policy", policy_path,
            "--probe", probe_path,
            "--router", f"http://{host}:{router_port}",
            "--output-dir", control_out,
            "--telemetry-dir", telemetry,
        ], env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        wait_ledger(ledger_path,
                    lambda rows: any(r["event"] == "controller_started"
                                     for r in rows),
                    "controller_started")
        # The controller owns the main log: base marker at seq 0.
        if log_next_seq(main_log) != 1:
            fail(f"controller did not anchor the main log "
                 f"(head {log_next_seq(main_log)}, want 1)")
        print("control_smoke: controller ticking, main log anchored")

        # ---- wave A: online trainer -> canary side channel ----------------
        # The wave refreshes user2/user3 — DISJOINT from the probe users,
        # so a legitimate wave's drift on the probe set is exactly 0 and
        # the promote verdict is deterministic. Only the poison (below)
        # touches the probe users.
        from photon_tpu.online import OnlineEvent, append_events

        append_events(events_path, [
            OnlineEvent(
                entities={"userId": f"user{2 + i % 2}"},
                features=[{"name": "g", "term": str(j), "value": 1.0}
                          for j in range(3)],
                label=float(i % 2),
            )
            for i in range(8)
        ])
        run_child([
            py, "-m", "photon_tpu.cli.online_training_driver",
            "--model-dir", model_dir,
            "--events", events_path,
            "--canary-log", canary_log,
            "--output-dir", os.path.join(td, "online_out"),
            "--window", "8", "--max-event-nnz", "8",
            "--refresh-batch", "2", "--cadence-s", "0",
            "--incremental-weight", "0.5", "--max-iter", "10",
            "--backend-policy", "cpu-only",
            "--telemetry-dir", telemetry,
        ], env, name="online driver (wave A)")
        n_good = sum(1 for rec in iter_log(canary_log)
                     if rec.delta is not None)
        if n_good < 1:
            fail(f"wave A published no deltas (canary log head "
                 f"{log_next_seq(canary_log)})")
        print(f"control_smoke: wave A in canary log ({n_good} delta(s))")

        # Promotion: every wave-A delta re-appended to the MAIN log with a
        # fresh mainline seq. (The controller may adjudicate the wave in
        # chunks if it catches the log mid-publication; the total is what
        # the protocol guarantees.)
        def promoted_total(rows):
            return sum(len(r.get("main_seqs") or ())
                       for r in rows if r["event"] == "canary_promote")

        rows = wait_ledger(ledger_path,
                           lambda rows: promoted_total(rows) >= n_good,
                           f"promotion of all {n_good} wave-A delta(s)")
        if any(r["event"] == "canary_rollback" for r in rows):
            fail(f"clean wave A was rolled back: "
                 f"{[r for r in rows if r['event'] == 'canary_rollback']}")
        head_after_promote = log_next_seq(main_log)
        if head_after_promote != 1 + n_good:
            fail(f"main log head {head_after_promote} after promote, "
                 f"want {1 + n_good}")
        print(f"control_smoke: wave A promoted (main log head "
              f"{head_after_promote})")

        # r0 tails the main log and must converge on the promoted wave.
        target = head_after_promote - 1
        t0 = time.monotonic()
        while time.monotonic() - t0 < 60:
            _, h = get_json(host, ports["r0"], "/healthz")
            mark = (h.get("replication") or {}).get("seq_watermark")
            if mark == target:
                break
            time.sleep(0.2)
        else:
            fail(f"r0 never converged to promoted watermark {target}")
        print(f"control_smoke: r0 converged @ {target}")

        # ---- wave B: the poison -------------------------------------------
        # Replace every probe user's coefficient vector with +80 per
        # column: the linear score for a probe row (three 1.0 features)
        # jumps by ~240, so |canary - reference| drift is hundreds of
        # units — deterministically past the 0.35 gate no matter what the
        # base model learned.
        ref_scores = direct_scores(host, ports["r0"], "r0")
        poison = ModelDelta(seq=777, event_horizon=-1, patches={
            "perUser": {
                u: EntityPatch(
                    key=u,
                    cols=np.array([0, 1, 2], np.int32),
                    vals=np.full(3, 80.0, np.float32))
                for u in PROBE_USERS
            }
        })
        with DeltaLogWriter(canary_log) as w:
            w.append(poison, trace_id="poison-wave")
        print(f"control_smoke: poison appended to canary log "
              f"(ref scores {ref_scores})")

        rows = wait_ledger(
            ledger_path,
            lambda rows: any(r["event"] == "canary_rollback" for r in rows),
            "canary_rollback")
        rb = [r for r in rows if r["event"] == "canary_rollback"]
        if len(rb) != 1 or rb[0]["reason"] != "score_drift":
            fail(f"expected exactly one score_drift rollback, got {rb}")
        rows = wait_ledger(
            ledger_path,
            lambda rows: any(r["event"] == "canary_resync" for r in rows),
            "canary_resync")
        resync = next(r for r in rows if r["event"] == "canary_resync")
        if not resync.get("ok") or resync.get("deltas") != n_good:
            fail(f"rollback resync must restore the {n_good} promoted "
                 f"mainline delta(s): {resync}")
        # THE acceptance property: the poison never reached the main log.
        if log_next_seq(main_log) != head_after_promote:
            fail(f"main log advanced past the rollback "
                 f"({log_next_seq(main_log)} != {head_after_promote})")
        print("control_smoke: poison rolled back + canary resynced; "
              "main log untouched")

        # r0's books: every mainline delta applied exactly once, and no
        # trace of the poisoned wave (it only ever existed canary-side).
        r0_rows = ledger_rows(os.path.join(r0_out, "recovery.jsonl"))
        applied = sorted(r["seq"] for r in r0_rows
                         if r["event"] == "replica_delta_applied")
        if applied != list(range(1, n_good + 1)):
            fail(f"r0 applied seqs {applied}, want "
                 f"{list(range(1, n_good + 1))} — the poisoned wave must "
                 "never reach a non-canary replica")
        print(f"control_smoke: r0 journal audit ok ({len(applied)} "
              "applies, zero from the poisoned wave)")

        # ---- the latency drill: fault-planned late joiner r1 --------------
        # The controller probes each replica with 2 rows per tick; after=12
        # gives r1 six clean baseline ticks, then every batch is delayed
        # 0.35s — a ~40x probe-latency level shift at the series edge.
        plan_path = os.path.join(td, "fault-plan.json")
        from photon_tpu.faults import FaultPlan, FaultSpec

        with open(plan_path, "w") as f:
            f.write(FaultPlan(seed=7, specs=[
                FaultSpec(site="serving.batcher_batch",
                          delay_s=0.35, after=12),
            ]).to_json())
        start_replica("r1", ports["r1"], main_log, fault_plan=plan_path)
        h1 = wait_healthy(host, ports["r1"], name="replica r1")
        v_before = h1["model_version"]

        def swapped(rows):
            return [r for r in rows
                    if r["event"] == "action_outcome"
                    and r["action"] == "standby_swap"
                    and r.get("ok")
                    and f":{ports['r1']}" in r["target"]]

        rows = wait_ledger(ledger_path,
                           lambda rows: swapped(rows),
                           "standby_swap mitigation on r1",
                           deadline_s=120.0)
        fired = [r for r in rows if r["event"] == "rule_fired"
                 and r["rule"] == "latency_shift"]
        if not fired:
            fail("standby_swap actuated without a journaled rule_fired")
        t0 = time.monotonic()
        while time.monotonic() - t0 < 30:
            _, h = get_json(host, ports["r1"], "/healthz")
            if h["model_version"] > v_before:
                break
            time.sleep(0.2)
        else:
            fail(f"r1 model_version never bumped past {v_before} "
                 "after the standby_swap mitigation")
        print(f"control_smoke: latency shift mitigated "
              f"(r1 model_version {v_before} -> {h['model_version']})")

        # ---- stop the controller; it must close its own books -------------
        procs["control"].send_signal(signal.SIGTERM)
        try:
            procs["control"].wait(timeout=60)
        except subprocess.TimeoutExpired:
            procs["control"].kill()
            fail("controller ignored SIGTERM for 60s")
        rows = ledger_rows(ledger_path)
        events = {r["event"] for r in rows}
        missing = {
            "controller_started", "canary_soak_begin", "canary_probe",
            "canary_promote", "canary_rollback", "canary_resync",
            "observation", "rule_fired", "action", "action_outcome",
            "controller_stopped",
        } - events
        if missing:
            fail(f"ledger incomplete, missing events: {sorted(missing)}")

        # Convergence, not oscillation: no lever re-fired on the same
        # target inside its cooldown window. (The engine guarantees this
        # structurally; the ledger is the proof an operator can audit.)
        cooldowns = {r["name"]: r["cooldown_s"] for r in POLICY["rules"]}
        last_fire = {}
        for r in rows:
            if r["event"] != "action":
                continue
            key = (r["action"], r["target"])
            cool = cooldowns.get(r.get("rule"), 0.0)
            prev = last_fire.get(key)
            if prev is not None and r["t"] - prev < cool:
                fail(f"lever reversal inside cooldown: {key} re-fired "
                     f"{r['t'] - prev:.1f}s after the last actuation "
                     f"(cooldown {cool}s)")
            last_fire[key] = r["t"]
        print(f"control_smoke: ledger complete ({len(rows)} rows), "
              "no reversal inside cooldown")
    finally:
        for name, proc in procs.items():
            if proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
        for name, proc in procs.items():
            try:
                proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                proc.kill()
                fail(f"{name} ignored SIGTERM for 60s")
    print("control_smoke: fleet stopped cleanly")

    # ---- the operator path: fleet report over the run dir ----------------
    report_path = os.path.join(td, "report.json")
    run_child([
        py, "-m", "photon_tpu.obs.analysis", "report", td,
        "--json", report_path,
    ], env, name="report CLI")
    with open(report_path) as f:
        report = json.load(f)
    roles = {t["role"] for t in report.get("topology") or []}
    if not ROLES_EXPECTED <= roles:
        fail(f"topology roles {sorted(roles)} missing "
             f"{sorted(ROLES_EXPECTED - roles)}")
    ctl = report.get("control")
    if not ctl:
        fail("fleet report has no control section despite a ledger")
    if (ctl["canary"]["promoted"] < 1 or ctl["canary"]["rolled_back"] != 1
            or ctl["canary"]["last_verdict"] not in ("promote", "rollback")):
        fail(f"control section canary summary wrong: {ctl['canary']}")
    if not ctl["actions"].get("standby_swap"):
        fail(f"control section missing the standby_swap mitigation: "
             f"{ctl['actions']}")
    if not ctl["outcomes"].get("ok"):
        fail(f"control section records no successful outcomes: "
             f"{ctl['outcomes']}")
    print(f"control_smoke: report ok (roles {sorted(roles)}, "
          f"canary {ctl['canary']}, actions {ctl['actions']})")
    print("control_smoke: OK")


if __name__ == "__main__":
    main()
