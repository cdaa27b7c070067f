#!/usr/bin/env python3
"""chip_smoke.py — GAME train → score → serve through the CLI drivers on one
TPU chip, held to a NumPy reference. The quickest proof the system still
starts on the chip.

    python chip_smoke.py                  # one chip: train, score, serve
    python chip_smoke.py --chips 4        # four chips: 4-device vs 1-device training only

This process never imports jax: a process that has touched JAX holds the
chip, so every phase is a child that runs one ``photon_tpu.cli`` driver
exactly as ``python -m photon_tpu.cli.<driver>`` would, exits, and only
then does the next start. Data generation and the NumPy reference are
children pinned to the CPU before import. Each chip-owning child asserts
its own ``jax.devices()[0].platform`` after the driver returns.

One JSON object per phase goes to stdout; the last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Any failed, skipped or degraded phase, or a non-TPU backend, is a one-line
reason on stderr and exit 1 with no result line.
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))

# Full width of the fixed effect r02 ran (BASELINE.md config-3 kind):
# 2^18 features (2^18 - 1 named + intercept), 32 nnz/row (31 + intercept);
# rows are the only cut. One per-user random effect on its own shard.
# Validation and test: every user twice, plus a few users the training data
# never held.
REAL = dict(n_users=8192, rows_per_user=16, d_global=(1 << 18) - 1,
            k_global=31, d_head=1024, d_user=8, k_user=4,
            eval_rows_per_user=2, unseen_users=64, unseen_rows=4)
# The tests' rehearsal size (guide on-chip-measurement §2, rehearsal 1).
TINY = dict(n_users=64, rows_per_user=16, d_global=255, k_global=7,
            d_head=32, d_user=4, k_user=2, eval_rows_per_user=4,
            unseen_users=4, unseen_rows=2)

N_SERVE_REQUESTS = 40           # known users + the unseen ones at the end
AUC_MARGIN = 0.05               # validation AUC must exceed 0.5 + this
SCORE_TOL = 1e-4                # |driver - NumPy reference| per row
CONVERGED = ("FUNCTION_VALUES_CONVERGED", "GRADIENT_CONVERGED")

# The four-chip comparison: the same training under two partitionings, on
# the same (plain) sparse formulation, so that nothing but the partitioning
# differs. A solve cut to the same steps on both sides is compared in its
# coefficients; a solve run to convergence is compared in objective values,
# because that is what its stopping rule bounds. The yardstick is
# scripts/sparse_formulation_check.py, two one-device runs that differ in
# summation order alone (PERF.md, PR 22 calls 6 and 6b):
#  - the fixed effect takes the same FOUR_CHIP_FIXED_ITERATIONS L-BFGS steps
#    on both sides (it stops on MAX_ITERATIONS, by construction), so only
#    rounding separates them: COEF_TOL of the largest coefficient (yardstick
#    1.2e-6). Run to convergence, the yardstick's two fits stop 5.9e-3
#    apart, after 65 and 53 iterations: the default tolerance, 1e-7 of the
#    objective, is float32's own resolution;
#  - every user's random-effect solve runs to its own convergence, and all
#    of them must converge on both sides. Their coefficients are printed,
#    not held: the solves stop within one float32 step of their objective
#    (~1e-6 of ~10), which leaves a regularized coefficient loose by ~1e-3
#    and the unregularized intercept of a user whose rows are nearly all one
#    label by more (yardstick 1.5e-3 and 1.9e-3 after the same fixed steps;
#    5.7e-3 and 1.0 after converged ones);
#  - the objective values the driver's own evaluators report after every
#    step, validation LOGISTIC_LOSS (relative) and AUC (absolute), to
#    OBJECTIVE_TOL (yardstick 2.0e-7 and 6.0e-7). One user solved wrongly,
#    its two validation rows off by ~0.5 in loss each, moves the mean over
#    16,640 rows by about 1e-4 of itself.
COEF_TOL = 1e-4
OBJECTIVE_TOL = 1e-5
FOUR_CHIP_FIXED_ITERATIONS = 10

RECORD_SCHEMA = {
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
        {"name": "userFeatures",
         "type": {"type": "array", "items": "FeatureAvro"}},
        {"name": "metadataMap",
         "type": ["null", {"type": "map", "values": "string"}],
         "default": None},
    ],
}


class SmokeFailure(Exception):
    """A phase failed; the message is the one-line reason."""


# ------------------------------------------------------------------ parent


def _emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def _child_env(platform: str, on_chip: bool, extra: dict) -> dict:
    env = dict(os.environ, **extra)
    env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
    if not on_chip or platform == "cpu":
        env["JAX_PLATFORMS"] = "cpu"
    return env


def _spawn(name: str, spec: dict, out: str, platform: str,
           on_chip: bool, env: dict | None = None) -> subprocess.Popen:
    spec_path = os.path.join(out, f"{name}.spec.json")
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    env = _child_env(platform, on_chip, env or {})
    with open(os.path.join(out, f"{name}.log"), "w") as log:
        return subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--child", spec_path],
            stdout=log, stderr=subprocess.STDOUT, env=env, cwd=HERE)


def _log_tail(out: str, name: str, n: int = 12) -> str:
    try:
        with open(os.path.join(out, f"{name}.log")) as f:
            return " | ".join(l.rstrip() for l in f.readlines()[-n:])
    except OSError:
        return ""


def _run_child(name: str, spec: dict, out: str, platform: str,
               on_chip: bool, timeout: float = 900.0,
               env: dict | None = None) -> dict:
    """Run one child to its end; its report, or SmokeFailure."""
    spec = dict(spec, phase=name, platform=platform,
                report=os.path.join(out, f"{name}.report.json"))
    t0 = time.monotonic()
    p = _spawn(name, spec, out, platform, on_chip, env)
    try:
        rc = p.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        _stop(p)
        raise SmokeFailure(f"{name}: no exit within {timeout:.0f}s")
    if rc != 0:
        raise SmokeFailure(
            f"{name}: exit {rc}: {_log_tail(out, name)}")
    with open(spec["report"]) as f:
        report = json.load(f)
    report["phase_seconds"] = round(time.monotonic() - t0, 3)
    return report


def _stop(p: subprocess.Popen, grace: float = 60.0) -> int:
    if p.poll() is None:
        p.send_signal(signal.SIGTERM)
        try:
            p.wait(timeout=grace)
        except subprocess.TimeoutExpired:
            p.kill()
            p.wait()
    return p.returncode


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _http(url: str, body: dict | None = None, timeout: float = 30.0):
    data = None if body is None else json.dumps(body).encode()
    with urllib.request.urlopen(
            urllib.request.Request(url, data=data), timeout=timeout) as r:
        return r.status, r.read().decode()


def _check_device(report: dict, platform: str, count: int) -> dict:
    dev = report["device"]
    if dev["platform"] != platform or dev["count"] != count:
        raise SmokeFailure(
            f"{report['phase']}: ran on {dev}, wanted {count} x {platform}")
    return dev


def _train_argv(paths: dict, out_dir: str, sweeps: int = 2,
                devices: int | None = None,
                fixed_max_iter: int | None = None) -> list:
    fixed = "fixed:type=fixed,shard=global,reg=L2,reg_weights=1"
    if fixed_max_iter is not None:
        fixed += f",max_iter={fixed_max_iter}"
    argv = [
        "--train-data", paths["train"],
        "--validation-data", paths["val"],
        "--output-dir", out_dir,
        "--task", "LOGISTIC_REGRESSION",
        "--feature-shard", "global:features",
        "--feature-shard", "user:userFeatures",
        "--coordinate", fixed,
        "--coordinate",
        "perUser:type=random,re_type=userId,shard=user,reg=L2,reg_weights=1",
        "--evaluators", "AUC", "LOGISTIC_LOSS",
        "--sweeps", str(sweeps),
    ]
    if devices is not None:
        argv += ["--devices", str(devices)]
    return argv


def _check_training(report: dict, out_dir: str, sweeps: int = 2,
                    fixed_cut: bool = False) -> dict:
    """The acceptance the issue states for the train phase, read from the
    driver's own per-step tracker output (metrics.jsonl). Every solve of
    every step must have converged; only where the caller cut the fixed
    effect's iterations (``fixed_cut``) may that step stop on the cut."""
    name = report["phase"]
    with open(os.path.join(out_dir, "metrics.jsonl")) as f:
        steps = [json.loads(l) for l in f if l.strip()]
    if [s["coordinate"] for s in steps] != ["fixed", "perUser"] * sweeps:
        raise SmokeFailure(f"{name}: unexpected step sequence {steps}")
    for s in steps:
        reasons = s.get("reasons") or {}
        allowed = CONVERGED + (("MAX_ITERATIONS",) if fixed_cut
                               and s["coordinate"] == "fixed" else ())
        if not reasons or set(reasons) - set(allowed):
            raise SmokeFailure(
                f"{name}: sweep {s['sweep']} {s['coordinate']} did not "
                f"converge: {reasons}")
    auc_fixed, auc_re = steps[0]["AUC"], steps[1]["AUC"]
    if not (auc_fixed > 0.5 + AUC_MARGIN and auc_re > auc_fixed):
        raise SmokeFailure(
            f"{name}: validation AUC {auc_fixed:.4f} after the first fixed "
            f"step, {auc_re:.4f} after the random-effect step; wanted > "
            f"{0.5 + AUC_MARGIN} and rising")
    with open(os.path.join(out_dir, "photon.log")) as f:
        log = f.read()
    if "pipelined ingest unavailable" in log:
        raise SmokeFailure(f"{name}: fell to the per-record reader")
    return {
        "steps": [
            {k: s[k] for k in ("sweep", "coordinate", "seconds",
                               "iterations", "data_passes", "reasons",
                               "AUC", "LOGISTIC_LOSS")}
            for s in steps
        ],
        "auc_after_first_fixed": auc_fixed,
        "auc_after_random_effect": auc_re,
    }


def _check_formulation(report: dict, *want: str) -> None:
    """The fixed-effect solve's gradient pass (``rmatvec``) was traced on
    one formulation, and that one is among ``want``."""
    traced = report["sparse_op_traces"]
    on = sorted(k for k, ops in traced.items() if "rmatvec" in ops)
    if len(on) != 1 or on[0] not in want:
        raise SmokeFailure(
            f"{report['phase']}: the solve's rmatvec ran on {on or 'nothing'}"
            f", wanted the {' or '.join(want)} formulation only: {traced}")


def _phase_line(report: dict, **extra) -> dict:
    keep = ("phase", "phase_seconds", "driver_seconds", "device", "probe",
            "sparse_op_traces", "compile_cache", "kernel_traces",
            "retraces_after_warmup", "peak_device_bytes", "sharded_bytes",
            "re_buckets")
    return {**{k: report[k] for k in keep if k in report}, **extra}


def _one_chip(sizes: dict, out: str, platform: str, paths: dict) -> dict:
    train_out = os.path.join(out, "train")
    rep = _run_child(
        "train", {"driver": "game_training_driver",
                  "argv": _train_argv(paths, train_out)},
        out, platform, on_chip=True)
    device = _check_device(rep, platform, 1)
    # One device: the default sparse path of the backend, and no other, is
    # what the fixed-effect solve traced: on the chip one of the two table
    # formulations, whichever the build chose from the data (the line's
    # ``sparse_op_traces`` says which), off the chip the plain one. A
    # default that silently is the plain one on a chip fails here.
    _check_formulation(
        rep, *(("window", "fast") if platform == "tpu" else ("plain",)))
    _emit(_phase_line(rep, **_check_training(rep, train_out)))

    score_out = os.path.join(out, "score")
    rep = _run_child(
        "score", {"driver": "game_scoring_driver",
                  "argv": ["--data", paths["test"],
                           "--model-dir", os.path.join(train_out, "best"),
                           "--output-dir", score_out]},
        out, platform, on_chip=True)
    _check_device(rep, platform, 1)
    ref = _run_child(
        "reference", {"paths": paths, "sizes": sizes,
                      "model_dir": os.path.join(train_out, "best"),
                      "scores": os.path.join(score_out, "scores.avro"),
                      "tol": SCORE_TOL},
        out, "cpu", on_chip=False)
    _emit(_phase_line(rep, rows=ref["rows"],
                      max_abs_diff_vs_numpy=ref["max_abs_diff"],
                      reference_seconds=ref["phase_seconds"]))

    _emit(_serve(out, platform, train_out, ref))
    return device


def _serve(out: str, platform: str, train_out: str, ref: dict) -> dict:
    port = _free_port()
    base = f"http://127.0.0.1:{port}"
    spec = {"driver": "serving_driver", "phase": "serve",
            "platform": platform,
            "report": os.path.join(out, "serve.report.json"),
            "argv": ["--model-dir", os.path.join(train_out, "best"),
                     "--port", str(port),
                     "--output-dir", os.path.join(out, "serve")]}
    t0 = time.monotonic()
    p = _spawn("serve", spec, out, platform, on_chip=True)
    try:
        deadline = time.monotonic() + 600
        while True:
            if p.poll() is not None:
                raise SmokeFailure(
                    f"serve: exited {p.returncode} before /healthz: "
                    f"{_log_tail(out, 'serve')}")
            try:
                if _http(base + "/healthz", timeout=2)[0] == 200:
                    break
            except (urllib.error.URLError, OSError):
                pass
            if time.monotonic() > deadline:
                raise SmokeFailure("serve: /healthz not up within 600s")
            time.sleep(0.5)
        ready_s = time.monotonic() - t0
        worst = 0.0
        with open(ref["requests"]) as f:
            requests = json.load(f)
        t_req = time.monotonic()
        for req in requests:
            status, body = _http(base + "/score", req["body"])
            got = json.loads(body)["score"]
            worst = max(worst, abs(got - req["expected"]))
            if status != 200 or not abs(got - req["expected"]) <= SCORE_TOL:
                raise SmokeFailure(
                    f"serve: uid {req['body']['uid']} scored {got}, NumPy "
                    f"reference {req['expected']}")
        req_s = time.monotonic() - t_req
        metrics = json.loads(_http(base + "/metrics")[1])
        if (metrics["requests"] < len(requests) or metrics["errors"]
                or metrics["degraded"]
                or metrics["kernel_retraces_after_warmup"]):
            raise SmokeFailure(f"serve: /metrics reports {metrics}")
    except BaseException:
        _stop(p)
        raise
    rc = _stop(p)
    if rc != 0:
        raise SmokeFailure(
            f"serve: exit {rc} after SIGTERM: {_log_tail(out, 'serve')}")
    with open(spec["report"]) as f:
        rep = json.load(f)
    rep["phase_seconds"] = round(time.monotonic() - t0, 3)
    _check_device(rep, platform, 1)
    return _phase_line(
        rep, requests=len(requests), seconds_to_healthz=round(ready_s, 3),
        request_seconds=round(req_s, 3), max_abs_diff_vs_numpy=worst,
        server_metrics={k: metrics[k] for k in (
            "requests", "errors", "degraded", "latency",
            "coefficient_caches")})


def _four_chips(out: str, platform: str, paths: dict, chips: int) -> dict:
    """Only what exists across chips: the default ``--devices 0`` training
    on every visible device, and the same training on one to compare. The
    mesh path runs the plain sparse formulation (the fast path's tables do
    not shard by rows), so the one-device side is held to it too."""
    outs, device = {}, None
    for name, devices, env in (
            ("train4", None, {}),
            ("train1", 1, {"PHOTON_DISABLE_ACCEL_PATHS": "1"})):
        outs[name] = os.path.join(out, name)
        rep = _run_child(
            name, {"driver": "game_training_driver",
                   "argv": _train_argv(paths, outs[name], 1, devices,
                                       FOUR_CHIP_FIXED_ITERATIONS)},
            out, platform, on_chip=True, env=env)
        dev = _check_device(rep, platform, chips)
        device = device or dev
        _check_formulation(rep, "plain")
        _emit(_phase_line(rep, devices_used=devices or chips,
                          **_check_training(rep, outs[name], sweeps=1,
                                            fixed_cut=True)))
        if name == "train4":
            spread = rep.get("sharded_bytes") or {}
            for kind in ("fixed_effect_features", "random_effect_bucket"):
                per_dev = spread.get(kind) or {}
                if len([b for b in per_dev.values() if b > 0]) != chips:
                    raise SmokeFailure(
                        f"train4: {kind} bytes per device {per_dev}: not "
                        f"spread over {chips} devices")
    cmp_ = _run_child(
        "compare", {"a": outs["train4"], "b": outs["train1"],
                    "coef_tol": COEF_TOL, "objective_tol": OBJECTIVE_TOL},
        out, "cpu", on_chip=False)
    _emit(_phase_line(cmp_, **{k: v for k, v in cmp_.items()
                               if k not in ("phase", "phase_seconds")}))
    return device


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=os.path.join(HERE, "chip_smoke_out"))
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4])
    # Internal, for tests/test_chip_smoke.py only: the tiny rehearsal size
    # on whatever backend JAX_PLATFORMS names.
    ap.add_argument("--rehearse", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--child", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.child:
        with open(args.child) as f:
            return _child(json.load(f))

    asked = os.environ.get("JAX_PLATFORMS", "")
    if args.rehearse:
        platform, sizes = "cpu", TINY
        # As many virtual CPU devices as the chips rehearsed, whatever the
        # caller's XLA_FLAGS forces (the children inherit this).
        flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
        flags.append(
            f"--xla_force_host_platform_device_count={args.chips}")
        os.environ["XLA_FLAGS"] = " ".join(flags)
    else:
        platform, sizes = "tpu", REAL
        if asked and "tpu" not in asked.split(","):
            print(f"chip_smoke: JAX_PLATFORMS={asked} names no TPU; this "
                  "check runs on the chip only", file=sys.stderr)
            return 1
    out = os.path.abspath(args.out)
    os.makedirs(out, exist_ok=True)
    try:
        data = _run_child("data", {"seed": args.seed, "sizes": sizes,
                                   "dir": os.path.join(out, "data")},
                          out, "cpu", on_chip=False)
        _emit({"phase": "data", "phase_seconds": data["phase_seconds"],
               "shapes": data["shapes"], "seed": args.seed})
        if args.chips == 1:
            device = _one_chip(sizes, out, platform, data["paths"])
        else:
            device = _four_chips(out, platform, data["paths"], args.chips)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    _emit({"ok": True, "device": {"platform": device["platform"],
                                  "kind": device["kind"],
                                  "count": device["count"]}})
    return 0


# ---------------------------------------------------------------- children


def _child(spec: dict) -> int:
    phase = spec["phase"]
    kind = spec.get("kind", phase)  # a caller may name its phases itself
    if "driver" in spec:
        report = _child_driver(spec)
    elif kind == "data":
        report = _child_data(spec)
    elif kind == "reference":
        report = _child_reference(spec)
    elif kind == "compare":
        report = _child_compare(spec)
    else:
        raise ValueError(f"unknown phase {phase!r}")
    report["phase"] = phase
    with open(spec["report"], "w") as f:
        json.dump(report, f)
    device = report.get("device")
    if device and device["platform"] != spec["platform"]:
        # Said by the process that owned the device, not inferred outside.
        print(f"chip_smoke: {phase} ran on {device}, not "
              f"{spec['platform']}", file=sys.stderr)
        return 3
    return 0


def _child_driver(spec: dict) -> dict:
    """Run one CLI driver as ``python -m`` would, then — still inside the
    process that owns the chip — name the device and read the counters."""
    import runpy

    from photon_tpu.runtime import compile_store

    compile_store.install_accounting()  # jax.monitoring only; no backend
    module = f"photon_tpu.cli.{spec['driver']}"
    sys.argv = [module] + list(spec["argv"])
    t0 = time.monotonic()
    runpy.run_module(module, run_name="__main__", alter_sys=True)
    seconds = time.monotonic() - t0

    import jax

    from photon_tpu.obs import retrace
    from photon_tpu.obs.metrics import REGISTRY
    from photon_tpu.runtime.backend_guard import guard_snapshot

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    guard = guard_snapshot() or {}
    snap = REGISTRY.snapshot()

    def total(name):
        v = snap.get(name, 0)
        return sum(v.values()) if isinstance(v, dict) else v

    traces = retrace.all_traces()
    stats = dev.memory_stats() or {}
    report = {
        "driver_seconds": round(seconds, 3),
        "device": device,
        "probe": {"seconds": guard.get("backend_init_seconds"),
                  "attempts": guard.get("probe_attempts"),
                  "backend": guard.get("backend"),
                  "policy": guard.get("policy")},
        # {formulation: {op: traces}} of the sparse ops this process really
        # traced into its programs (data/batch.py SparseFeatures).
        "sparse_op_traces": _by_kind(snap.get("sparse_op_traces_total")) or {},
        "compile_cache": {
            "dir": jax.config.jax_compilation_cache_dir,
            "hits": int(total("xla_cache_hits_total")),
            "misses": int(total("xla_cache_misses_total")),
            "xla_compile_seconds": round(
                total("xla_compile_seconds_total"), 3),
            "cache_load_seconds": round(
                total("xla_cache_load_seconds_total"), 3),
            "re_solver_compile_seconds": round(
                total("re_solver_compile_seconds_total"), 3),
        },
        "kernel_traces": traces,
        "retraces_after_warmup": {
            k: retrace.retraces_after_warmup(k) for k in traces},
        "peak_device_bytes": stats.get("peak_bytes_in_use"),
        "sharded_bytes": _by_kind(snap.get("sharded_bytes_per_device")),
        "re_buckets": _re_buckets(),
    }
    return report


def _re_buckets() -> list:
    """What the last fit's ``optim.re_bucket`` spans say of each bucket
    solve: the coordinate's entity column (``re_type``), its shape, the
    solver it was routed to and how that solver solved its Newton systems
    (``solve``). Empty off training."""
    from photon_tpu.obs import recent_trees

    keys = ("re_type", "entities", "padded_rows", "local_dim", "solver",
            "solve", "chunk")
    trees = recent_trees("estimator.fit", last=1)
    return [{k: args.get(k) for k in keys}
            for name, _, _, _, _, args in (trees[-1] if trees else ())
            if name == "optim.re_bucket"]


def _by_kind(series) -> dict | None:
    """The registry's flat snapshot of a two-label series,
    ``{"<first>.<second>": value}`` (labels in name order), as
    ``{first: {second: value}}``."""
    if not isinstance(series, dict):
        return None
    out: dict = {}
    for key, value in series.items():
        first, _, second = key.rpartition(".")
        out.setdefault(first, {})[second] = int(value)
    return out


def _make_rows(rng, sizes, users, wg, wu, bu):
    """Rows for the given users (an int array; -1 = unseen user, who has no
    per-user effect). Global features: half from a popular head, half from
    the whole space; user features on their own shard."""
    import numpy as np

    n, kg, ku = len(users), sizes["k_global"], sizes["k_user"]
    kh = kg // 2
    gi = np.concatenate([
        rng.integers(0, sizes["d_head"], size=(n, kh)),
        rng.integers(0, sizes["d_global"], size=(n, kg - kh)),
    ], axis=1)
    gv = rng.normal(size=(n, kg)) / np.sqrt(kg)
    ui = np.argsort(rng.random((n, sizes["d_user"])), axis=1)[:, :ku]
    uv = rng.normal(size=(n, ku))
    known = users >= 0
    u = np.where(known, users, 0)
    z = (gv * wg[gi]).sum(1) + known * (
        bu[u] + (uv * wu[u[:, None], ui]).sum(1))
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-z))).astype(np.float64)
    return gi, gv, ui, uv, y


def _user_keys(users, unseen_rows: int) -> list:
    """Entity ids per row: ``user<u>``, and for the unseen rows (-1) one
    ``stranger<i>`` per ``unseen_rows`` consecutive rows."""
    keys, strangers = [], 0
    for u in users.tolist():
        if u >= 0:
            keys.append(f"user{u}")
        else:
            keys.append(f"stranger{strangers // unseen_rows}")
            strangers += 1
    return keys


def _records(prefix, keys, gi, gv, ui, uv, y):
    for r in range(len(keys)):
        yield {
            "uid": f"{prefix}{r}",
            "response": float(y[r]),
            "offset": None,
            "weight": None,
            "features": [
                {"name": "g", "term": str(int(j)), "value": float(v)}
                for j, v in zip(gi[r], gv[r])],
            "userFeatures": [
                {"name": "u", "term": str(int(j)), "value": float(v)}
                for j, v in zip(ui[r], uv[r])],
            "metadataMap": {"userId": keys[r]},
        }


def _child_data(spec: dict) -> dict:
    import numpy as np

    from photon_tpu.io.avro import write_container

    s, seed = spec["sizes"], spec["seed"]
    os.makedirs(spec["dir"], exist_ok=True)
    truth = np.random.default_rng([seed, 0])
    wg = truth.normal(size=s["d_global"]) * 0.3
    wg[: s["d_head"]] = truth.normal(size=s["d_head"]) * 1.5
    wu = truth.normal(size=(s["n_users"], s["d_user"])) * 1.0
    bu = truth.normal(size=s["n_users"]) * 2.0

    held_out = np.concatenate([
        np.repeat(np.arange(s["n_users"]), s["eval_rows_per_user"]),
        np.full(s["unseen_users"] * s["unseen_rows"], -1)])
    users = {
        "train": np.repeat(np.arange(s["n_users"]), s["rows_per_user"]),
        "val": held_out, "test": held_out.copy(),
    }
    paths, arrays = {}, {}
    for i, split in enumerate(("train", "val", "test")):
        rng = np.random.default_rng([seed, 1 + i])
        u = users[split]
        if split == "train":
            rng.shuffle(u)
        gi, gv, ui, uv, y = _make_rows(rng, s, u, wg, wu, bu)
        if split == "train":
            # Every named feature occurs, so the index the driver builds
            # from the training data has the full width.
            cover = -(-s["d_global"] // s["k_global"])
            gi[:cover] = (np.arange(cover * s["k_global"])
                          % s["d_global"]).reshape(cover, s["k_global"])
        paths[split] = os.path.join(spec["dir"], f"{split}.avro")
        write_container(paths[split], RECORD_SCHEMA,
                        _records(split[0], _user_keys(u, s["unseen_rows"]),
                                 gi, gv, ui, uv, y))
        arrays[split] = (u, gi, gv, ui, uv)
    u, gi, gv, ui, uv = arrays["test"]
    paths["test_npz"] = os.path.join(spec["dir"], "test.npz")
    np.savez(paths["test_npz"], users=u, gi=gi, gv=gv, ui=ui, uv=uv)
    return {
        "paths": paths,
        "shapes": {
            "fixed_effect_features": s["d_global"] + 1,
            "fixed_effect_nnz_per_row": s["k_global"] + 1,
            "train_rows": len(users["train"]),
            "validation_rows": len(users["val"]),
            "test_rows": len(users["test"]),
            "users": s["n_users"], "rows_per_user": s["rows_per_user"],
            "random_effect_features": s["d_user"] + 1,
            "random_effect_nnz_per_row": s["k_user"] + 1,
            "dtype": "float32",
        },
    }


def _numpy_reference(model_dir: str, npz: str):
    """Score the test rows with NumPy alone from the saved model: sparse
    dot with the fixed coefficients plus the row's user's coefficients
    (none for a user the model never saw)."""
    import numpy as np

    from photon_tpu.index.index_map import MmapIndexMap
    from photon_tpu.io.model_io import default_index_root, load_game_model

    root = default_index_root(model_dir)
    imaps = {s: MmapIndexMap(os.path.join(root, s))
             for s in ("global", "user")}
    model, _ = load_game_model(model_dir, imaps, dtype=np.float64)
    t = np.load(npz)
    users, gi, gv, ui, uv = (t[k] for k in ("users", "gi", "gv", "ui", "uv"))
    w = np.asarray(model["fixed"].model.coefficients.means, np.float64)
    g_col = {j: imaps["global"].get_index("g", str(j))
             for j in np.unique(gi).tolist()}
    u_col = {j: imaps["user"].get_index("u", str(j))
             for j in np.unique(ui).tolist()}
    cols = np.vectorize(g_col.get)(gi)
    # A feature the training data never held has no index (-1) and drops.
    scores = np.where(cols >= 0, gv * w[np.maximum(cols, 0)], 0.0).sum(1)
    scores += w[imaps["global"].intercept_index]
    u_icpt = imaps["user"].intercept_index
    for r in range(len(users)):
        if users[r] < 0:
            continue
        ci, cv = model["perUser"].coefficients_for(f"user{int(users[r])}")
        wu = dict(zip(ci.tolist(), np.asarray(cv, np.float64).tolist()))
        scores[r] += wu.get(u_icpt, 0.0) + sum(
            wu.get(u_col[int(j)], 0.0) * v for j, v in zip(ui[r], uv[r]))
    return users, gi, gv, ui, uv, scores


def _child_reference(spec: dict) -> dict:
    import numpy as np

    from photon_tpu.io.avro import read_records

    users, gi, gv, ui, uv, want = _numpy_reference(
        spec["model_dir"], spec["paths"]["test_npz"])
    recs = read_records(spec["scores"])
    if len(recs) != len(want):
        raise SystemExit(f"scored {len(recs)} rows, test file has {len(want)}")
    got = np.empty(len(want))
    for r in recs:  # uid is "t<row>"
        got[int(r["uid"][1:])] = r["predictionScore"]
    if not np.all(np.isfinite(got)):
        raise SystemExit("non-finite scores")
    diff = float(np.max(np.abs(got - want)))
    if not diff <= spec["tol"]:
        raise SystemExit(
            f"scores differ from the NumPy reference by {diff:.3g} "
            f"(> {spec['tol']}) over {len(want)} rows")
    # Serving requests: the first known-user test rows and, at the end,
    # rows of users the model never saw.
    n_req = min(N_SERVE_REQUESTS, len(want))
    n_unseen = max(1, n_req // 8)
    rows = list(range(n_req - n_unseen)) + list(
        range(len(want) - n_unseen, len(want)))
    keys = _user_keys(users, spec["sizes"]["unseen_rows"])
    requests = [{
        "expected": float(want[r]),
        "body": {
            "uid": f"t{r}",
            "features": [{"name": "g", "term": str(int(j)), "value": float(v)}
                         for j, v in zip(gi[r], gv[r])],
            "userFeatures": [
                {"name": "u", "term": str(int(j)), "value": float(v)}
                for j, v in zip(ui[r], uv[r])],
            "entities": {"userId": keys[r]},
        }} for r in rows]
    req_path = os.path.join(os.path.dirname(spec["report"]), "requests.json")
    with open(req_path, "w") as f:
        json.dump(requests, f)
    return {"rows": len(want), "max_abs_diff": diff, "requests": req_path}


def _child_compare(spec: dict) -> dict:
    """Two trainings of the same data (output directories ``a`` and ``b``)
    held to each other as the note at COEF_TOL sets out. Every figure is
    reported; any over its limit fails."""
    import numpy as np

    from photon_tpu.index.index_map import MmapIndexMap
    from photon_tpu.io.model_io import default_index_root, load_game_model

    loaded, steps, intercepts = [], [], set()
    for d in (spec["a"], spec["b"]):
        best = os.path.join(d, "best")
        root = default_index_root(best)
        imaps = {s: MmapIndexMap(os.path.join(root, s))
                 for s in ("global", "user")}
        loaded.append(load_game_model(best, imaps, dtype=np.float64)[0])
        intercepts.add(imaps["user"].intercept_index)
        with open(os.path.join(d, "metrics.jsonl")) as f:
            steps.append([json.loads(l) for l in f if l.strip()])
    a, b = loaded
    wa, wb = (np.asarray(m["fixed"].model.coefficients.means) for m in (a, b))
    fixed_rel = float(np.max(np.abs(wa - wb)) / np.max(np.abs(wb)))
    (icpt,) = intercepts  # the same data gives both runs the same index
    re_reg, re_icpt, re_scale = 0.0, 0.0, 0.0
    for key in b["perUser"].entity_keys:
        (ia, va), (ib, vb) = (m["perUser"].coefficients_for(key)
                              for m in (a, b))
        da, db = dict(zip(ia.tolist(), va)), dict(zip(ib.tolist(), vb))
        for c in set(da) | set(db):
            diff = float(abs(da.get(c, 0.0) - db.get(c, 0.0)))
            if c == icpt:
                re_icpt = max(re_icpt, diff)
            else:
                re_reg = max(re_reg, diff)
        re_scale = max(re_scale, float(np.max(np.abs(vb))) if len(vb) else 0.0)

    failures, objective = [], []
    if len(steps[0]) != len(steps[1]):
        failures.append(f"{len(steps[0])} steps against {len(steps[1])}")
    for sa, sb in zip(*steps):
        where = f"sweep {sb['sweep']} {sb['coordinate']}"
        ra, rb = sa["reasons"], sb["reasons"]
        if sum(rb.values()) == 1:
            same = ra == rb       # one solve: the same reason
        else:                     # one per entity: as many converged
            same = (sum(ra.values()) == sum(rb.values())
                    and sum(ra.get(k, 0) for k in CONVERGED)
                    == sum(rb.get(k, 0) for k in CONVERGED))
        if not same:
            failures.append(f"{where}: reasons {ra} against {rb}")
        loss = abs(sa["LOGISTIC_LOSS"] / sb["LOGISTIC_LOSS"] - 1.0)
        auc = abs(sa["AUC"] - sb["AUC"])
        objective.append({"sweep": sb["sweep"], "coordinate": sb["coordinate"],
                          "logistic_loss_rel_diff": loss, "auc_diff": auc,
                          "iterations": [sa["iterations"], sb["iterations"]],
                          "data_passes": [sa["data_passes"],
                                          sb["data_passes"]],
                          "reasons": [ra, rb]})
        if not max(loss, auc) <= spec["objective_tol"]:
            failures.append(f"{where}: LOGISTIC_LOSS differs by {loss:.3g} "
                            f"(relative), AUC by {auc:.3g}")
    if not fixed_rel <= spec["coef_tol"]:
        failures.append(f"fixed-effect coefficients differ by {fixed_rel:.3g}"
                        " of the largest")
    report = {"fixed_rel_diff": fixed_rel, "fixed_rel_limit": spec["coef_tol"],
              "random_regularized_abs_diff": re_reg,
              "random_intercept_abs_diff": re_icpt,
              "random_largest_coefficient": re_scale,
              "objective_limit": spec["objective_tol"], "steps": objective}
    if failures:
        raise SystemExit(f"{spec['a']} against {spec['b']}: "
                         + "; ".join(failures) + f" — {json.dumps(report)}")
    return report


if __name__ == "__main__":
    sys.exit(main())
