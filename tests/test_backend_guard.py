"""Backend-failure resilience (photon_tpu/runtime/backend_guard.py +
supervisor.RunSupervisor; docs/robustness.md §"Backend-failure
resilience"): classification, the subprocess probe's hard deadline, the
strict/failover/cpu-only policy ladder, the classified restart supervisor
+ recovery journal, and the PR 6 gate's refusal of failover artifacts.

The probe tests use the ``probe_code`` injection seam (arbitrary child
code), so they run in seconds on any box — no chip, no jax import in the
child.
"""
import json
import os

import numpy as np
import pytest

from photon_tpu.faults import (
    DeviceLostError,
    FaultPlan,
    FaultSpec,
    PreemptionError,
    active_plan,
)
from photon_tpu.obs.metrics import REGISTRY
from photon_tpu.runtime import backend_guard as bg
from photon_tpu.supervisor import (
    RecoveryJournal,
    RestartPolicy,
    RestartsExhausted,
    RunSupervisor,
)


@pytest.fixture(autouse=True)
def _fresh_guard():
    bg.reset_guard()
    yield
    bg.reset_guard()


# ------------------------------------------------------------ classification


@pytest.mark.parametrize("text,cause", [
    # The literal signatures from the repo's own recovery log.
    ("UNAVAILABLE: TPU backend setup/compile error", "init_unavailable"),
    ("RuntimeError: Unable to initialize backend: UNAVAILABLE",
     "init_unavailable"),
    ("probe hung past the 120s PHOTON_BACKEND_INIT_TIMEOUT_S deadline "
     "(wedged device grant?)", "init_unavailable"),
    ("INTERNAL: device was lost mid-collective", "device_lost"),
    ("XlaRuntimeError: DEVICE_LOST: heartbeat missed", "device_lost"),
    ("RESOURCE_EXHAUSTED: out of memory allocating 16G on HBM", "oom"),
    ("XlaCompile failed: unsupported op", "compile_error"),
    ("Mosaic failed to lower kernel", "compile_error"),
    ("ValueError: bad flag", "unknown"),
])
def test_classification_from_text(text, cause):
    assert bg.classify_backend_error(text) == cause


def test_classification_from_exception_types():
    # Types outrank message text: an injected DeviceLostError classifies
    # by what it is even with an unhelpful message.
    assert bg.classify_backend_error(DeviceLostError("boom")) == "device_lost"
    assert bg.classify_backend_error(MemoryError("x")) == "oom"
    assert bg.is_device_lost(DeviceLostError("injected"))
    assert not bg.is_device_lost(RuntimeError("something else"))
    # An init-phase failure that mentions "compile" is still init: the
    # recovery-log tail must never classify as a code bug.
    assert bg.classify_backend_error(
        RuntimeError("UNAVAILABLE: TPU backend setup/compile error")
    ) == "init_unavailable"


def test_timeout_env_knob(monkeypatch):
    monkeypatch.setenv("PHOTON_BACKEND_INIT_TIMEOUT_S", "7.5")
    assert bg.backend_init_timeout_s() == 7.5
    monkeypatch.setenv("PHOTON_BACKEND_INIT_TIMEOUT_S", "not-a-number")
    assert bg.backend_init_timeout_s() == 120.0  # degrade, never disable
    monkeypatch.setenv("PHOTON_BACKEND_INIT_TIMEOUT_S", "-3")
    assert bg.backend_init_timeout_s() == 120.0


# -------------------------------------------------------------------- probe


def test_probe_hang_killed_at_deadline():
    import time

    t0 = time.monotonic()
    r = bg.probe_backend(timeout_s=1.5,
                         probe_code="import time; time.sleep(600)")
    took = time.monotonic() - t0
    assert not r.ok
    assert took < 30.0  # the deadline, not the child's 600s
    assert r.cause == "init_unavailable"
    assert "deadline" in r.reason


def test_probe_classifies_child_failure():
    r = bg.probe_backend(
        timeout_s=30.0,
        probe_code=("import sys; sys.stderr.write('Unable to initialize "
                    "backend: UNAVAILABLE\\n'); sys.exit(1)"))
    assert not r.ok and r.cause == "init_unavailable"
    assert "UNAVAILABLE" in r.reason


def test_probe_success_reports_backend():
    r = bg.probe_backend(timeout_s=30.0,
                         probe_code="print('PHOTON_BACKEND=cpu')")
    assert r.ok and r.backend == "cpu" and r.cause is None


def test_probe_attempts_counted():
    r = bg.probe_backend(timeout_s=30.0, attempts=2,
                         probe_code="import sys; sys.exit(1)")
    assert not r.ok and r.attempts == 2


# ------------------------------------------------------------------ policies


def test_strict_policy_raises_classified():
    with pytest.raises(bg.BackendUnusable) as ei:
        bg.ensure_backend(
            policy="strict", timeout_s=30.0,
            probe_code=("import sys; sys.stderr.write('UNAVAILABLE');"
                        "sys.exit(1)"))
    assert ei.value.cause == "init_unavailable"
    assert "UNAVAILABLE" in str(ei.value)


def test_failover_policy_pins_cpu_and_stamps():
    before = REGISTRY.counter("backend_failovers_total").value(
        cause="init_unavailable")
    snap = bg.ensure_backend(
        policy="failover", timeout_s=30.0,
        probe_code=("import sys; sys.stderr.write('UNAVAILABLE');"
                    "sys.exit(1)"))
    assert snap["backend"] == "cpu"
    assert snap["failover"]["to"] == "cpu"
    assert snap["failover"]["cause"] == "init_unavailable"
    assert bg.guard_snapshot()["failover"] is not None
    assert REGISTRY.counter("backend_failovers_total").value(
        cause="init_unavailable") == before + 1
    import jax

    assert jax.config.jax_platforms == "cpu"


def test_cpu_only_policy_never_probes():
    snap = bg.ensure_backend(policy="cpu-only")
    assert snap == {"policy": "cpu-only", "backend": "cpu",
                    "backend_init_seconds": 0.0, "probe_attempts": 0,
                    "failover": None}


def test_unknown_policy_rejected():
    with pytest.raises(ValueError, match="backend policy"):
        bg.ensure_backend(policy="yolo")


def test_initialized_process_skips_the_subprocess_probe():
    """A process whose jax backend is already live (every test process)
    must not pay a subprocess per driver run — the probe short-circuits
    and the snapshot still records the live backend."""
    import jax.numpy as jnp

    jnp.zeros(1).block_until_ready()  # force backend init
    import time

    t0 = time.monotonic()
    snap = bg.ensure_backend(policy="strict")
    assert time.monotonic() - t0 < 0.5  # no subprocess was spawned
    assert snap["backend"] == "cpu"
    assert snap["failover"] is None


# --------------------------------------------------------- RunSupervisor


def _policy(n=2):
    return RestartPolicy(max_restarts=n, backoff_seconds=0, jitter=False)


def test_run_supervisor_classified_restart_and_journal(tmp_path):
    path = str(tmp_path / "recovery.jsonl")
    calls = []

    def flaky(i):
        calls.append(i)
        if i == 0:
            raise DeviceLostError("chip fell off the bus")
        return {"ok": True}

    before = REGISTRY.counter("run_restarts_total").value(
        cause="device_lost")
    sup = RunSupervisor(_policy(), journal=RecoveryJournal(path),
                        sleep=lambda s: None)
    assert sup.run(flaky) == {"ok": True}
    assert calls == [0, 1]
    assert REGISTRY.counter("run_restarts_total").value(
        cause="device_lost") == before + 1
    rows = [json.loads(x) for x in open(path).read().splitlines()]
    assert [r["event"] for r in rows] == [
        "attempt_start", "attempt_failed", "restart", "attempt_start",
        "run_ok"]
    failed = rows[1]
    assert failed["cause"] == "device_lost" and failed["ok"] is False
    assert failed["will_restart"] is True
    assert all("time" in r and "pid" in r for r in rows)


def test_run_supervisor_exhausts_with_last_cause(tmp_path):
    def doomed(i):
        raise RuntimeError("Unable to initialize backend: UNAVAILABLE")

    sup = RunSupervisor(
        _policy(1), journal=str(tmp_path / "r.jsonl"), sleep=lambda s: None)
    with pytest.raises(RestartsExhausted) as ei:
        sup.run(doomed)
    assert ei.value.cause == "init_unavailable"
    assert len(ei.value.failures) == 2
    assert all(f.cause == "init_unavailable" for f in ei.value.failures)
    rows = [json.loads(x)
            for x in open(tmp_path / "r.jsonl").read().splitlines()]
    assert rows[-1]["event"] == "exhausted"
    assert rows[-1]["cause"] == "init_unavailable"


def test_run_supervisor_fatal_not_retried(tmp_path):
    calls = []

    def config_bug(i):
        calls.append(i)
        raise ValueError("bad coordinate spec")

    sup = RunSupervisor(_policy(), journal=str(tmp_path / "r.jsonl"),
                        sleep=lambda s: None)
    with pytest.raises(ValueError):
        sup.run(config_bug)
    assert calls == [0]  # never restarted
    rows = [json.loads(x)
            for x in open(tmp_path / "r.jsonl").read().splitlines()]
    assert rows[-1]["event"] == "fatal"


def test_run_supervisor_preemption_cause(tmp_path):
    def preempted(i):
        if i == 0:
            raise PreemptionError("spot instance reclaimed")
        return i

    sup = RunSupervisor(_policy(), journal=str(tmp_path / "r.jsonl"),
                        sleep=lambda s: None)
    assert sup.run(preempted) == 1
    rows = [json.loads(x)
            for x in open(tmp_path / "r.jsonl").read().splitlines()]
    assert rows[1]["cause"] == "preemption"


# ---------------------------------------------- failover artifacts vs gate


def _write_artifact(path, backend, value, failover=None):
    details = {
        "fixed_effect_samples_per_sec": value,
        "backend": backend,
        "written_at": "2026-08-04T00:00:00Z",
        "provenance": {
            "hostname": "bench-box",
            "jax_version": "0.4.37",
            "backend_summary": {"backend": backend,
                                "stage_backends_distinct": [backend],
                                "mixed_backends": False},
            "backend_guard": {
                "backend_init_seconds": 1.2 if failover is None else 120.0,
                "probe_attempts": 1,
                "failover": failover,
            },
        },
    }
    with open(path, "w") as f:
        json.dump(details, f)
    return str(path)


def test_gate_refuses_failover_round_against_accelerator(tmp_path):
    """ISSUE acceptance: a failover run's artifact resolves to backend=cpu
    and the PR 6 gate refuses the comparison against an accelerator round
    — with the failover surfaced in the comparability notes."""
    from photon_tpu.obs.analysis.bench_compare import compare_pair
    from photon_tpu.obs.analysis.artifacts import load_bench_artifact

    accel = _write_artifact(tmp_path / "BENCH_r10.json", "tpu", 13.0)
    failed_over = _write_artifact(
        tmp_path / "BENCH_r11.json", "cpu", 1.0,
        failover={"to": "cpu", "cause": "init_unavailable",
                  "reason": "UNAVAILABLE: TPU backend setup/compile error"})
    old, new = load_bench_artifact(accel), load_bench_artifact(failed_over)
    assert new.details["backend"] == "cpu"  # failover stamped honestly
    verdict = compare_pair(old, new)
    d = next(x for x in verdict.deltas
             if x.metric == "fixed_effect_samples_per_sec")
    # The 13x "regression" is a hardware change, not a code change.
    assert d.verdict == "incomparable"
    assert verdict.verdict == "incomparable"
    assert any("failover occurred" in n for n in verdict.notes)
    assert any("init_unavailable" in n for n in verdict.notes)


# --------------------------------------------------- OOC in-run recovery


@pytest.mark.chaos
def test_ooc_device_lost_resumes_bit_identical(tmp_path):
    """A device_lost injected mid-solve through the optim.ooc_iteration
    hook triggers the in-run recovery (cache clear + checkpoint
    fast-forward) and the final coefficients equal the uninterrupted
    run's bit for bit."""
    from tests.test_out_of_core import _data, _problem
    from photon_tpu.optim.out_of_core import ChunkedGLMData, run_out_of_core

    idx, val, labels = _data(n=600, seed=4)
    problem = _problem(max_iter=12)

    def solve(ckpt):
        data = ChunkedGLMData.from_arrays(idx, val, labels, 150,
                                          chunk_rows=256)
        return run_out_of_core(problem, data, checkpoint_path=ckpt)

    _, ref = solve(str(tmp_path / "ref.npz"))

    plan = FaultPlan(seed=0, specs=[
        FaultSpec(site="optim.ooc_iteration", error="device_lost",
                  after=3, count=1),
    ])
    before = REGISTRY.counter("run_restarts_total").value(
        cause="device_lost")
    with active_plan(plan) as inj:
        _, rec = solve(str(tmp_path / "rec.npz"))
    assert inj.fired("optim.ooc_iteration") == 1  # the loss really fired
    assert REGISTRY.counter("run_restarts_total").value(
        cause="device_lost") == before + 1
    np.testing.assert_array_equal(np.asarray(rec.x), np.asarray(ref.x))
    assert float(rec.value) == float(ref.value)


@pytest.mark.chaos
def test_ooc_device_lost_exhausts_bounded_recoveries(tmp_path, monkeypatch):
    """Past PHOTON_DEVICE_LOST_MAX_RECOVERIES the loss escalates instead
    of looping forever."""
    from tests.test_out_of_core import _data, _problem
    from photon_tpu.optim.out_of_core import ChunkedGLMData, run_out_of_core

    monkeypatch.setenv("PHOTON_DEVICE_LOST_MAX_RECOVERIES", "1")
    idx, val, labels = _data(n=300, seed=5)
    problem = _problem(max_iter=8)
    plan = FaultPlan(seed=0, specs=[
        FaultSpec(site="optim.ooc_iteration", error="device_lost"),
    ])
    data = ChunkedGLMData.from_arrays(idx, val, labels, 150, chunk_rows=256)
    with active_plan(plan) as inj:
        with pytest.raises(DeviceLostError):
            run_out_of_core(problem, data,
                            checkpoint_path=str(tmp_path / "c.npz"))
    # initial + 1 allowed recovery = 2 firings, then escalate.
    assert inj.fired("optim.ooc_iteration") == 2
