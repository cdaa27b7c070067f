"""What the bring-up on the chip repaired, held in place without a chip:
the native decoder's build race, the strict backend policy's refusal of a
CPU nobody asked for, the one place the compile cache goes, and
chip_smoke.py itself (its tiny rehearsal path, and its refusal to report
anything off the chip)."""
import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


# ------------------------------------------------- step 1: the build race


def _fresh_native_checkout(tmp_path):
    """A package tree holding photon_tpu/native as git commits it: the
    source, no built library."""
    pkg = tmp_path / "photon_tpu"
    (pkg / "native").mkdir(parents=True)
    (pkg / "__init__.py").write_text("")
    for name in ("__init__.py", "avro_block.cc"):
        shutil.copy(os.path.join(REPO, "photon_tpu", "native", name),
                    pkg / "native" / name)
    return pkg / "native"


@pytest.mark.skipif(shutil.which("g++") is None, reason="no C++ toolchain")
def test_concurrent_first_use_builds_once_and_every_process_loads(tmp_path):
    """Six fresh processes reach get_lib() at once on a checkout without the
    .so (xdist workers, ingest workers): every one gets the library — the
    losers of the race load the winner's file, none is left marked failed —
    and no temporary is left behind."""
    native = _fresh_native_checkout(tmp_path)
    code = ("import sys, photon_tpu.native as n; "
            "sys.exit(0 if n.get_lib() is not None and not n._failed else 1)")
    env = dict(os.environ, PYTHONPATH=str(tmp_path))
    env.pop("PHOTON_TPU_NO_NATIVE", None)
    procs = [subprocess.Popen([sys.executable, "-c", code], env=env,
                              cwd=str(tmp_path)) for _ in range(6)]
    assert [p.wait(timeout=600) for p in procs] == [0] * 6
    built = sorted(os.listdir(native))
    assert len([f for f in built if f.endswith(".so")]) == 1
    assert not [f for f in built if f.endswith(".tmp")]


def test_compile_under_the_lock_rechecks_for_a_finished_library(
        tmp_path, monkeypatch):
    """A process that lost the race finds the winner's .so once it holds
    the lock and does not run the compiler again."""
    import photon_tpu.native as native

    so = tmp_path / "_avro_block.test.so"
    so.write_bytes(b"built by the winner")
    monkeypatch.setattr(native, "_SO", str(so))

    def no_compiler(*a, **k):
        raise AssertionError("compiler started although the .so is fresh")

    monkeypatch.setattr(native.subprocess, "run", no_compiler)
    assert native._compile() is True
    assert so.read_bytes() == b"built by the winner"


# --------------------------------------- step 5: strict means the chip


def _probe_printing(backend: str) -> str:
    return f"print('PHOTON_BACKEND={backend}')"


@pytest.fixture
def guard():
    from photon_tpu.runtime import backend_guard as bg

    bg.reset_guard()
    yield bg
    bg.reset_guard()


@pytest.mark.parametrize("policy", ["strict", "failover"])
def test_cpu_that_nobody_asked_for_is_a_failed_probe(
        guard, monkeypatch, policy):
    """JAX finds no chip and falls to the CPU; the probe child prints 'cpu'
    and exits 0. Under strict that is BackendUnusable, not a training run;
    under failover it is the stamped swap the operator opted into."""
    monkeypatch.setattr(guard, "_cpu_asked_for", lambda: False)
    monkeypatch.setattr(guard, "_pin_cpu", lambda: None)
    if policy == "strict":
        with pytest.raises(guard.BackendUnusable) as e:
            guard.ensure_backend("strict", probe_code=_probe_printing("cpu"))
        assert e.value.cause == guard.CAUSE_INIT_UNAVAILABLE
        assert "no accelerator" in e.value.reason
    else:
        snap = guard.ensure_backend(
            "failover", probe_code=_probe_printing("cpu"))
        assert snap["failover"]["cause"] == guard.CAUSE_INIT_UNAVAILABLE


def test_live_cpu_nobody_asked_for_is_refused_without_a_probe(
        guard, monkeypatch):
    """The in-process road (jax already live here) is judged like the
    probe child's answer: a CPU that was not asked for is refused."""
    import jax.numpy as jnp

    jnp.zeros(1).block_until_ready()  # backend live: cpu
    monkeypatch.setattr(guard, "_cpu_asked_for", lambda: False)
    monkeypatch.setattr(
        guard, "probe_backend",
        lambda **k: pytest.fail("spawned a probe although jax is live"))
    with pytest.raises(guard.BackendUnusable, match="no accelerator"):
        guard.ensure_backend("strict")


def test_no_variable_turns_the_probe_off(guard, monkeypatch):
    """Before jax is live nothing skips the probe: PHOTON_BACKEND_PROBE=0
    used to, and strict then trained on whatever jax fell back to."""
    monkeypatch.setenv("PHOTON_BACKEND_PROBE", "0")
    monkeypatch.setattr(guard, "_jax_initialized", lambda: False)
    monkeypatch.setattr(guard, "_cpu_asked_for", lambda: False)
    probed = []

    def probe(**kwargs):
        probed.append(kwargs)
        return guard.BackendProbeResult(
            ok=True, backend="cpu", seconds=0.0, attempts=1)

    monkeypatch.setattr(guard, "probe_backend", probe)
    with pytest.raises(guard.BackendUnusable, match="no accelerator"):
        guard.ensure_backend("strict")
    assert len(probed) == 1


def test_strict_accepts_the_cpu_when_it_was_asked_for(guard, monkeypatch):
    monkeypatch.setattr(guard, "_cpu_asked_for", lambda: True)
    snap = guard.ensure_backend("strict", probe_code=_probe_printing("cpu"))
    assert snap["backend"] == "cpu" and snap["failover"] is None


def test_strict_accepts_the_chip_whatever_was_asked_for(guard, monkeypatch):
    monkeypatch.setattr(guard, "_cpu_asked_for", lambda: False)
    snap = guard.ensure_backend("strict", probe_code=_probe_printing("tpu"))
    assert snap["backend"] == "tpu"


@pytest.mark.parametrize("value,asked", [
    (None, False), ("", False), ("tpu", False), ("cpu", True),
    ("tpu,cpu", True)])
def test_cpu_asked_for_reads_the_variable_before_jax_is_imported(
        guard, monkeypatch, value, asked):
    if value is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", value)
    monkeypatch.setitem(sys.modules, "jax", None)  # as in a fresh driver
    assert guard._cpu_asked_for() is asked


def _files_naming(name, *more):
    """Files of the program, its drivers and scripts (and ``more``) that
    still hold ``name``; binary files left out."""
    return subprocess.run(
        ["grep", "-rlI", name, "photon_tpu", "bench.py", "chip_smoke.py",
         "scripts", "ci.sh", *more],
        cwd=REPO, capture_output=True, text=True).stdout


def test_no_cpu_masquerade_left():
    from photon_tpu import types

    assert types.REAL_ACCELERATOR_BACKENDS == ("tpu",)
    for gone in ("PHOTON_ACCEPT_CPU_AS_REAL", "PHOTON_AUTOPILOT_FAKE",
                 "PHOTON_BACKEND_LOCK_WAIT", "PHOTON_XLA_CACHE_DIR"):
        assert _files_naming(gone) == "", f"{gone} still read"


@pytest.mark.parametrize("gone", [
    "PHOTON_PALLAS_INTERPRET", "PHOTON_ACCEL_AUX_BUDGET_GB",
    "with_pallas_path", "pallas_sparse"])
def test_names_that_are_gone(gone):
    """PR 30: the sparse pass has two formulations and one table field; the
    third, its switch and the budget variable are named by no code or
    document a user reads."""
    assert _files_naming(gone, "README.md", "docs") == "", (
        f"{gone} still named")


# ---------------------------------- step 6: one place for the compile cache


def test_variable_set_means_the_program_sets_no_directory(
        monkeypatch, tmp_path):
    import jax

    from photon_tpu.runtime.compile_store import (
        compilation_cache_dir,
        enable_compilation_cache,
    )

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    assert compilation_cache_dir() is None
    assert compilation_cache_dir(str(tmp_path / "env")) is None  # same place
    with pytest.raises(ValueError, match="conflicts"):
        compilation_cache_dir(str(tmp_path / "elsewhere"))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compilation_cache() == before
    assert jax.config.jax_compilation_cache_dir == before
    assert not (tmp_path / "env").exists()  # jax makes it, not the program


def test_unset_the_cache_sits_beside_the_package_for_every_output_dir(
        monkeypatch, tmp_path):
    from photon_tpu.cli import params
    from photon_tpu.runtime import compile_store as cs

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    want = os.path.join(REPO, ".jax_cache")
    assert cs.compilation_cache_dir() == want
    assert cs.compilation_cache_dir(str(tmp_path / "flag")) == str(
        tmp_path / "flag")
    # The drivers reach the same function through the flag's module.
    assert params.enable_compilation_cache is cs.enable_compilation_cache

    # The compile store of two runs with different output directories asks
    # the same resolver, with no directory of its own.
    import jax

    asked = []
    monkeypatch.setattr(
        cs, "enable_compilation_cache",
        lambda flag=None, min_compile_secs=None: asked.append(
            cs.compilation_cache_dir(flag)))
    session_dir = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)  # as in a driver
    try:
        for out in ("a", "b"):
            cs.configure(str(tmp_path / out / "compile-store"))
    finally:
        cs.deactivate()
        jax.config.update("jax_compilation_cache_dir", session_dir)
    assert asked == [want, want]


# ------------------------------------------------------- chip_smoke.py


def _run_smoke(*args, env=None, cwd=REPO, script=SMOKE):
    return subprocess.run(
        [sys.executable, script, *args], env=env or dict(os.environ),
        cwd=cwd, capture_output=True, text=True, timeout=900)


def test_smoke_parent_never_imports_jax():
    code = ("import sys; sys.path.insert(0, %r); import chip_smoke; "
            "assert 'jax' not in sys.modules and "
            "'photon_tpu' not in sys.modules" % REPO)
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


def test_smoke_on_the_cpu_fails_with_one_line_and_no_result(tmp_path):
    r = _run_smoke("--out", str(tmp_path / "out"),
                   env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode != 0
    assert r.stdout == ""
    assert len(r.stderr.strip().splitlines()) == 1
    assert "JAX_PLATFORMS=cpu" in r.stderr


def test_smoke_alone_without_the_program_fails(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the repo
    there is nothing to drive: exit != 0, no result line."""
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "PYTHONPATH")}
    r = _run_smoke("--out", str(tmp_path / "out"), env=env,
                   cwd=str(tmp_path), script=str(tmp_path / "chip_smoke.py"))
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


@pytest.mark.parametrize("chips,phases", [
    (1, ["data", "train", "score", "serve"]),
    (4, ["data", "train4", "train1", "compare"]),
])
def test_smoke_rehearsal_runs_every_phase(tmp_path, chips, phases):
    """Rehearsals 1 and 2 of the on-chip-measurement guide: the whole
    script at the tiny size on the CPU (four virtual devices for the
    four-chip option), through the real drivers."""
    r = _run_smoke("--rehearse", "--chips", str(chips),
                   "--out", str(tmp_path / "out"),
                   env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [json.loads(l) for l in r.stdout.splitlines()]
    assert [l["phase"] for l in lines[:-1]] == phases
    assert lines[-1] == {"ok": True, "device": {
        "platform": "cpu", "kind": lines[-1]["device"]["kind"],
        "count": chips}}
    by_phase = {l["phase"]: l for l in lines[:-1]}
    if chips == 1:
        assert by_phase["score"]["max_abs_diff_vs_numpy"] <= 1e-4
        assert by_phase["serve"]["max_abs_diff_vs_numpy"] <= 1e-4
        assert by_phase["serve"]["requests"] >= 32
        # What each process really traced: off the chip the plain path; the
        # server scores rows without SparseFeatures.
        assert set(by_phase["train"]["sparse_op_traces"]["plain"]) == {
            "matvec", "rmatvec"}
        assert by_phase["score"]["sparse_op_traces"] == {
            "plain": {"matvec": 1}}
        assert by_phase["serve"]["sparse_op_traces"] == {}
        for step in by_phase["train"]["steps"]:
            assert "MAX_ITERATIONS" not in step["reasons"]
        for phase in ("train", "score", "serve"):
            assert "dir" in by_phase[phase]["compile_cache"]
    else:
        spread = by_phase["train4"]["sharded_bytes"]
        for kind in ("fixed_effect_features", "random_effect_bucket"):
            assert len(spread[kind]) == 4
            assert len(set(spread[kind].values())) == 1  # evenly
        # Only the partitioning differs between the two sides.
        assert (list(by_phase["train4"]["sparse_op_traces"])
                == list(by_phase["train1"]["sparse_op_traces"]) == ["plain"])
        cmp_ = by_phase["compare"]
        assert cmp_["fixed_rel_diff"] <= cmp_["fixed_rel_limit"] == 1e-4
        for printed in ("random_regularized_abs_diff",
                        "random_intercept_abs_diff"):  # not held
            assert cmp_[printed] >= 0
        for step in cmp_["steps"]:
            assert step["logistic_loss_rel_diff"] <= 1e-5
            assert step["auc_diff"] <= 1e-5


def test_unconverged_training_fails_the_smoke(tmp_path):
    """A step that stopped on the iteration limit is not a convergence
    reason — except the fixed effect where the caller cut it."""
    import chip_smoke

    steps = [
        {"sweep": 0, "coordinate": "fixed", "seconds": 1.0, "iterations": 10,
         "data_passes": 23, "reasons": {"MAX_ITERATIONS": 1}, "AUC": 0.7,
         "LOGISTIC_LOSS": 0.6},
        {"sweep": 0, "coordinate": "perUser", "seconds": 1.0,
         "iterations": 9, "data_passes": 99,
         "reasons": {"GRADIENT_CONVERGED": 64}, "AUC": 0.8,
         "LOGISTIC_LOSS": 0.5},
    ]
    (tmp_path / "metrics.jsonl").write_text(
        "".join(json.dumps(s) + "\n" for s in steps))
    (tmp_path / "photon.log").write_text("")
    report = {"phase": "train"}
    with pytest.raises(chip_smoke.SmokeFailure, match="did not converge"):
        chip_smoke._check_training(report, str(tmp_path), sweeps=1)
    chip_smoke._check_training(report, str(tmp_path), sweeps=1,
                               fixed_cut=True)
    steps[1]["reasons"] = {"GRADIENT_CONVERGED": 63, "MAX_ITERATIONS": 1}
    (tmp_path / "metrics.jsonl").write_text(
        "".join(json.dumps(s) + "\n" for s in steps))
    with pytest.raises(chip_smoke.SmokeFailure, match="perUser did not"):
        chip_smoke._check_training(report, str(tmp_path), sweeps=1,
                                   fixed_cut=True)


def test_solve_on_another_formulation_than_the_default_fails_the_smoke():
    import chip_smoke

    rep = {"phase": "train", "sparse_op_traces": {
        "fast": {"matvec": 3}, "plain": {"matvec": 2, "rmatvec": 2}}}
    chip_smoke._check_formulation(rep, "plain")
    with pytest.raises(chip_smoke.SmokeFailure, match="wanted the fast"):
        chip_smoke._check_formulation(rep, "fast")
    # On the chip either table formulation will do, but one of them alone.
    with pytest.raises(chip_smoke.SmokeFailure, match="window or fast"):
        chip_smoke._check_formulation(rep, "window", "fast")
    rep["sparse_op_traces"] = {"window": {"matvec": 3, "rmatvec": 2},
                               "plain": {"matvec": 2}}
    chip_smoke._check_formulation(rep, "window", "fast")
    rep["sparse_op_traces"]["fast"] = {"rmatvec": 1}
    with pytest.raises(chip_smoke.SmokeFailure, match="window or fast"):
        chip_smoke._check_formulation(rep, "window", "fast")


def test_sparse_ops_count_the_formulation_they_trace(monkeypatch):
    import jax.numpy as jnp

    from photon_tpu.data.batch import SparseFeatures
    from photon_tpu.obs.metrics import REGISTRY
    from photon_tpu.ops import fast_sparse

    counter = REGISTRY.counter("sparse_op_traces_total")
    before = {k: counter.value(op=k[0], formulation=k[1]) for k in (
        ("matvec", "plain"), ("rmatvec", "window"), ("sq_rmatvec", "window"),
        ("matvec", "fast"), ("rmatvec", "fast"))}
    plain = SparseFeatures(idx=jnp.zeros((8, 2), jnp.int32),
                           val=jnp.ones((8, 2), jnp.float32), dim=4)
    plain.matvec(jnp.ones(4, jnp.float32))
    tables = plain.with_fast_path()      # one window a side: the kernel
    tables.rmatvec(jnp.ones(8, jnp.float32))
    tables.sq_rmatvec(jnp.ones(8, jnp.float32))
    monkeypatch.setattr(fast_sparse, "WINDOW_BREAK_EVEN_PASSES", -1.0)
    row_slices = plain.with_fast_path()
    row_slices.matvec(jnp.ones(4, jnp.float32))
    row_slices.rmatvec(jnp.ones(8, jnp.float32))
    for (op, kind), n in before.items():
        assert counter.value(op=op, formulation=kind) == n + 1
