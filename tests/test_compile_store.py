"""AOT compile-artifact store (photon_tpu/runtime/compile_store.py):
zero-recompile recovery. The cold-vs-warm roundtrip is the ISSUE 12
acceptance drill — compile the blessed kernel set with the store enabled,
clear the executable caches, pre-warm from the manifest, and the re-run
must re-trace NOTHING (warm reload compile time a vanishing fraction of
the cold compile) while producing bit-identical solve results. Also here:
manifest persistence across store instances, backend-mismatch skipping,
the supervisor pre-warm + restart_to_first_step journal contract, the
checkpoint manifest-reference stamp, and the enable_compilation_cache
late-call guard (satellite: a late call was a silent no-op)."""
import json
import logging
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.data.batch import LabeledBatch, SparseFeatures
from photon_tpu.functions.problem import GLMOptimizationProblem, _fit_jitted
from photon_tpu.obs import retrace
from photon_tpu.obs.metrics import REGISTRY
from photon_tpu.optim import (
    OptimizerConfig,
    RegularizationContext,
    RegularizationType,
)
from photon_tpu.runtime import compile_store as cs
from photon_tpu.supervisor import (
    RecoveryJournal,
    RestartPolicy,
    RunSupervisor,
    clear_executable_caches,
)
from photon_tpu.types import TaskType


@pytest.fixture(autouse=True)
def _isolated_store(tmp_path):
    """Every test gets a clean store slot and an EMPTY persistent cache of
    its own (cold-vs-warm assertions need a cache nothing has written to),
    and leaves jax's persistent-cache config exactly as it found it
    (configure() mutates process state)."""
    prev_dir = jax.config.jax_compilation_cache_dir
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "xla"))
    cs._reset_jax_cache_handle()
    yield
    cs.deactivate()
    cs.disarm_first_step_clock()
    jax.config.update("jax_compilation_cache_dir", prev_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", prev_min)
    cs._reset_jax_cache_handle()


def _problem_batch(n=1024, d=48, k=5, seed=0, max_iterations=8):
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, d, size=(n, k)).astype(np.int32)
    val = rng.normal(size=(n, k)).astype(np.float32)
    y = (rng.random(n) < 0.5).astype(np.float32)
    batch = LabeledBatch(
        features=SparseFeatures(jnp.asarray(idx), jnp.asarray(val), d),
        labels=jnp.asarray(y),
        offsets=jnp.zeros(n, jnp.float32),
        weights=jnp.ones(n, jnp.float32),
    )
    problem = GLMOptimizationProblem(
        task=TaskType.LOGISTIC_REGRESSION,
        regularization=RegularizationContext(RegularizationType.L2),
        reg_weight=1.0,
        optimizer_config=OptimizerConfig(max_iterations=max_iterations),
    )
    return problem, batch, jnp.zeros(d, jnp.float32)


def test_cold_vs_warm_roundtrip_bit_identical(tmp_path):
    """ISSUE 12 acceptance: cold compile → record → cache clear → manifest
    pre-warm → warm re-run with zero kernel re-traces (compile_watch sees
    NO compile, so the warm reload compile time is literally 0 — a small
    fraction of the cold compile by any margin) and bit-identical
    results. The pre-warm itself must be load-dominated (XLA share below
    I/O share)."""
    store = cs.configure(str(tmp_path / "store"))
    problem, batch, w0 = _problem_batch()

    with cs.compile_split() as cold_split, \
            retrace.compile_watch(kernels=("glm_fit",)) as cw_cold:
        model, _ = problem.fit(batch, w0)
        np.asarray(model.coefficients.means[:1])
    assert cw_cold.compiled.get("glm_fit", 0) >= 1   # genuinely cold
    assert cold_split.xla_seconds > 0
    ref = np.asarray(model.coefficients.means)
    assert len(store.entries()) == 1                 # the record site fired

    clear_executable_caches("test: roundtrip")
    summary = store.prewarm()
    assert summary["loaded"] == 1 and summary["compiled"] == 0
    assert summary["skipped"] == 0
    # Warm reload is load-dominated AND a small fraction of the cold
    # compile: the XLA share is ~0 and even load+xla stays well under the
    # cold XLA wall.
    assert summary["xla_seconds"] <= summary["load_seconds"]
    assert (summary["load_seconds"] + summary["xla_seconds"]
            < 0.9 * cold_split.xla_seconds)

    with retrace.compile_watch(kernels=("glm_fit",)) as cw_warm:
        model2, _ = problem.fit(batch, w0)
        np.asarray(model2.coefficients.means[:1])
    # The pre-warm populated the jit dispatch cache: the re-run re-traced
    # NOTHING, so its compile time is zero.
    assert cw_warm.compiled == {}
    assert cw_warm.compile_seconds == 0.0
    np.testing.assert_array_equal(ref, np.asarray(model2.coefficients.means))


def test_record_dedup_and_manifest_persistence(tmp_path):
    store = cs.configure(str(tmp_path / "store"))
    problem, batch, w0 = _problem_batch(n=256, d=16, max_iterations=3)
    import dataclasses

    key = dataclasses.replace(problem, reg_mask=None, prior=None,
                              reg_weight=1.0)
    rw = jnp.asarray(problem.reg_weight, w0.dtype)
    args = (key, batch, w0, None, None, None, rw)
    assert store.record("glm_fit", _fit_jitted, args) is True
    assert store.record("glm_fit", _fit_jitted, args) is False  # dedup
    assert len(store.entries()) == 1

    # A FRESH store object on the same root reloads the manifest and can
    # pre-warm it (a restarted process's view).
    reloaded = cs.CompileStore(store.root)
    assert reloaded.entries().keys() == store.entries().keys()
    summary = reloaded.prewarm()
    assert summary["entries"] == 1 and summary["skipped"] == 0
    assert summary["loaded"] + summary["compiled"] == 1
    assert reloaded.manifest_digest() == store.manifest_digest()


def test_prewarm_skips_foreign_backend_and_corrupt_entries(tmp_path):
    store = cs.configure(str(tmp_path / "store"))
    # Unique shape: an aval already jit-cached by another test would not
    # compile, so the record site would never fire.
    problem, batch, w0 = _problem_batch(n=384, d=24, max_iterations=3)
    problem.fit(batch, w0)
    assert len(store.entries()) == 1

    # Tamper: a TPU-recorded entry on a CPU host must be skipped, not
    # compiled into the wrong backend's cache.
    with open(store.manifest_path) as f:
        data = json.load(f)
    (key,) = data["entries"]
    data["entries"][key]["backend"] = "tpu"
    data["entries"]["deadbeef" * 3] = {  # sig file missing → skipped
        "kernel": "glm_fit", "fn": "photon_tpu.functions.problem:_fit_jitted",
        "backend": jax.default_backend(), "jax_version": jax.__version__,
        "code_fingerprint": "bogus",
    }
    with open(store.manifest_path, "w") as f:
        json.dump(data, f)
    reloaded = cs.CompileStore(store.root)
    summary = reloaded.prewarm()
    assert summary["loaded"] == 0 and summary["compiled"] == 0
    assert summary["skipped"] == 2

    # A corrupt manifest degrades to an empty store, never an error.
    with open(store.manifest_path, "w") as f:
        f.write("{torn")
    assert cs.CompileStore(store.root).entries() == {}


def test_supervisor_prewarm_journal_and_first_step(tmp_path):
    """The RunSupervisor contract (docs/robustness.md §recovery time): a
    restart pre-warms from the store between attempts (ONE un-mirrored
    ``prewarm`` journal row, load-dominated) and every attempt journals
    ``restart_to_first_step_seconds``; the restarted attempt re-traces
    nothing."""
    from photon_tpu.faults import DeviceLostError

    store = cs.configure(str(tmp_path / "store"))
    problem, batch, w0 = _problem_batch(n=768, d=40)  # unique shape
    journal_path = str(tmp_path / "recovery.jsonl")
    traced = {}

    def attempt(i):
        before = retrace.traces("glm_fit")
        model, _ = problem.fit(batch, w0)
        np.asarray(model.coefficients.means[:1])
        traced[i] = retrace.traces("glm_fit") - before
        cs.note_first_step("test.step")
        if i == 0:
            clear_executable_caches("test: injected loss")
            raise DeviceLostError("injected")
        return np.asarray(model.coefficients.means)

    sup = RunSupervisor(
        RestartPolicy(max_restarts=1, backoff_seconds=0, jitter=False),
        journal=RecoveryJournal(journal_path),
        sleep=lambda s: None,
        compile_store=store,
    )
    out = sup.run(attempt)
    assert np.isfinite(out).all()
    assert traced[0] >= 1 and traced[1] == 0

    rows = [json.loads(x) for x in open(journal_path).read().splitlines()]
    prewarms = [r for r in rows if r["event"] == "prewarm"]
    assert len(prewarms) == 1
    assert prewarms[0]["loaded"] >= 1
    assert prewarms[0]["xla_seconds"] <= prewarms[0]["load_seconds"]
    firsts = [r for r in rows if r["event"] == "first_step"]
    assert [r["attempt"] for r in firsts] == [0, 1]
    assert all(r["restart_to_first_step_seconds"] > 0 for r in firsts)
    # The gauge serves /healthz and bench.
    assert REGISTRY.gauge("restart_to_first_step_seconds").value() > 0
    # The clock disarms with the run: a later step stamps nothing new.
    assert cs.note_first_step("test.step") is None


def test_checkpoint_carries_manifest_ref_and_prewarms(tmp_path):
    from photon_tpu.checkpoint import CheckpointManager

    store = cs.configure(str(tmp_path / "store"))
    problem, batch, w0 = _problem_batch(n=512, d=20, max_iterations=3)
    problem.fit(batch, w0)  # one recorded entry (unique shape: must compile)

    mgr = CheckpointManager(str(tmp_path / "ck"))
    mgr.save(0, state={"w": np.zeros(3)}, meta={"kind": "t"})
    mgr.close()
    payload = CheckpointManager(str(tmp_path / "ck")).load_latest()
    ref = payload["meta"]["compile_store"]
    assert ref["root"] == store.root and ref["entries"] == 1

    clear_executable_caches("test: resume")
    summary = cs.prewarm_from_checkpoint(payload)
    assert summary is not None and summary["loaded"] == 1

    # Resume on a host where BOTH the referenced root and the active store
    # are gone: degrade to None, never an error.
    cs.deactivate()
    payload["meta"]["compile_store"]["root"] = str(tmp_path / "nope")
    assert cs.prewarm_from_checkpoint(payload) is None


def test_enable_compilation_cache_late_call_warns(tmp_path, caplog,
                                                  monkeypatch):
    """Satellite: enabling the persistent cache AFTER the first compile
    used to be a silent no-op. It must now warn loudly (and re-initialize
    the cache handle so later compiles do persist)."""
    # conftest names the session's cache through the variable; a flag is
    # only honoured without it.
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    cs.note_compilation()  # this process has long since compiled something
    with caplog.at_level(logging.WARNING, logger="photon_tpu.runtime"):
        cs.enable_compilation_cache(str(tmp_path / "late"))
    assert any("AFTER this process already compiled" in r.message
               for r in caplog.records)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path / "late")


def test_explicit_off_pins_over_env(tmp_path, monkeypatch):
    """`--compile-store off` must hold even under a fleet-wide
    $PHOTON_COMPILE_STORE export — the lazy env activation previously
    overrode the operator's explicit opt-out on the first compile."""
    monkeypatch.setenv("PHOTON_COMPILE_STORE", str(tmp_path / "envstore"))
    cs.disable()
    assert cs.active() is None
    assert cs.record_if_active("glm_fit", _fit_jitted, ()) is False
    cs.deactivate()  # pristine again: the env names the store once more
    assert cs.active() is not None
    assert cs.active().root == str(tmp_path / "envstore")


def test_record_is_best_effort_on_unpicklable_statics(tmp_path):
    store = cs.configure(str(tmp_path / "store"))

    unpicklable = lambda x: x  # noqa: E731 - locals don't pickle
    assert store.record("glm_fit", _fit_jitted,
                        (unpicklable, jnp.zeros(3))) is False
    assert store.entries() == {}
