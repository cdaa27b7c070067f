"""Memory-pressure resilience (runtime/memory_guard; docs/robustness.md
§"Memory pressure"): OOM classification, the bounded/sticky downshift
ladder, the device-memory watchdog's spill + shed thresholds, the live
sweep-cache budget clamp, and the supervisor's restart-cannot-fix-OOM
policy. The per-site ladder drills (RE chunk tier, out-of-core rechunk)
run here at tiny shapes; the end-to-end chaos drills live in
tests/test_chaos.py / test_serving.py / test_online.py (``-m chaos``).
"""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.faults import (
    DeviceOomError,
    FaultPlan,
    FaultSpec,
    active_plan,
)
from photon_tpu.obs.metrics import REGISTRY
from photon_tpu.runtime import backend_guard as bg
from photon_tpu.runtime import memory_guard as mg
from photon_tpu.supervisor import (
    RecoveryJournal,
    RestartPolicy,
    RestartsExhausted,
    RunSupervisor,
    run_with_recovery,
)


@pytest.fixture(autouse=True)
def _fresh_guard_state():
    """Sticky downshifts are process-global by design; tests must not
    leak degraded plans into each other."""
    mg.reset_state()
    yield
    mg.reset_state()


def _fake_stats(in_use: float, limit: float = 1000.0):
    return lambda: {"bytes_in_use": float(in_use),
                    "bytes_limit": float(limit),
                    "watermark": float(in_use) / float(limit)}


# ------------------------------------------------------------ classification


def test_device_oom_classifies_oom_by_type():
    assert bg.classify_backend_error(DeviceOomError("boom")) == bg.CAUSE_OOM
    assert mg.is_oom(DeviceOomError("boom"))
    assert mg.is_oom(MemoryError("host oom"))
    assert mg.is_oom(RuntimeError(
        "RESOURCE_EXHAUSTED: out of memory allocating 16G"))
    # A device loss is NOT an OOM — it takes the PR 8 recovery path.
    assert not mg.is_oom(RuntimeError("device was lost"))


def test_device_oom_is_supervisor_retryable():
    """DeviceOomError subclasses RuntimeError (like XlaRuntimeError) so
    the restart policy admits it — the OOM-specific handling then decides
    what a 'retry' means."""
    assert RestartPolicy().is_retryable(DeviceOomError("boom"))


def test_fault_plan_device_oom_spec_roundtrips():
    plan = FaultPlan(seed=0, specs=[
        FaultSpec(site="re.solve", error="device_oom", count=1)])
    back = FaultPlan.from_json(plan.to_json())
    assert back.specs[0].error == "device_oom"
    with active_plan(back) as inj:
        from photon_tpu.faults import fault_point

        with pytest.raises(DeviceOomError):
            fault_point("re.solve")
        assert inj.fired("re.solve") == 1


# ---------------------------------------------------------------- downshifter


def test_downshifter_bounded_and_counted(monkeypatch):
    monkeypatch.setenv("PHOTON_OOM_MAX_DOWNSHIFTS", "2")
    before = REGISTRY.counter("oom_downshifts_total").value(
        site="t.site", cause="oom")
    d = mg.downshifter("t.site")
    err = DeviceOomError("boom")
    assert d.absorb(err, before="a", after="b")
    assert d.absorb(err, before="b", after="c")
    assert not d.absorb(err, before="c", after="d")  # budget spent
    assert REGISTRY.counter("oom_downshifts_total").value(
        site="t.site", cause="oom") == before + 2
    # Same site resolves to the same (process-global) budget.
    assert mg.downshifter("t.site") is d


def test_downshift_journal_rows(tmp_path):
    path = str(tmp_path / "recovery.jsonl")
    mg.set_journal(RecoveryJournal(path))
    d = mg.downshifter("t.journal")
    assert d.absorb(DeviceOomError("boom"),
                    before="newton_dual@4096", after="newton_dual@1024")
    rows = [json.loads(x) for x in open(path).read().splitlines()]
    assert len(rows) == 1
    assert rows[0]["event"] == "oom_downshift"
    assert rows[0]["site"] == "t.journal"
    assert rows[0]["before"] == "newton_dual@4096"
    assert rows[0]["after"] == "newton_dual@1024"
    assert rows[0]["cause"] == "oom"


def test_sticky_plan_roundtrip():
    assert mg.sticky_plan("re.solve") is None
    mg.set_sticky_plan("re.solve", {"chunk": 1024})
    assert mg.sticky_plan("re.solve") == {"chunk": 1024}
    mg.reset_state()
    assert mg.sticky_plan("re.solve") is None


def test_oom_next_tier_ladder(monkeypatch):
    """full -> next-smaller blessed chunk -> ... -> vmapped -> exhausted."""
    monkeypatch.setenv("PHOTON_RE_CHUNK_LADDER", "256,1024,4096")
    from photon_tpu.game.random_effect import _oom_next_tier

    e = 5000
    assert _oom_next_tier("newton_dual", None, e) == ("newton_dual", 4096)
    assert _oom_next_tier("newton_dual", 4096, e) == ("newton_dual", 1024)
    assert _oom_next_tier("newton_dual", 256, e) == ("vmapped_lbfgs", 256)
    assert _oom_next_tier("vmapped_lbfgs", 256, e) is None
    # Small buckets fall straight to the FULL vmapped solve.
    assert _oom_next_tier("newton_primal", None, 100) == (
        "vmapped_lbfgs", None)
    assert _oom_next_tier("vmapped_lbfgs", None, 100) is None
    # A big vmapped bucket still has chunked tiers below it.
    assert _oom_next_tier("vmapped_lbfgs", None, e) == (
        "vmapped_lbfgs", 4096)


def test_apply_sticky_plan_clamps():
    from photon_tpu.game.random_effect import _apply_sticky_plan

    assert _apply_sticky_plan(("newton_dual", None), None, 5000) == (
        "newton_dual", None)
    assert _apply_sticky_plan(
        ("newton_dual", None), {"chunk": 1024}, 5000) == (
        "newton_dual", 1024)
    # A bucket already under the cap keeps its full-bucket plan.
    assert _apply_sticky_plan(
        ("newton_dual", None), {"chunk": 1024}, 500) == ("newton_dual", None)
    assert _apply_sticky_plan(
        ("newton_primal", 4096),
        {"chunk": 256, "solver": "vmapped_lbfgs"}, 5000,
    ) == ("vmapped_lbfgs", 256)


# ------------------------------------------------------------------ watchdog


def test_memory_guard_thresholds():
    g = mg.MemoryGuard(stats_fn=_fake_stats(900), min_sample_interval_s=0.0)
    assert g.watermark() == pytest.approx(0.9)
    assert g.under_pressure() and not g.should_shed()
    g = mg.MemoryGuard(stats_fn=_fake_stats(990), min_sample_interval_s=0.0)
    before = REGISTRY.counter("memory_pressure_sheds_total").value()
    assert g.should_shed()
    assert REGISTRY.counter(
        "memory_pressure_sheds_total").value() == before + 1


def test_memory_guard_no_stats_backend_is_quiet():
    """CPU (no memory_stats): nothing sheds, nothing spills, gauges read
    0 watermark — the classified-OOM ladder alone carries the story."""
    g = mg.MemoryGuard(stats_fn=lambda: None, min_sample_interval_s=0.0)
    assert g.watermark() is None
    assert not g.under_pressure() and not g.should_shed()
    assert g.check() == {"available": False, "watermark": None,
                         "spilled_bytes": 0}


def test_memory_guard_exports_gauges():
    g = mg.MemoryGuard(stats_fn=_fake_stats(850), min_sample_interval_s=0.0)
    g.sample(force=True)
    assert REGISTRY.gauge("device_memory_bytes_in_use").value() == 850.0
    assert REGISTRY.gauge("device_memory_bytes_limit").value() == 1000.0
    assert REGISTRY.gauge("device_memory_watermark").value() == 0.85


def test_watchdog_spills_sweep_cache_pins_above_high_water():
    from photon_tpu.data.device_cache import DeviceSweepCache

    cache = DeviceSweepCache(budget_bytes=1 << 20)
    host = [np.zeros(64, np.float32) for _ in range(4)]
    for h in host:
        cache.get_or_put(("t", id(h)), h.nbytes,
                         lambda h=h: jnp.asarray(h), retain=h)
    assert cache.resident_bytes == 4 * 256
    # 900/1000 in use, high water 0.85 -> target: free >= 50 bytes; the
    # oldest pin (256 bytes) covers it.
    g = mg.MemoryGuard(stats_fn=_fake_stats(900), min_sample_interval_s=0.0)
    report = g.check()
    assert report["spilled_bytes"] >= 50
    assert cache.resident_bytes < 4 * 256
    # The spill is sticky: a re-lookup of the shed key streams (miss),
    # and does NOT re-pin.
    shed_key = ("t", id(host[0]))
    resident_after = cache.resident_bytes
    cache.get_or_put(shed_key, host[0].nbytes,
                     lambda: jnp.asarray(host[0]), retain=host[0])
    assert cache.resident_bytes == resident_after
    cache.release()


def test_shed_exempts_dataset_mirrors(rng):
    """Mirrors are identity-pinned (score/train identity contract) — the
    pressure valve must only spill chunk entries."""
    from photon_tpu.data.device_cache import DeviceSweepCache
    from photon_tpu.data.random_effect import build_random_effect_dataset
    from tests.test_random_effect import _make_entity_data

    idx, val, labels, keys = _make_entity_data(rng, n_entities=4)
    ds = build_random_effect_dataset(
        "userId", keys, idx, val, labels, global_dim=50,
        host_resident=True)
    cache = DeviceSweepCache(budget_bytes=1 << 24)
    mirror = cache.dataset_mirror(ds)
    h = np.zeros(64, np.float32)
    cache.get_or_put(("t", id(h)), h.nbytes, lambda: jnp.asarray(h),
                     retain=h)
    cache.shed(1 << 30)  # ask for everything
    # The chunk pin went; the mirror stayed — and stays the SAME object.
    assert cache.dataset_mirror(ds) is mirror
    stats = cache.stats()
    assert stats["entries"] == 1  # the mirror's entry survived
    cache.release()


# -------------------------------------------------------------- budget clamp


def test_effective_sweep_budget_clamps_to_device_limit(monkeypatch, caplog):
    monkeypatch.setenv("PHOTON_SWEEP_CACHE_DEVICE_FRACTION", "0.5")
    mg.guard().stats_fn = _fake_stats(100, limit=1000.0)
    mg.guard().min_sample_interval_s = 0.0
    import logging

    with caplog.at_level(logging.WARNING, logger="photon_tpu.memory_guard"):
        assert mg.effective_sweep_budget(10_000) == 500  # clamped
        assert mg.effective_sweep_budget(400) == 400     # fits
    warnings = [r for r in caplog.records if "clamping" in r.message]
    assert len(warnings) == 1  # one-time warning


def test_effective_sweep_budget_no_stats_keeps_requested():
    mg.guard().stats_fn = lambda: None
    mg.guard().min_sample_interval_s = 0.0
    assert mg.effective_sweep_budget(12345) == 12345


def test_pre_degrade_halves_budget_scale_and_caps_ladder(tmp_path):
    path = str(tmp_path / "recovery.jsonl")
    mg.set_journal(RecoveryJournal(path))
    mg.guard().stats_fn = lambda: None
    plan = mg.pre_degrade_for_restart("test oom")
    assert plan["sweep_cache_budget_scale"] == 0.5
    assert plan["re_chunk_cap"] in mg.sticky_plan("re.solve").values()
    # The degraded scale reaches a NEW cache's effective budget.
    assert mg.effective_sweep_budget(1000) == 500
    # Another pre-degrade steps one more tier down + halves again.
    plan2 = mg.pre_degrade_for_restart("again")
    assert plan2["sweep_cache_budget_scale"] == 0.25
    assert plan2["re_chunk_cap"] < plan["re_chunk_cap"]
    rows = [json.loads(x) for x in open(path).read().splitlines()]
    assert [r["event"] for r in rows] == ["oom_predegrade", "oom_predegrade"]


# ------------------------------------------------------------- supervisor


def test_supervisor_oom_restarts_once_predegraded_no_backoff(tmp_path):
    sleeps = []
    calls = []

    def attempt(i):
        calls.append(i)
        if i == 0:
            raise DeviceOomError("RESOURCE_EXHAUSTED: injected")
        # The retry runs PRE-DEGRADED: budget scale halved, ladder capped.
        assert mg.sweep_budget_scale() == 0.5
        assert mg.sticky_plan("re.solve") is not None
        return "survived"

    journal = str(tmp_path / "recovery.jsonl")
    # compile_store=None: this test pins the OOM journal sequence; a store
    # left active by another test would add its own prewarm row.
    sup = RunSupervisor(
        RestartPolicy(max_restarts=3, backoff_seconds=5.0, jitter=False),
        journal=journal, sleep=sleeps.append, compile_store=None,
    )
    assert sup.run(attempt) == "survived"
    assert calls == [0, 1]
    assert sleeps == []  # no backoff burned on a deterministic failure
    rows = [json.loads(x) for x in open(journal).read().splitlines()]
    events = [r["event"] for r in rows]
    assert events == ["attempt_start", "attempt_failed", "oom_predegrade",
                      "restart", "attempt_start", "run_ok"]
    restart = rows[events.index("restart")]
    assert restart["cause"] == "oom" and restart["backoff_s"] == 0.0


def test_supervisor_second_oom_escalates_classified(tmp_path):
    def doomed(i):
        raise DeviceOomError("RESOURCE_EXHAUSTED: still too big")

    journal = str(tmp_path / "recovery.jsonl")
    sup = RunSupervisor(
        RestartPolicy(max_restarts=5, backoff_seconds=0, jitter=False),
        journal=journal, sleep=lambda s: None, compile_store=None,
    )
    with pytest.raises(RestartsExhausted) as ei:
        sup.run(doomed)
    assert ei.value.cause == "oom"
    # Exactly ONE pre-degraded restart was attempted, despite the 5-deep
    # restart budget — the budget is for transients, not capacity walls.
    assert len(ei.value.failures) == 2
    rows = [json.loads(x) for x in open(journal).read().splitlines()]
    assert [r["event"] for r in rows] == [
        "attempt_start", "attempt_failed", "oom_predegrade", "restart",
        "attempt_start", "attempt_failed", "exhausted"]
    assert rows[-1]["cause"] == "oom"


def test_supervisor_oom_restart_rides_outside_transient_budget():
    """The one pre-degraded OOM restart is NOT charged against
    max_restarts: after it, genuine transients still get the full
    transient budget."""
    from photon_tpu.faults import DeviceLostError

    seq = [DeviceOomError("RESOURCE_EXHAUSTED: x"),
           DeviceLostError("lost"), DeviceLostError("lost")]
    calls = []

    def attempt(i):
        calls.append(i)
        if seq:
            raise seq.pop(0)
        return "ok"

    sup = RunSupervisor(
        RestartPolicy(max_restarts=2, backoff_seconds=0, jitter=False),
        sleep=lambda s: None, compile_store=None,
    )
    assert sup.run(attempt) == "ok"
    # 1 free OOM restart + the 2 budgeted transient restarts = 4 attempts.
    assert calls == [0, 1, 2, 3]


def test_supervisor_zero_budget_never_restarts_oom():
    """max_restarts=0 means never restart — the OOM carve-out does not
    override an operator's explicit no-restart policy."""
    def doomed(i):
        raise DeviceOomError("RESOURCE_EXHAUSTED: x")

    sup = RunSupervisor(RestartPolicy(max_restarts=0),
                        sleep=lambda s: None, compile_store=None)
    with pytest.raises(RestartsExhausted) as ei:
        sup.run(doomed)
    assert len(ei.value.failures) == 1 and ei.value.cause == "oom"


def test_supervisor_without_journal_preserves_outer_journal(tmp_path):
    """A journal-less supervisor must not detach a journal some outer
    component registered (set_journal save/restore contract)."""
    outer = RecoveryJournal(str(tmp_path / "outer.jsonl"))
    mg.set_journal(outer)
    sup = RunSupervisor(RestartPolicy(max_restarts=0),
                        sleep=lambda s: None, compile_store=None)
    assert sup.run(lambda i: "ok") == "ok"
    mg.downshifter("t.outer").absorb(DeviceOomError("b"),
                                     before="a", after="b")
    rows = open(outer.path).read().splitlines()
    assert rows and json.loads(rows[0])["event"] == "oom_downshift"


def test_run_with_recovery_skips_backoff_on_oom():
    sleeps = []
    calls = []

    def attempt(i):
        calls.append(i)
        if i == 0:
            raise DeviceOomError("boom")
        return "ok"

    assert run_with_recovery(
        attempt, RestartPolicy(max_restarts=1, backoff_seconds=7.0,
                               jitter=False),
        sleep=sleeps.append) == "ok"
    assert calls == [0, 1] and sleeps == []


# ------------------------------------------------- per-site ladder drills


def _re_problem():
    from photon_tpu.functions.problem import GLMOptimizationProblem
    from photon_tpu.optim import (
        OptimizerConfig,
        OptimizerType,
        RegularizationContext,
        RegularizationType,
    )
    from photon_tpu.types import TaskType

    return GLMOptimizationProblem(
        task=TaskType.LOGISTIC_REGRESSION,
        optimizer_config=OptimizerConfig(max_iterations=40),
        optimizer_type=OptimizerType.LBFGS,
        regularization=RegularizationContext(RegularizationType.L2),
        reg_weight=0.5,
    )


def _uniform_entity_data(rng, n_entities=9, rows=6, global_dim=50, k=6):
    """Every entity gets the same row count -> ONE bucket, so the faulted
    dispatch is the bucket whose downshift tier we control."""
    idx_rows, val_rows, labels, keys = [], [], [], []
    for e in range(n_entities):
        support = rng.choice(global_dim, size=8, replace=False)
        for _ in range(rows):
            cols = rng.choice(support, size=k, replace=False)
            vals = rng.normal(size=k)
            idx_rows.append(cols.astype(np.int64))
            val_rows.append(vals)
            labels.append(float(rng.random() < 0.5))
            keys.append(f"u{e}")
    return (np.asarray(idx_rows), np.asarray(val_rows),
            np.asarray(labels, np.float32), np.asarray(keys, object))


def test_re_solve_oom_downshifts_one_tier_same_result(rng, monkeypatch):
    """The tentpole RE drill at unit scale: an injected device_oom on the
    bucket dispatch downshifts one blessed chunk tier (sticky), completes
    WITHOUT escalating, and the coefficients match the uninterrupted run
    to 1e-12 (PR 4 chunked==full equivalence) — only the chunk tier
    changed, the solver family did not."""
    monkeypatch.setenv("PHOTON_RE_CHUNK_LADDER", "4,8")
    from photon_tpu.data.random_effect import build_random_effect_dataset
    from photon_tpu.game import train_random_effects

    problem = _re_problem()
    idx, val, labels, keys = _uniform_entity_data(rng, n_entities=9)
    # f64: the 1e-12 equivalence bound is a double-precision claim (the
    # f32 chunked-vs-full delta is batched-GEMM reassociation noise).
    ds = build_random_effect_dataset(
        "userId", keys, idx, val, labels, global_dim=50, dtype=np.float64)
    assert len(ds.buckets) == 1 and ds.buckets[0].n_entities == 9
    offsets = jnp.zeros((ds.n_rows,), jnp.float64)
    ref, _ = train_random_effects(problem, ds, offsets)

    mg.reset_state()
    before = REGISTRY.counter("oom_downshifts_total").value(
        site="re.solve", cause="oom")
    plan = FaultPlan(seed=0, specs=[
        FaultSpec(site="re.solve", error="device_oom", count=1)])
    with active_plan(plan) as inj:
        shifted, _ = train_random_effects(problem, ds, offsets)
    assert inj.fired("re.solve") == 1
    assert REGISTRY.counter("oom_downshifts_total").value(
        site="re.solve", cause="oom") == before + 1
    # Sticky: the surviving (downshifted) plan is recorded for the run —
    # one chunk tier below the full 9-entity bucket on the 4/8 ladder.
    assert mg.sticky_plan("re.solve") == {"chunk": 8, "solver": None}
    for a, b in zip(shifted.bucket_coefs, ref.bucket_coefs):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-12, rtol=0)


def test_measured_routing_oom_demotes_to_sticky_static_tier(
    rng, monkeypatch,
):
    """Under PHOTON_RE_ROUTING=measured an OOM out of the measured plan
    (or its calibration race) demotes to one tier below the STATIC plan —
    never a no-op or an up-shift — sticky, so later buckets skip the
    measured winner that cannot fit."""
    monkeypatch.setenv("PHOTON_RE_ROUTING", "measured")
    monkeypatch.setenv("PHOTON_RE_CHUNK_LADDER", "4,8")
    from photon_tpu.data.random_effect import build_random_effect_dataset
    from photon_tpu.game import solver_routing, train_random_effects

    solver_routing.reset_process_table()
    problem = _re_problem()
    idx, val, labels, keys = _uniform_entity_data(rng, n_entities=9)
    ds = build_random_effect_dataset(
        "userId", keys, idx, val, labels, global_dim=50)
    offsets = jnp.zeros((ds.n_rows,), jnp.float32)
    plan = FaultPlan(seed=0, specs=[
        FaultSpec(site="re.solve", error="device_oom", count=1)])
    try:
        with active_plan(plan) as inj:
            model, _ = train_random_effects(problem, ds, offsets)
        assert inj.fired("re.solve") == 1
        sticky = mg.sticky_plan("re.solve")
        assert sticky is not None and sticky["chunk"] == 8  # 9 -> tier 8
        assert np.isfinite(np.asarray(model.bucket_coefs[0])).all()
        # Later fits run on the sticky plan without re-racing the winner.
        train_random_effects(problem, ds, offsets)
    finally:
        solver_routing.reset_process_table()


def test_re_solve_oom_ladder_exhausted_escalates(rng, monkeypatch):
    """A device_oom on EVERY dispatch drains the ladder and the original
    classified error escalates (journaled exhaustion, no infinite loop)."""
    monkeypatch.setenv("PHOTON_RE_CHUNK_LADDER", "4,8")
    monkeypatch.setenv("PHOTON_OOM_MAX_DOWNSHIFTS", "8")
    from photon_tpu.data.random_effect import build_random_effect_dataset
    from photon_tpu.game import train_random_effects
    from tests.test_random_effect import _make_entity_data

    problem = _re_problem()
    idx, val, labels, keys = _make_entity_data(rng, n_entities=6)
    ds = build_random_effect_dataset(
        "userId", keys, idx, val, labels, global_dim=50)
    offsets = jnp.zeros((ds.n_rows,), jnp.float32)
    plan = FaultPlan(seed=0, specs=[
        FaultSpec(site="re.solve", error="device_oom")])  # every dispatch
    with active_plan(plan):
        with pytest.raises(DeviceOomError):
            train_random_effects(problem, ds, offsets)
    assert bg.classify_backend_error(
        DeviceOomError("x")) == bg.CAUSE_OOM  # escalates classified


def test_ooc_rechunk_preserves_rows():
    from photon_tpu.optim.out_of_core import ChunkedGLMData

    rng = np.random.default_rng(0)
    n, dim, k = 37, 20, 4
    idx = rng.integers(0, dim, size=(n, k)).astype(np.int32)
    val = rng.normal(size=(n, k)).astype(np.float32)
    labels = rng.normal(size=n).astype(np.float32)
    data = ChunkedGLMData.from_arrays(idx, val, labels, dim, chunk_rows=16)
    half = data.rechunk(2)
    assert half.chunk_rows == 8 and half.n_rows == n
    assert half.n_chunks == 2 * data.n_chunks
    # Row content (true rows + ghost convention) is preserved exactly.
    def flatten(d):
        i = np.concatenate([c.idx for c in d.chunks])
        v = np.concatenate([c.val for c in d.chunks])
        w = np.concatenate([np.asarray(x) for x in d.weights])
        real = w > 0
        return i[real], v[real]

    for a, b in zip(flatten(data), flatten(half)):
        np.testing.assert_array_equal(a, b)
    with pytest.raises(ValueError):
        ChunkedGLMData.from_arrays(idx, val, labels, dim,
                                   chunk_rows=1).rechunk(2)


def test_ooc_oom_halves_chunk_rows_and_completes():
    """An injected device_oom on a streamed chunk feed re-cuts the data at
    half chunk_rows and the solve completes at the same optimum (the cut
    only changes accumulation grouping)."""
    from photon_tpu.optim.out_of_core import ChunkedGLMData, OutOfCoreLBFGS
    from photon_tpu.ops.losses import loss_for_task
    from photon_tpu.types import TaskType

    rng = np.random.default_rng(1)
    n, dim, k = 256, 30, 4
    idx = rng.integers(0, dim, size=(n, k)).astype(np.int32)
    val = (rng.normal(size=(n, k)) / 2).astype(np.float32)
    z = val.sum(1)
    labels = (rng.random(n) < 1 / (1 + np.exp(-z))).astype(np.float32)
    solver = OutOfCoreLBFGS(
        loss=loss_for_task(TaskType.LOGISTIC_REGRESSION), l2_weight=1.0)
    data = ChunkedGLMData.from_arrays(idx, val, labels, dim, chunk_rows=64)
    # The reference is the uninterrupted run AT THE DOWNSHIFTED CUT: the
    # re-cut regroups f32 accumulation, so the honest equivalence claim is
    # against the same chunking (the optimum agrees to solver tolerance
    # either way — asserted on the objective below).
    ref = solver.optimize(
        ChunkedGLMData.from_arrays(idx, val, labels, dim, chunk_rows=32),
        jnp.zeros(dim))
    full = solver.optimize(data, jnp.zeros(dim))

    before = REGISTRY.counter("oom_downshifts_total").value(
        site="optim.ooc_chunk", cause="oom")
    data2 = ChunkedGLMData.from_arrays(idx, val, labels, dim, chunk_rows=64)
    plan = FaultPlan(seed=0, specs=[
        FaultSpec(site="optim.ooc_chunk", error="device_oom", count=1)])
    with active_plan(plan) as inj:
        shifted = solver.optimize(data2, jnp.zeros(dim))
    assert inj.fired("optim.ooc_chunk") == 1
    assert REGISTRY.counter("oom_downshifts_total").value(
        site="optim.ooc_chunk", cause="oom") == before + 1
    # Bit-identical to the uninterrupted half-cut run (the fault fired
    # before any step committed), and at the same optimum as the full cut.
    np.testing.assert_array_equal(np.asarray(shifted.x), np.asarray(ref.x))
    assert abs(float(shifted.value) - float(full.value)) < 1e-6
    # The same optimum, as far as float32 can say. Two float32 solves that
    # differ in chunking stop where the objective (149.4, spacing 1.5e-5)
    # no longer resolves a decrease, 1e-3 apart in the coefficients here
    # and 4e-16 in float64. So each solution is held by its own gradient,
    # in float64 on the host: OutOfCoreLBFGS adds 0.5 * l2_weight * |w|^2,
    # unscaled, to the SUM of the row losses, so the objective is strongly
    # convex with modulus l2_weight and a point lies within |grad| / modulus
    # of the optimum; the two then lie within the sum of those of each other.
    x = np.zeros((n, dim))
    np.add.at(x, (np.arange(n)[:, None], idx), val.astype(np.float64))

    def radius(w):
        w = np.asarray(w, np.float64)
        grad = x.T @ (1 / (1 + np.exp(-(x @ w))) - labels) + solver.l2_weight * w
        return np.linalg.norm(grad) / solver.l2_weight

    r_shifted, r_full = radius(shifted.x), radius(full.x)
    # 0.5 * modulus * d^2 = one spacing of the objective at d = 5.5e-3.
    assert r_shifted < 1e-2 and r_full < 1e-2
    assert np.linalg.norm(np.asarray(shifted.x, np.float64)
                          - np.asarray(full.x, np.float64)) <= r_shifted + r_full


def test_ooc_oom_exhausted_escalates(monkeypatch):
    monkeypatch.setenv("PHOTON_OOM_MAX_DOWNSHIFTS", "1")
    from photon_tpu.optim.out_of_core import ChunkedGLMData, OutOfCoreLBFGS
    from photon_tpu.ops.losses import loss_for_task
    from photon_tpu.types import TaskType

    rng = np.random.default_rng(2)
    idx = rng.integers(0, 10, size=(32, 3)).astype(np.int32)
    val = rng.normal(size=(32, 3)).astype(np.float32)
    labels = rng.normal(size=32).astype(np.float32)
    solver = OutOfCoreLBFGS(
        loss=loss_for_task(TaskType.LINEAR_REGRESSION), l2_weight=1.0)
    data = ChunkedGLMData.from_arrays(idx, val, labels, 10, chunk_rows=16)
    plan = FaultPlan(seed=0, specs=[
        FaultSpec(site="optim.ooc_chunk", error="device_oom")])  # always
    with active_plan(plan):
        with pytest.raises(DeviceOomError):
            solver.optimize(data, jnp.zeros(10))
