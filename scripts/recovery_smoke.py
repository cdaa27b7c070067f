"""Recovery smoke (ci.sh stage; docs/robustness.md §backend resilience).

Exercises the fail-fast backend contract end to end WITHOUT a chip, using
the probe's injection seam (``probe_code`` runs arbitrary child code):

1. an injected init HANG is killed at the configured deadline — seconds —
   and classified ``init_unavailable``;
2. an injected ``Unable to initialize backend: UNAVAILABLE`` init failure
   (the recovery log's literal signature) classifies ``init_unavailable``;
3. an injected RESOURCE_EXHAUSTED death classifies ``oom``;
4. ``ensure_backend`` enforces the policy ladder on a failing probe:
   ``strict`` raises a classified ``BackendUnusable``; ``failover``
   re-enters on CPU and stamps the swap into the guard snapshot;
5. a ``RunSupervisor`` drill: a flaky attempt restarts with the cause
   classified and journaled (valid JSONL rows, ``run_restarts_total``
   counter bumped), then an always-failing attempt exhausts the budget
   and surfaces the last classified cause;
6. a WARM-RESTART drill (docs/robustness.md §"Recovery time"): a real
   kernel compiles cold into the AOT compile store
   (the persistent cache is the artifact layer — ci.sh names a fresh
   dir through ``$JAX_COMPILATION_CACHE_DIR`` so the cold half really is
   cold), the attempt dies on a
   device loss after the executable caches clear, and the supervisor's
   pre-warmed retry must journal ``restart_to_first_step_seconds`` with
   the pre-warm's XLA share BELOW its I/O share and ZERO kernel re-traces
   on the restarted attempt.
"""
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from photon_tpu.runtime import backend_guard as bg  # noqa: E402
from photon_tpu.supervisor import (  # noqa: E402
    RecoveryJournal,
    RestartPolicy,
    RestartsExhausted,
    RunSupervisor,
)

HANG = "import time; time.sleep(600)"
UNAVAILABLE = (
    "import sys; sys.stderr.write('RuntimeError: Unable to initialize "
    "backend: UNAVAILABLE: TPU backend setup/compile error\\n'); sys.exit(1)"
)
OOM = (
    "import sys; sys.stderr.write('RESOURCE_EXHAUSTED: out of memory "
    "allocating 16G\\n'); sys.exit(1)"
)


def check(cond, msg):
    if not cond:
        print(f"RECOVERY SMOKE FAILED: {msg}", file=sys.stderr)
        sys.exit(1)
    print(f"  ok: {msg}")


def main() -> None:
    print("== injected init-hang dies at the deadline ==")
    t0 = time.monotonic()
    r = bg.probe_backend(timeout_s=2.0, probe_code=HANG)
    took = time.monotonic() - t0
    check(not r.ok, "hanging probe reported failure")
    check(took < 30.0, f"killed at the deadline ({took:.1f}s, not ~1500s)")
    check(r.cause == bg.CAUSE_INIT_UNAVAILABLE,
          f"hang classified init_unavailable (got {r.cause})")

    print("== injected UNAVAILABLE init classifies ==")
    r = bg.probe_backend(timeout_s=30.0, probe_code=UNAVAILABLE)
    check(not r.ok and r.cause == bg.CAUSE_INIT_UNAVAILABLE,
          f"UNAVAILABLE classified init_unavailable (got {r.cause})")

    print("== injected OOM init classifies ==")
    r = bg.probe_backend(timeout_s=30.0, probe_code=OOM)
    check(not r.ok and r.cause == bg.CAUSE_OOM,
          f"RESOURCE_EXHAUSTED classified oom (got {r.cause})")

    print("== policy ladder on a failing probe ==")
    bg.reset_guard()
    try:
        bg.ensure_backend(policy="strict", timeout_s=30.0,
                          probe_code=UNAVAILABLE)
        check(False, "strict raised BackendUnusable")
    except bg.BackendUnusable as e:
        check(e.cause == bg.CAUSE_INIT_UNAVAILABLE,
              f"strict raised classified BackendUnusable ({e.cause})")
    bg.reset_guard()
    snap = bg.ensure_backend(policy="failover", timeout_s=30.0,
                             probe_code=UNAVAILABLE)
    check(snap["backend"] == "cpu" and snap["failover"] is not None,
          "failover re-entered on CPU with the swap stamped")
    check(snap["failover"]["cause"] == bg.CAUSE_INIT_UNAVAILABLE,
          "failover event carries the classified cause")
    bg.reset_guard()

    print("== RunSupervisor drill: classified restart + journal ==")
    from photon_tpu.faults import DeviceLostError
    from photon_tpu.obs.metrics import REGISTRY

    with tempfile.TemporaryDirectory() as td:
        journal_path = os.path.join(td, "recovery.jsonl")
        calls = []

        def flaky(i):
            calls.append(i)
            if i == 0:
                raise DeviceLostError("chip fell off the bus")
            return "recovered"

        before = REGISTRY.counter("run_restarts_total").value(
            cause="device_lost")
        sup = RunSupervisor(
            RestartPolicy(max_restarts=2, backoff_seconds=0, jitter=False),
            journal=RecoveryJournal(journal_path),
            sleep=lambda s: None,
        )
        check(sup.run(flaky) == "recovered" and calls == [0, 1],
              "one classified restart, then success")
        after = REGISTRY.counter("run_restarts_total").value(
            cause="device_lost")
        check(after == before + 1,
              'run_restarts_total{cause="device_lost"} bumped')
        rows = [json.loads(line)
                for line in open(journal_path).read().splitlines()]
        events = [r["event"] for r in rows]
        check(events == ["attempt_start", "attempt_failed", "restart",
                         "attempt_start", "run_ok"],
              f"journal tells the whole story ({events})")
        check(rows[1]["cause"] == "device_lost",
              "journaled failure carries the classified cause")

        def doomed(i):
            raise RuntimeError("Unable to initialize backend: UNAVAILABLE")

        sup2 = RunSupervisor(
            RestartPolicy(max_restarts=1, backoff_seconds=0, jitter=False),
            journal=RecoveryJournal(os.path.join(td, "r2.jsonl")),
            sleep=lambda s: None,
        )
        try:
            sup2.run(doomed)
            check(False, "exhausted budget raised RestartsExhausted")
        except RestartsExhausted as e:
            check(e.cause == bg.CAUSE_INIT_UNAVAILABLE,
                  f"exhaustion surfaces the last classified cause "
                  f"({e.cause})")

    warm_restart_drill()
    oom_drill()

    print("recovery smoke ok")


def warm_restart_drill() -> None:
    """Zero-recompile warm restart, end to end (docs/robustness.md
    §"Recovery time"): cold compile → record → device loss + cache clear →
    supervisor pre-warm from the store → restarted attempt re-dispatches
    with NO new kernel trace, journaling restart_to_first_step_seconds and
    a prewarm row whose XLA share sits below its I/O share."""
    import jax.numpy as jnp
    import numpy as np

    from photon_tpu.data.batch import LabeledBatch, SparseFeatures
    from photon_tpu.faults import DeviceLostError
    from photon_tpu.functions.problem import GLMOptimizationProblem
    from photon_tpu.obs import retrace
    from photon_tpu.optim import (
        OptimizerConfig,
        RegularizationContext,
        RegularizationType,
    )
    from photon_tpu.runtime import compile_store as cs
    from photon_tpu.supervisor import clear_executable_caches
    from photon_tpu.types import TaskType

    print("== warm-restart drill: compile store + supervisor pre-warm ==")
    # The persistent cache is the artifact layer; cs.configure below
    # switches it on where runtime/compile_store.compilation_cache_dir puts it (ci.sh
    # names a fresh dir through $JAX_COMPILATION_CACHE_DIR).

    rng = np.random.default_rng(0)
    n, d, k = 4096, 64, 6
    idx = rng.integers(0, d, size=(n, k)).astype(np.int32)
    val = rng.normal(size=(n, k)).astype(np.float32)
    y = (rng.random(n) < 0.5).astype(np.float32)
    batch = LabeledBatch(
        features=SparseFeatures(jnp.asarray(idx), jnp.asarray(val), d),
        labels=jnp.asarray(y), offsets=jnp.zeros(n), weights=jnp.ones(n))
    problem = GLMOptimizationProblem(
        task=TaskType.LOGISTIC_REGRESSION,
        regularization=RegularizationContext(RegularizationType.L2),
        reg_weight=1.0, optimizer_config=OptimizerConfig(max_iterations=10))
    w0 = jnp.zeros(d)

    with tempfile.TemporaryDirectory() as td:
        store = cs.configure(os.path.join(td, "store"))
        journal_path = os.path.join(td, "recovery.jsonl")
        traces_in_attempt = {}

        def attempt(i):
            t_before = retrace.traces("glm_fit")
            model, _ = problem.fit(batch, w0)
            np.asarray(model.coefficients.means[:1])  # completed-solve sync
            traces_in_attempt[i] = retrace.traces("glm_fit") - t_before
            cs.note_first_step("smoke.step")
            if i == 0:
                # The device dies AND takes every compiled executable with
                # it — the exact state a restart re-enters from.
                clear_executable_caches("smoke: injected device loss")
                raise DeviceLostError("injected: chip fell off the bus")
            return np.asarray(model.coefficients.means)

        sup = RunSupervisor(
            RestartPolicy(max_restarts=1, backoff_seconds=0, jitter=False),
            journal=RecoveryJournal(journal_path),
            sleep=lambda s: None,
            compile_store=store,
        )
        coefs = sup.run(attempt)
        check(np.isfinite(coefs).all(), "restarted attempt solved")
        check(traces_in_attempt[0] >= 1, "attempt 0 compiled cold")
        check(traces_in_attempt[1] == 0,
              "restarted attempt re-traced NOTHING (pre-warm made the "
              "dispatch warm)")

        rows = [json.loads(x)
                for x in open(journal_path).read().splitlines()]
        prewarms = [r for r in rows if r["event"] == "prewarm"]
        check(len(prewarms) == 1, "supervisor journaled one prewarm row")
        pw = prewarms[0]
        check(pw["loaded"] >= 1,
              f"prewarm LOADED from the store ({pw['loaded']} loaded, "
              f"{pw['compiled']} compiled)")
        check(pw["xla_seconds"] < max(pw["load_seconds"], 1e-9),
              f"warm restart XLA share below I/O share "
              f"(xla {pw['xla_seconds']}s vs load {pw['load_seconds']}s)")
        firsts = [r for r in rows if r["event"] == "first_step"]
        check(len(firsts) == 2 and all(
            "restart_to_first_step_seconds" in r for r in firsts),
            "restart_to_first_step_seconds journaled per attempt")
        check(firsts[-1]["restart_to_first_step_seconds"]
              < firsts[0]["restart_to_first_step_seconds"],
              f"warm restart beat the cold one "
              f"({firsts[-1]['restart_to_first_step_seconds']}s vs "
              f"{firsts[0]['restart_to_first_step_seconds']}s)")


def oom_drill() -> None:
    """OOM degradation-ladder drill (docs/robustness.md §"Memory
    pressure"): an injected ``device_oom`` at the RE bucket dispatch of a
    SUPERVISED run must be absorbed by a chunk-tier downshift — exactly
    ONE ``oom_downshift`` journal row, ZERO supervisor restarts, the run
    completes, and the result matches the uninterrupted run to 1e-12 (the
    PR 4 chunked==full equivalence; the drill is f64)."""
    import jax.numpy as jnp
    import numpy as np

    from photon_tpu.data.random_effect import build_random_effect_dataset
    from photon_tpu.faults import FaultPlan, FaultSpec, active_plan
    from photon_tpu.functions.problem import GLMOptimizationProblem
    from photon_tpu.game import train_random_effects
    from photon_tpu.obs.metrics import REGISTRY
    from photon_tpu.optim import (
        OptimizerConfig,
        OptimizerType,
        RegularizationContext,
        RegularizationType,
    )
    from photon_tpu.runtime import memory_guard as mg
    from photon_tpu.supervisor import RunSupervisor
    from photon_tpu.types import TaskType

    print("== OOM drill: downshift-not-restart ==")
    import jax

    jax.config.update("jax_enable_x64", True)
    rng = np.random.default_rng(3)
    n_entities, rows, k, dim = 12, 6, 4, 40
    idx_rows, val_rows, labels, keys = [], [], [], []
    for e in range(n_entities):
        support = rng.choice(dim, size=2 * k, replace=False)
        for _ in range(rows):
            cols = rng.choice(support, size=k, replace=False)
            idx_rows.append(cols.astype(np.int64))
            val_rows.append(rng.normal(size=k))
            labels.append(float(rng.random() < 0.5))
            keys.append(f"u{e}")
    ds = build_random_effect_dataset(
        "userId", np.asarray(keys, object), np.asarray(idx_rows),
        np.asarray(val_rows), np.asarray(labels, np.float64),
        global_dim=dim, dtype=np.float64)
    problem = GLMOptimizationProblem(
        task=TaskType.LOGISTIC_REGRESSION,
        optimizer_config=OptimizerConfig(max_iterations=40),
        optimizer_type=OptimizerType.LBFGS,
        regularization=RegularizationContext(RegularizationType.L2),
        reg_weight=1.0,
    )
    offsets = jnp.zeros((ds.n_rows,), jnp.float64)
    prev_ladder = os.environ.get("PHOTON_RE_CHUNK_LADDER")
    os.environ["PHOTON_RE_CHUNK_LADDER"] = "4,8"  # a tier below 12 entities
    mg.reset_state()
    try:
        ref, _ = train_random_effects(problem, ds, offsets)
        mg.reset_state()
        restarts0 = sum(
            v for _, v in REGISTRY.counter("run_restarts_total").collect())
        shifts0 = REGISTRY.counter("oom_downshifts_total").value(
            site="re.solve", cause="oom")
        with tempfile.TemporaryDirectory() as td:
            journal_path = os.path.join(td, "recovery.jsonl")
            attempts = []

            def attempt(i):
                attempts.append(i)
                return train_random_effects(problem, ds, offsets)

            plan = FaultPlan(seed=0, specs=[
                FaultSpec(site="re.solve", error="device_oom", count=1)])
            sup = RunSupervisor(journal=journal_path, sleep=lambda s: None)
            with active_plan(plan) as inj:
                model, _ = sup.run(attempt)
            check(inj.fired("re.solve") == 1, "the device_oom really fired")
            check(attempts == [0],
                  "ZERO supervisor restarts (downshift-not-restart)")
            check(sum(v for _, v in REGISTRY.counter(
                "run_restarts_total").collect()) == restarts0,
                "run_restarts_total unmoved")
            shifts = REGISTRY.counter("oom_downshifts_total").value(
                site="re.solve", cause="oom") - shifts0
            check(shifts == 1,
                  f"oom_downshifts_total matches the injection count "
                  f"({int(shifts)})")
            rows_j = [json.loads(x)
                      for x in open(journal_path).read().splitlines()]
            downshifts = [r for r in rows_j
                          if r["event"] == "oom_downshift"]
            check(len(downshifts) == 1,
                  "exactly one oom_downshift journal row")
            check(downshifts[0]["site"] == "re.solve"
                  and downshifts[0]["cause"] == "oom",
                  f"journal row carries site+cause "
                  f"({downshifts[0]['before']} -> "
                  f"{downshifts[0]['after']})")
            diff = max(
                float(np.abs(np.asarray(a) - np.asarray(b)).max())
                for a, b in zip(model.bucket_coefs, ref.bucket_coefs))
            check(diff <= 1e-12,
                  f"downshifted result within 1e-12 of the uninterrupted "
                  f"run (max diff {diff:.2e})")
    finally:
        if prev_ladder is None:
            os.environ.pop("PHOTON_RE_CHUNK_LADDER", None)
        else:
            os.environ["PHOTON_RE_CHUNK_LADDER"] = prev_ladder
        mg.reset_state()


if __name__ == "__main__":
    main()
