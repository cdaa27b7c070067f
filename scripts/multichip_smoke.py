"""Multichip smoke (ci.sh stage; docs/scaling.md §"Device mesh").

``MULTICHIP_r0x`` graduated from an rc-check into a real harness: 8
forced host devices exercise the mesh-sharded GAME training path end to
end WITHOUT a chip (ROADMAP item 1 acceptance, run mechanically on every
CI pass):

1. sharded ``game_scale`` (the ``bench.py`` game_scale mesh leg at smoke
   shapes): the 1-device and entity-sharded arms are pinned to the SAME
   chunked-Newton tier by a scoped ladder + budget, and the harness
   asserts the mesh arm ran on all 8 devices with ZERO retraces after
   warmup, the chunked Newton tiers (not the vmapped fallback) carrying
   >= 90% of routed rows, and the two arms' coefficients agreeing.
   Scaling efficiency is ASSERTED only when the host has at least as
   many cores as devices — on a smaller box the 8 virtual devices
   timeshare the cores and efficiency reads ~cores/devices by
   construction, so it is printed + stamped (``host_cpu_count``) but
   cannot gate;
2. the single-shard device-loss drill (docs/robustness.md §"Shard
   loss"): one injected ``device_lost`` mid-sweep must redistribute that
   shard's entities over the surviving devices and complete the sweep in
   the SAME process — a classified ``shard_lost`` row in the recovery
   journal, results within 1e-12 of the uninterrupted mesh run at f64,
   and the degradation sticky so the next sweep starts on the surviving
   mesh instead of re-failing.
"""
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
# The bench mesh leg sizes its fixture from bench.SMOKE; the harness always
# runs toy shapes (real figures come from the driver's bench runs).
os.environ["PHOTON_BENCH_SMOKE"] = "1"

import jax  # noqa: E402

# Pin the CPU whatever JAX_PLATFORMS says; the smoke must never claim a
# chip.
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def check(cond, msg):
    if not cond:
        print(f"MULTICHIP SMOKE FAILED: {msg}", file=sys.stderr)
        sys.exit(1)
    print(f"  ok: {msg}")


def sharded_game_scale() -> None:
    """The bench game_scale mesh leg, with its correctness claims gated."""
    import bench

    out = bench._game_scale_mesh()
    note = out.get("game_scale_mesh_note")
    check(note is None, f"mesh leg ran (no skip note){f': {note}' if note else ''}")
    n_dev = out["game_scale_mesh_devices"]
    cores = out["game_scale_mesh_host_cpu_count"]
    eff = out["game_scale_mesh_re_scaling_efficiency"]
    print(f"  figures: devices={n_dev} cores={cores} "
          f"1dev={out['game_scale_mesh_re_step_seconds_1dev']}s "
          f"mesh={out['game_scale_mesh_re_step_seconds']}s "
          f"scaling={out['game_scale_mesh_re_scaling_x']}x "
          f"efficiency={eff} plans={out['game_scale_mesh_plans']}")
    check(n_dev == 8, f"8 forced host devices (got {n_dev})")
    check(out["game_scale_mesh_retraces_after_warmup"] == 0,
          "zero RE-solver retraces after warmup under the mesh")
    frac = out["game_scale_mesh_chunked_newton_row_fraction"]
    check(frac >= 0.9,
          f"chunked Newton tiers carry >=90% of routed rows ({frac})")
    gap = out["game_scale_mesh_vs_1dev_coef_gap"]
    check(gap < 1e-3, f"mesh coefficients match 1-device arm (gap {gap:.2e}"
          " at f32 reduction noise)")
    if cores is not None and cores >= n_dev:
        check(eff >= 0.6,
              f"RE-step scaling efficiency >= 0.6x ideal ({eff})")
    else:
        print(f"  note: {cores} core(s) < {n_dev} devices — virtual devices "
              f"timeshare the host, efficiency {eff} is structural, not "
              "asserted (the multi-core rig of record gates it)")


def shard_loss_drill() -> None:
    """One lost shard mid-sweep: redistribute, complete, journal — no
    process restart. Mirrors tests/test_mesh_invariance.py's chaos drill
    so the contract also holds in this harness's fresh process."""
    from photon_tpu.data.random_effect import build_random_effect_dataset
    from photon_tpu.faults import FaultPlan, FaultSpec, active_plan
    from photon_tpu.functions.problem import GLMOptimizationProblem
    from photon_tpu.game.random_effect import train_random_effects
    from photon_tpu.obs.metrics import REGISTRY
    from photon_tpu.optim import (
        OptimizerConfig,
        OptimizerType,
        RegularizationContext,
        RegularizationType,
    )
    from photon_tpu.parallel.mesh import make_mesh
    from photon_tpu.runtime import memory_guard as mg
    from photon_tpu.supervisor import RecoveryJournal
    from photon_tpu.types import TaskType

    rng = np.random.default_rng(7)
    n_entities, rows, dim, k = 37, 6, 24, 4  # ragged over 8 devices
    n = n_entities * rows
    keys = np.asarray([f"e{i // rows}" for i in range(n)])
    ds = build_random_effect_dataset(
        "e", keys,
        rng.integers(0, dim, size=(n, k)).astype(np.int32),
        rng.normal(size=(n, k)),
        rng.random(n).astype(np.float64),
        global_dim=dim, dtype=np.float64)
    problem = GLMOptimizationProblem(
        task=TaskType.LOGISTIC_REGRESSION,
        optimizer_type=OptimizerType.LBFGS,
        optimizer_config=OptimizerConfig(max_iterations=60),
        regularization=RegularizationContext(RegularizationType.L2),
        reg_weight=0.3,
    )
    offsets = jnp.zeros((ds.n_rows,), jnp.float64)
    mesh = make_mesh()
    m_ok, _ = train_random_effects(problem, ds, offsets, mesh=mesh)

    mg.reset_state()
    losses0 = REGISTRY.counter("re_shard_losses_total").value()
    with tempfile.TemporaryDirectory() as td:
        journal_path = os.path.join(td, "recovery.jsonl")
        prev = mg.set_journal(RecoveryJournal(journal_path))
        try:
            plan = FaultPlan(specs=[
                FaultSpec(site="re.shard", error="device_lost", count=1)])
            with active_plan(plan) as inj:
                m_rec, _ = train_random_effects(
                    problem, ds, offsets, mesh=mesh)
            check(inj.fired("re.shard") == 1, "exactly one shard lost")
        finally:
            mg.set_journal(prev)
        with open(journal_path) as f:
            rows_j = [json.loads(line) for line in f]
    shard_rows = [r for r in rows_j if r["event"] == "shard_lost"]
    check(len(shard_rows) == 1, "one classified shard_lost journal row")
    r = shard_rows[0]
    check(r["cause"] == "device_lost" and r["site"] == "re.shard",
          f"row classified (cause={r['cause']}, site={r['site']})")
    check(r["devices_after"] < r["devices_before"],
          f"entities redistributed onto survivors "
          f"({r['devices_before']} -> {r['devices_after']} devices)")
    check(REGISTRY.counter("re_shard_losses_total").value() == losses0 + 1,
          "re_shard_losses_total bumped once")
    worst = max(
        float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
        for a, b in zip(m_ok.bucket_coefs, m_rec.bucket_coefs))
    check(worst <= 1e-12,
          f"degraded sweep within 1e-12 of uninterrupted ({worst:.2e})")
    check(mg.sticky_plan("re.shard") == {"shards": 4},
          "degradation sticky for the run (next sweeps start on 4 shards)")
    m_next, _ = train_random_effects(problem, ds, offsets, mesh=mesh)
    worst = max(
        float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
        for a, b in zip(m_ok.bucket_coefs, m_next.bucket_coefs))
    check(worst <= 1e-12,
          f"next sweep completes degraded without re-failing ({worst:.2e})")
    mg.reset_state()


def main() -> None:
    print("== multichip smoke: sharded game_scale (8 forced host devices) ==")
    sharded_game_scale()
    print("== multichip smoke: single-shard device-loss drill ==")
    shard_loss_drill()
    print("MULTICHIP SMOKE GREEN")


if __name__ == "__main__":
    main()
