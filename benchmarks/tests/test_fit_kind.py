"""The ``fit`` kind end to end on the CPU at a tiny size: a well-formed
result line that names the device it ran on; the refusal of a CPU at the
real entry; and ``correct`` coming out false for the control and for each
fault a one-chip training cell can have (PERF.md §2)."""
import json
import os
import subprocess
import sys

import pytest

from benchmarks import run as bench_run
from benchmarks.kinds import fit
from benchmarks.tests import faults
from benchmarks.tests.conftest import ROOT, load, tiny

CELLS = {"glm_fit": "glm-logistic-l2", "game_fit": "game-logistic-user-re"}


def run_tiny(cell_name, bench, mix, seed=5, seconds=0.2, limits=None):
    cell = next(w for w in bench["workloads"] if w["name"] == cell_name)
    config = tiny(load("benchmarks", "configs", CELLS[cell_name] + ".json"))
    if limits is None:
        limits = load("benchmarks", "limits", cell_name + ".json")
    lines = []
    line = bench_run.run_cell(bench, cell, config, mix, limits, seed, seconds,
                              False, lines.append)
    return line, lines


@pytest.mark.parametrize("cell_name", sorted(CELLS))
def test_result_line_is_well_formed_and_names_the_cpu(cell_name, bench, mix):
    line, earlier = run_tiny(cell_name, bench, mix)
    line = json.loads(json.dumps(line))               # it is JSON
    assert list(line)[-1] == "compared"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] >= 1
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    wanted = {m["name"] for m in bench["end_to_end"]
              if bench_run.applies(m, {"name": cell_name}, bench)}
    assert set(line["metrics"]) == wanted and "setup_s" in wanted
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    for value, limit in line["compared"].values():
        assert value <= limit
    assert any("setup" in e for e in earlier) and any("trackers" in e for e in earlier)


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_a_planted_fault_is_not_correct(fault, bench, mix):
    with faults.FAULTS[fault]():
        line, _ = run_tiny("game_fit", bench, mix)
    failed = [n for n, (v, lim) in line["compared"].items() if not v <= lim]
    assert line["correct"] is False and failed, line["compared"]


@pytest.mark.parametrize("fault", ["unchanged_fixed", "half_batch", "altered",
                                   "short_memory", "stops_early"])
def test_a_planted_fault_is_not_correct_in_the_glm_cell(fault, bench, mix):
    with faults.FAULTS[fault]():
        line, _ = run_tiny("glm_fit", bench, mix)
    assert line["correct"] is False, line["compared"]


@pytest.mark.parametrize("cell_name", sorted(CELLS))
def test_the_bfloat16_control_is_not_correct(cell_name, bench, mix):
    """The control at a size a test can hold: the program's own
    lower-precision path (feature values stored in bfloat16 and widened on
    load), switched on where the program switches it."""
    sound, _ = run_tiny(cell_name, bench, mix, seed=9)
    with faults.CONTROL():
        control, _ = run_tiny(cell_name, bench, mix, seed=9)
    assert sound["correct"] is True, sound["compared"]
    assert control["correct"] is False, control["compared"]
    value, limit = control["compared"]["scores"]
    assert value > limit


def test_probe_removes_its_wrappers():
    from photon_tpu.game import coordinates as co

    before = co.FixedEffectCoordinate.train
    probe = fit.Probe()
    probe.install()
    assert co.FixedEffectCoordinate.train is not before
    probe.remove()
    assert co.FixedEffectCoordinate.train is before


def test_the_entry_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "glm_fit", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs 1 accelerator" in p.stderr


def test_every_metric_configuration_and_mix_has_its_file(bench):
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "layer_metrics", m["name"] + ".py")), m["name"]
    for c in bench["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
    for w in bench["workloads"]:
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "traffic", w["traffic"] + ".json"))
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "limits", w["name"] + ".json"))


def test_per_layer_readers_on_a_tiny_state(bench, mix):
    """Every reader reads a state of the shape ``kinds/fit.py`` returns; a
    reader with nothing to read returns nothing."""
    import importlib

    cell = next(w for w in bench["workloads"] if w["name"] == "game_fit")
    config = tiny(load("benchmarks", "configs", CELLS["game_fit"] + ".json"))
    limits = load("benchmarks", "limits", "game_fit.json")
    out = fit.run(cell, config, mix, limits, 4, 0.2, None,
                  bench_run.T_START, lambda o: None)
    peak = load("benchmarks", "peaks.json")["TPU v5 lite"]
    window = out["state"]["window_s"]
    state = dict(out["state"], peak=peak, trace={
        "busy_s": 0.25 * window, "window_s": window,
        "module_s": {"jit__fit_jitted": 0.1 * window}})
    values = bench_run.per_layer(bench, cell, state)
    # the CPU reports no memory peak, so that reader finds nothing to read
    assert set(values) == {m["name"] for m in bench["per_layer"]
                           if bench_run.applies(m, cell, bench)} - {"peak_hbm_gb"}
    assert values["device_idle_pct"]["value"] == pytest.approx(75.0)
    assert 0 < values["fit_mfu"]["value"] < 100
    assert values["fixed_solve_roofline"]["value"] > 0
    # nothing to read: no random-effect step, no program of that name
    glm_state = dict(state, trackers=[[s for s in t if s["coordinate"] == "fixed"]
                                      for t in state["trackers"]],
                     trace=dict(state["trace"], module_s={}))
    for name in ("re_step_s", "re_entities_per_s", "fixed_solve_roofline"):
        reader = importlib.import_module(f"benchmarks.layer_metrics.{name}")
        assert reader.read(glm_state) is None
