"""The cell ``glm_fit_tron`` end to end on the CPU at a tiny size, under the
cell's own limits: a well-formed result line with ``correct`` true, the
three ``tron_*`` readers on the run's state, and ``correct`` false for the
control and for the faults a TRON solve can have (``faults_tron.py`` and
those of ``faults.py`` that apply to it)."""
import importlib

import pytest

from benchmarks import run as bench_run
from benchmarks.kinds import fit
from benchmarks.tests import faults, faults_tron
from benchmarks.tests.conftest import load, tiny

CELL = "glm_fit_tron"
READERS = ("tron_hvp_per_fit", "tron_cg_steps_per_iteration",
           "tron_rejected_per_fit")


def _cell(bench):
    return next(w for w in bench["workloads"] if w["name"] == CELL)


@pytest.fixture(autouse=True, scope="module")
def float64():
    import jax

    was = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", was)


def small(config: dict) -> dict:
    """The configuration at ``tiny``'s size, in float64. The cell's limits
    are set from the chip's float32 at the cell's own size; a thousand rows
    in float32 on the CPU are another arithmetic (the gradient after the
    third iteration lies at float32's floor there: ``grad3`` reads 0.02 to
    1.0 over a dozen seeds, the chip 0.003 at the most, PERF.md §2), which
    says nothing about the program. In float64 every sound number reads
    far under its limit on any machine, and the control and the faults
    read as they do in float32."""
    c = tiny(config)
    c["dtype"] = "float64"
    return c


def run_tiny(bench, mix, seed=5):
    cell = _cell(bench)
    config = small(load("benchmarks", "configs", cell["config"] + ".json"))
    limits = load("benchmarks", "limits", CELL + ".json")
    lines = []
    line = bench_run.run_cell(bench, cell, config, mix, limits, seed, 0.2,
                              False, lines.append)
    return line, lines


def _failed(line):
    return sorted(n for n, (v, lim) in line["compared"].items() if not v <= lim)


def test_the_cell_names_tron_and_cuts_no_row(bench):
    cell = _cell(bench)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load(entry["file"])
    (coordinate,) = config["coordinates"]
    assert coordinate["optimizer"] == "TRON" and cell["chips"] == 1
    assert entry["reduced"] == config["reduced"] == ["max_iterations"]
    assert config["data"]["rows"] == config["published"]["rows"] == 72309
    assert config["data"]["named_features"] == config["published"]["features"]
    assert set(config["reduced_why"]) == set(config["reduced"])


@pytest.mark.parametrize("seed", [5, 9])
def test_a_sound_run_is_correct_and_holds_the_path(seed, bench, mix):
    line, earlier = run_tiny(bench, mix, seed)
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) == {"fit_s", "row_passes_per_s", "setup_s"}
    # the reference vouched for the first three iterations, so the path
    # was compared and not only its ends
    assert {"loss3", "grad3", "loss_mid", "grad0", "final_loss", "scores",
            "early_stop"} <= set(line["compared"])
    (tracker,) = [e for e in earlier if "trackers" in e]
    step = tracker["trackers"][-1][0]
    assert step["iterations"] == 4 and step["reasons"] == {"MAX_ITERATIONS": 1}


def test_the_bfloat16_control_is_not_correct(bench, mix):
    with faults.CONTROL():
        line, _ = run_tiny(bench, mix)
    assert line["correct"] is False, line["compared"]
    assert "scores" in _failed(line)


@pytest.mark.parametrize("fault", ["one_cg_step", "unweighted_hvp"])
def test_a_planted_tron_fault_is_not_correct(fault, bench, mix):
    with faults_tron.FAULTS[fault]():
        line, _ = run_tiny(bench, mix)
    assert line["correct"] is False, line["compared"]
    assert {"loss3", "grad3", "loss_mid"} <= set(_failed(line))


def test_accepting_every_step_changes_nothing_where_none_is_refused(bench, mix):
    """A TRON fit from zero coefficients refuses no step before it has
    converged (the first Newton steps of a logistic loss fall short of the
    optimum, never past it), so in this cell the third fault is the sound
    program and has to read so; ``test_reference_tron.py`` holds it on a
    start that refuses steps (PERF.md §2)."""
    sound, _ = run_tiny(bench, mix)
    with faults_tron.accepts_all():
        line, _ = run_tiny(bench, mix)
    assert line["correct"] is True
    assert line["compared"] == sound["compared"]


@pytest.mark.parametrize("fault", ["unchanged_fixed", "half_batch", "altered",
                                   "altered_validation", "stops_early"])
def test_a_planted_fault_is_not_correct_in_the_tron_cell(fault, bench, mix):
    with faults.FAULTS[fault]():
        line, _ = run_tiny(bench, mix)
    assert line["correct"] is False and _failed(line), line["compared"]


def test_the_tron_readers_read_the_step_spans(bench, mix):
    cell = _cell(bench)
    config = small(load("benchmarks", "configs", cell["config"] + ".json"))
    out = fit.run(cell, config, mix, {}, 4, 0.2, None, bench_run.T_START,
                  lambda o: None)
    state = out["state"]
    values = {name: importlib.import_module(
        f"benchmarks.layer_metrics.{name}").read(state) for name in READERS}
    passes = state["trackers"][-1][0]["data_passes"]
    # data_passes = 2 + 3 an iteration + 2 a product, 4 iterations
    assert values["tron_hvp_per_fit"] == (passes - 2 - 3 * 4) / 2
    assert values["tron_cg_steps_per_iteration"] == (
        values["tron_hvp_per_fit"] / 4)
    assert values["tron_rejected_per_fit"] == 0
    wanted = {m["name"] for m in bench["per_layer"]
              if bench_run.applies(m, cell, bench)}
    assert set(READERS) <= wanted


def test_the_tron_readers_find_nothing_on_an_lbfgs_step(bench, mix):
    cell = next(w for w in bench["workloads"] if w["name"] == "glm_fit")
    config = tiny(load("benchmarks", "configs", cell["config"] + ".json"))
    out = fit.run(cell, config, mix, {}, 4, 0.2, None, bench_run.T_START,
                  lambda o: None)
    for name in READERS:
        reader = importlib.import_module(f"benchmarks.layer_metrics.{name}")
        assert reader.read(out["state"]) is None
