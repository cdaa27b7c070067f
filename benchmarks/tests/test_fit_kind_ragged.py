"""The kind ``fit_ragged`` driven end to end on the CPU at a tiny size
under the cell's own limits: sound runs are ``correct``, the control and
every planted fault are not; its generator gives every seed the same
shapes; its three readers read a tree built by hand."""
import copy
import importlib

import numpy as np
import pytest

from benchmarks import run as bench_run
from benchmarks.kinds import fit_ragged
from benchmarks.references import entities_ragged
from benchmarks.tests import faults, faults_ragged
from benchmarks.tests.conftest import load

CELL = "game_fit_ragged"
ALL_FAULTS = dict(faults.FAULTS, **faults_ragged.FAULTS)
# What the comparison cannot see, and why (PERF.md §2).
READS_AS_SOUND = {"padded_rows_weighted"}


def tiny_ragged(config: dict) -> dict:
    """The configuration at a size a test can hold: 96 users of 20 to 300
    rows by the same quantile rule (five size classes), 256 columns."""
    c = copy.deepcopy(config)
    d = c["data"]
    d.update(named_features=255, users=96,
             validation={"rows_per_user": 4, "unseen_users": 4,
                         "unseen_rows": 2})
    d["rows_per_user"].update(of_users=96, every=1, max=300)
    return c


@pytest.fixture(scope="module")
def config():
    return load("benchmarks", "configs", "game-logistic-ragged-re.json")


@pytest.fixture(scope="module")
def ragged_mix():
    return load("benchmarks", "traffic", "fit_from_zero_ragged.json")


def run_tiny(bench, config, mix, seed=3):
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    limits = load("benchmarks", "limits", CELL + ".json")
    said = []
    line = bench_run.run_cell(bench, cell, tiny_ragged(config), mix, limits,
                              seed, 0.3, False, said.append)
    return line, said


def test_a_run_gives_a_correct_result_line(bench, config, ragged_mix):
    line, said = run_tiny(bench, config, ragged_mix)
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) == {"fit_s", "setup_s"}
    assert {"re_resid", "scores", "offsets", "val_auc", "val_logistic_loss",
            "loss3", "grad0", "final_loss"} <= set(line["compared"])
    shapes = said[0]["shapes"]
    assert shapes["rows_per_user"] == shapes["rows"] / shapes["users"]
    assert said[-1]["fits"] == line["attempted"]


def test_the_cell_is_declared_with_its_files(bench):
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "game-logistic-ragged-re", "fit_from_zero_ragged", 1)
    reported = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
                if bench_run.applies(m, cell, bench)}
    assert {"fit_s", "setup_s", "re_buckets_per_step", "re_pad_share_pct",
            "re_device_s", "re_step_s", "re_entities_per_s", "fit_mfu",
            "fixed_solve_roofline", "device_idle_pct"} <= reported
    assert not {m for m in reported if m.startswith("tron_")}
    assert "row_passes_per_s" not in reported


@pytest.mark.parametrize("fault", sorted(ALL_FAULTS))
def test_a_planted_fault_is_not_correct(fault, bench, config, ragged_mix):
    with ALL_FAULTS[fault]():
        line, _ = run_tiny(bench, config, ragged_mix)
    if fault in READS_AS_SOUND:
        # A padded slot's design row is all zero (its columns are the
        # ghost, its intercept too): weight 1 there adds log 2 to the
        # objective and nothing to any gradient or Hessian.
        assert line["correct"] is True, line["compared"]
    else:
        assert line["correct"] is False, line["compared"]


def test_the_smallest_class_shows_only_by_class(bench, config, ragged_mix):
    """The fault the class-wise residual is for: the class with the fewest
    users left at its start fails ``re_resid`` (1 in its class)."""
    with faults_ragged.smallest_class_unchanged():
        line, _ = run_tiny(bench, config, ragged_mix)
    value, limit = line["compared"]["re_resid"]
    assert value == pytest.approx(1.0, abs=1e-3) and value > limit


def test_the_bfloat16_control_is_not_correct(bench, config, ragged_mix):
    sound, _ = run_tiny(bench, config, ragged_mix, seed=9)
    with faults.CONTROL():
        control, _ = run_tiny(bench, config, ragged_mix, seed=9)
    assert sound["correct"] is True, sound["compared"]
    assert control["correct"] is False, control["compared"]
    value, limit = control["compared"]["scores"]
    assert value > limit


# ------------------------------------------------------------ the generator


def test_the_counts_are_the_stated_sequence(config):
    """The whole source's sequence (0.3 s): 138,493 ascending counts from
    20 to 9,254 with median 68, summing to what the file states; the cut
    takes every ``every``-th of them."""
    d = copy.deepcopy(config["data"])
    d["rows_per_user"]["every"] = 1
    whole = fit_ragged.user_counts(d)
    assert len(whole) == d["rows_per_user"]["of_users"] == 138493
    assert (whole.min(), whole.max(), np.median(whole)) == (20, 9254, 68)
    assert np.all(np.diff(whole) >= 0)
    assert whole.sum() == 20000405          # the source: 20,000,263 ratings
    cut = fit_ragged.user_counts(config["data"])
    assert config["data"]["rows_per_user"]["every"] == 2
    np.testing.assert_array_equal(cut, whole[1::2])     # positions 1, 3, 5, ...
    d["rows_per_user"]["every"] = 3
    np.testing.assert_array_equal(fit_ragged.user_counts(d), whole[1::3])
    assert len(cut) == config["data"]["users"] == config["users"]
    assert cut.sum() == config["rows"]
    assert (cut.min(), cut.max(), np.median(cut)) == (20, 9254, 68)


def test_every_seed_gives_the_same_shapes_and_bucket_classes(config):
    d = tiny_ragged(config)["data"]
    seeds = [fit_ragged.generate(d, s) for s in (7, 8, 2**31 + 12345)]
    want = np.sort(fit_ragged.user_counts(d))
    per_seed = []
    for ds in seeds:
        counts = np.bincount(ds.train.users, minlength=ds.n_users)
        np.testing.assert_array_equal(np.sort(counts), want)
        per_seed.append(counts)
        assert ds.train.gi.shape == seeds[0].train.gi.shape
        assert ds.validation.n_rows == seeds[0].validation.n_rows
        # every user's rows between them hold every user column: one
        # local width, so one bucket a size class
        held = np.zeros((ds.n_users, ds.user_dim), bool)
        held[ds.train.users[:, None], ds.train.ui] = True
        assert held.all()
    assert len(set(entities_ragged.size_classes(want))) == 5
    assert not np.array_equal(per_seed[0], per_seed[1])   # who gets which
    again = fit_ragged.generate(d, 7)
    assert np.array_equal(again.train.users, seeds[0].train.users)
    assert np.array_equal(again.train.gv, seeds[0].train.gv)


def test_a_users_count_that_disagrees_is_an_error(config):
    d = tiny_ragged(config)["data"]
    d["users"] = 95
    with pytest.raises(ValueError, match="gives 96 users"):
        fit_ragged.generate(d, 1)


# -------------------------------------------------------------- the readers


def _tree(first_id: int, with_arguments: bool = True) -> list:
    """One fit: two random-effect steps of three buckets each."""
    spans = []
    sid = first_id + 10
    for step in (2, 4):
        for b, (entities, s, rows) in enumerate(
                [(10, 32, 250), (4, 64, 200), (1, 256, 150)]):
            args = {"bucket": b, "entities": entities, "local_dim": 32,
                    "solver": "newton_primal"}
            if with_arguments:
                args.update(rows=rows, row_slots=entities * s, padded_rows=s,
                            chunk=None)
            sid += 1
            spans.append(("optim.re_bucket", sid, first_id + step, 0.0, 0.1,
                          args))
        spans.append(("descent.step", first_id + step, first_id + 1, 0.0, 0.5,
                      {"coordinate": "perUser"}))
    spans.append(("descent.step", first_id + 3, first_id + 1, 0.5, 0.6,
                  {"coordinate": "fixed"}))
    spans.append(("estimator.fit", first_id + 1, None, 0.0, 1.0, {}))
    return spans


def _read(name: str, state: dict):
    return importlib.import_module(
        f"benchmarks.layer_metrics.{name}").read(state)


@pytest.fixture
def kept(monkeypatch):
    from photon_tpu.obs import trace as program

    held: list = []
    monkeypatch.setattr(
        program, "recent_trees",
        lambda root, last=None: held[-last:] if last else list(held))
    return held


def test_the_span_readers_on_a_made_up_tree(kept):
    kept.extend([_tree(0), _tree(100)])
    state = {"trackers": [[], []]}
    assert _read("re_buckets_per_step", state) == 3.0
    slots, rows = 10 * 32 + 4 * 64 + 256, 250 + 200 + 150
    assert _read("re_pad_share_pct", state) == pytest.approx(
        100.0 * (1 - rows / slots))


def test_the_span_readers_read_nothing_on_an_older_program(kept):
    """Bucket spans without the arguments of PR 33, and no tree at all."""
    state = {"trackers": [[]]}
    assert _read("re_buckets_per_step", state) is None
    kept.append(_tree(0, with_arguments=False))
    assert _read("re_buckets_per_step", state) is None
    assert _read("re_pad_share_pct", state) is None


def test_the_device_reader_sums_the_bucket_programs():
    state = {"trackers": [[], [], []], "trace": {"module_s": {
        "jit_fit_bucket_newton": 4.5, "jit__fit_bucket_jitted": 1.5,
        "jit__fit_jitted": 9.0}}}
    assert _read("re_device_s", state) == pytest.approx(2.0)
    state["trace"]["module_s"] = {"jit__fit_jitted": 9.0}
    assert _read("re_device_s", state) is None


def test_per_layer_readers_on_a_tiny_ragged_state(bench, config, ragged_mix):
    """Every reader the cell lists reads the state the kind returns."""
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    limits = load("benchmarks", "limits", CELL + ".json")
    out = fit_ragged.run(cell, tiny_ragged(config), ragged_mix, limits, 4, 0.2,
                         None, bench_run.T_START, lambda o: None)
    window = out["state"]["window_s"]
    state = dict(out["state"], peak=load("benchmarks", "peaks.json")["TPU v5 lite"],
                 trace={"busy_s": 0.25 * window, "window_s": window,
                        "module_s": {"jit__fit_jitted": 0.1 * window,
                                     "jit_fit_bucket_newton": 0.2 * window}})
    values = bench_run.per_layer(bench, cell, state)
    # the CPU reports no memory peak, so that reader finds nothing to read
    assert set(values) == {m["name"] for m in bench["per_layer"]
                           if bench_run.applies(m, cell, bench)} - {"peak_hbm_gb"}
    assert values["re_buckets_per_step"]["value"] == 5.0
    assert 0 < values["re_pad_share_pct"]["value"] < 50
    assert values["re_device_s"]["value"] == pytest.approx(
        0.2 * window / len(out["state"]["trackers"]))
    assert 0 < values["fit_mfu"]["value"] < 100
