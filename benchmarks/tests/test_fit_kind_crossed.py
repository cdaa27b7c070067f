"""The kind ``fit_crossed`` driven end to end on the CPU at a tiny size
under the cell's own limits: sound runs are ``correct``, the control and
every planted fault are not; its generator gives every seed the same
bucket shapes for both keys; its four readers read a tree built by hand
and the state a run returns."""
import copy
import importlib

import numpy as np
import pytest

from benchmarks import run as bench_run
from benchmarks.kinds import fit_crossed, fit_ragged
from benchmarks.references import entities_ragged
from benchmarks.tests import faults, faults_crossed, faults_ragged
from benchmarks.tests.conftest import load

CELL = "game_fit_crossed"
ALL_FAULTS = dict(faults.FAULTS, **faults_ragged.FAULTS,
                  **faults_crossed.FAULTS)
# What the comparison cannot see at this size, and why (PERF.md §2):
# a padded slot's design row is all zero, so weight 1 there moves no
# gradient, and the few hundred padded slots here move no stop either.
READS_AS_SOUND = {"padded_rows_weighted"}


def tiny_crossed(config: dict) -> dict:
    """The configuration at a size a test can hold: 96 users of 20 to 300
    rows (five size classes) and 40 movies of 1 to 2,755 by the same
    rules, the same 21 columns."""
    c = copy.deepcopy(config)
    d = c["data"]
    d.update(users=96, movies=40,
             validation={"rows_per_user": 4, "unseen_users": 4,
                         "unseen_rows": 2, "unseen_movies": 4,
                         "unseen_movie_rows": 2})
    d["rows_per_user"].update(of_users=96, every=1, max=300)
    d["rows_per_movie"].update(of_movies=40, max=3000)
    return c


@pytest.fixture(scope="module")
def config():
    return load("benchmarks", "configs", "game-logistic-crossed-re.json")


@pytest.fixture(scope="module")
def crossed_mix():
    return load("benchmarks", "traffic", "fit_from_zero_crossed.json")


def run_tiny(bench, config, mix, seed=3):
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    limits = load("benchmarks", "limits", CELL + ".json")
    said = []
    line = bench_run.run_cell(bench, cell, tiny_crossed(config), mix, limits,
                              seed, 0.3, False, said.append)
    return line, said


def test_a_run_gives_a_correct_result_line(bench, config, crossed_mix):
    line, said = run_tiny(bench, config, crossed_mix)
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) == {"fit_s", "setup_s"}
    assert {"re_resid_user", "re_resid_movie", "scores", "offsets", "val_auc",
            "val_logistic_loss", "loss3", "grad0", "grad3", "grad_mid",
            "loss_mid", "final_loss", "early_stop"} == set(line["compared"])
    assert "re_resid" not in line["compared"]
    shapes = said[0]["shapes"]
    assert shapes["rows_per_user"] == 2 * shapes["rows"] / (
        shapes["users"] + shapes["movies"])
    assert [s["coordinate"] for s in said[0]["warmup_tracker"]] == [
        "fixed", "perUser", "perMovie"] * 2
    assert said[-1]["fits"] == line["attempted"]


def test_the_fixed_cap_is_the_one_every_seed_runs_to(config):
    """The fixed step's cap is what makes every fit do the same passes
    (PERF.md §6, PR 36): under the per-entity steps' 8, the program's
    default tolerance in every coordinate, and the configuration says
    which seeds read which iterations and passes on the chip."""
    fixed, user, movie = config["coordinates"]
    assert fixed["max_iterations"] == config["fixed_max_iterations"] == 6
    assert user["max_iterations"] == movie["max_iterations"] == 8
    assert fixed["max_iterations"] < user["max_iterations"]
    assert {c["tolerance"] for c in config["coordinates"]} == {1e-7}
    why = config["reduced_why"]["max_iterations"]
    for said in ("6 iterations and 14 passes", "28 passes a fit",
                 "2147483300", "2147489011", "seeds", "PR 35"):
        assert said in why, said
    assert config["reduced"] == ["max_iterations", "users", "rows"]
    assert set(config["reduced_why"]) == set(config["reduced"])


def test_the_cell_is_declared_with_its_files(bench, config):
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "game-logistic-crossed-re", "fit_from_zero_crossed", 1)
    reported = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
                if bench_run.applies(m, cell, bench)}
    ragged = next(w for w in bench["workloads"]
                  if w["name"] == "game_fit_ragged")
    assert reported == {m["name"] for m in bench["per_layer"]
                        + bench["end_to_end"]
                        if bench_run.applies(m, ragged, bench)} | {
        "re_item_step_s", "re_item_pad_share_pct", "re_programs_per_sweep",
        "re_group_s"}
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    assert config["item_entity"] == config["coordinates"][2]["entity"]
    assert [c["id"] for c in config["coordinates"]] == [
        "fixed", "perUser", "perMovie"]


@pytest.mark.parametrize("fault", sorted(ALL_FAULTS))
def test_a_planted_fault_is_not_correct(fault, bench, config, crossed_mix):
    with ALL_FAULTS[fault]():
        line, _ = run_tiny(bench, config, crossed_mix)
    if fault in READS_AS_SOUND:
        assert line["correct"] is True, line["compared"]
    else:
        assert line["correct"] is False, line["compared"]


@pytest.mark.parametrize("fault, number", [
    ("movie_residual_without_users", "re_resid_movie"),
    ("unchanged_movie", "re_resid_movie"),
    ("single_row_movies_unsolved", "re_resid_movie"),
    ("unseen_movie_scored", "val_logistic_loss"),
    ("short_memory", "grad_mid"),
])
def test_a_crossed_fault_fails_the_number_it_is_for(fault, number, bench,
                                                    config, crossed_mix):
    with ALL_FAULTS[fault]():
        line, _ = run_tiny(bench, config, crossed_mix)
    value, limit = line["compared"][number]
    assert value > limit, line["compared"]
    # the per-user coordinate is sound under every one of them
    value, limit = line["compared"]["re_resid_user"]
    assert value <= limit, line["compared"]


def test_the_bfloat16_control_is_not_correct(bench, config, crossed_mix):
    sound, _ = run_tiny(bench, config, crossed_mix, seed=9)
    with faults.CONTROL():
        control, _ = run_tiny(bench, config, crossed_mix, seed=9)
    assert sound["correct"] is True, sound["compared"]
    assert control["correct"] is False, control["compared"]
    value, limit = control["compared"]["scores"]
    assert value > limit


# ------------------------------------------------------------ the generator


def test_the_counts_are_the_stated_sequences(config):
    """The users' counts are ``game-logistic-ragged-re``'s to the letter;
    the movies' 26,744 counts are the stated log-normal's, scaled to the
    users' rows: 1 to 33,648, median 9, seventeen size classes."""
    ragged = load("benchmarks", "configs", "game-logistic-ragged-re.json")
    assert config["data"]["rows_per_user"] == ragged["data"]["rows_per_user"]
    users = fit_ragged.user_counts(config["data"])
    np.testing.assert_array_equal(users,
                                  fit_ragged.user_counts(ragged["data"]))
    assert (len(users), users.sum()) == (config["users"], config["rows"])
    movies = fit_crossed.movie_counts(config["data"], int(users.sum()))
    assert len(movies) == config["movies"] == config["data"]["movies"]
    assert movies.sum() == config["rows"] == 9997911
    assert (movies.min(), movies.max(), np.median(movies)) == (
        config["rows_per_movie_min"], config["rows_per_movie_max"], 9)
    classes = entities_ragged.size_classes(movies)
    assert len(set(classes)) == 17 and classes.max() == 65536
    assert classes.sum() == 15393219             # row slots: 35.0% padding
    assert (movies == 1).sum() == 6669 and (classes == 65536).sum() == 66
    # unscaled: the source's own counts, at the whole source's rows
    whole = copy.deepcopy(config["data"])
    whole["rows_per_user"]["every"] = 1
    source = fit_crossed.movie_counts(whole, 20000047)
    assert (source.min(), source.max(), np.median(source)) == (1, 67310, 18)
    assert (source == 67310).sum() == 64          # the clip: assumed


def test_every_seed_gives_the_same_bucket_shapes_for_both_keys(config):
    d = tiny_crossed(config)["data"]
    seeds = [fit_crossed.generate(d, s) for s in (7, 8, 2**31 + 12345)]
    want_users = np.sort(fit_ragged.user_counts(d))
    want_movies = np.sort(fit_crossed.movie_counts(d, int(want_users.sum())))
    per_seed = []
    for ds in seeds:
        by_user = np.bincount(ds.train.users, minlength=ds.n_users)
        by_movie = np.bincount(ds.train.movies, minlength=ds.n_movies)
        np.testing.assert_array_equal(np.sort(by_user), want_users)
        np.testing.assert_array_equal(np.sort(by_movie), want_movies)
        per_seed.append((by_user, by_movie))
        assert ds.validation.n_rows == seeds[0].validation.n_rows
        # every user's rows between them hold every user column: one
        # local width a key, so one bucket a size class
        held = np.zeros((ds.n_users, ds.user_dim), bool)
        held[ds.train.users[:, None], ds.train.ui] = True
        assert held.all()
        va = ds.validation
        assert (va.users < 0).sum() == 8 and (va.movies < 0).sum() == 8
        assert not np.any((va.users < 0) & (va.movies < 0))
    assert not np.array_equal(per_seed[0][1], per_seed[1][1])   # who gets which
    again = fit_crossed.generate(d, 7)
    assert np.array_equal(again.train.movies, seeds[0].train.movies)
    assert np.array_equal(again.train.y, seeds[0].train.y)


def test_counts_that_disagree_are_an_error(config):
    d = tiny_crossed(config)["data"]
    with pytest.raises(ValueError, match="gives 40 movies"):
        fit_crossed.generate(dict(d, movies=39), 1)
    with pytest.raises(ValueError, match="gives 96 users"):
        fit_crossed.generate(dict(d, users=95), 1)


# -------------------------------------------------------------- the readers


def _tree(first_id: int, re_type: bool = True) -> list:
    """One fit: two sweeps, each a per-user step of two buckets and a
    per-movie step of three."""
    spans = []
    sid = first_id + 100
    for sweep, steps in ((50, (2, 3)), (51, (4, 5))):
        for step in steps:
            movie = step % 2 == 1
            held = ([(8, 1, 8), (3, 4, 9), (1, 64, 40)] if movie
                    else [(10, 32, 250), (4, 64, 200)])
            for b, (entities, s, rows) in enumerate(held):
                args = {"bucket": b, "entities": entities,
                        "local_dim": 1 if movie else 32,
                        "solver": "newton_primal", "rows": rows,
                        "row_slots": entities * s, "padded_rows": s}
                if re_type:
                    args["re_type"] = "movieId" if movie else "userId"
                sid += 1
                spans.append(("optim.re_bucket", sid, first_id + step, 0.0,
                              0.1, args))
            spans.append(("descent.step", first_id + step, first_id + sweep,
                          0.0, 0.25 if movie else 0.5, {}))
        spans.append(("descent.sweep", first_id + sweep, first_id + 1, 0.0,
                      1.0, {}))
    spans.append(("estimator.fit", first_id + 1, None, 0.0, 2.0, {}))
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


def test_the_readers_on_a_made_up_tree(kept):
    kept.extend([_tree(0), _tree(1000)])
    state = {"trackers": [[], []], "config": {"item_entity": "movieId"}}
    assert _read("re_item_step_s", state) == pytest.approx(0.5)
    slots, rows = 8 + 12 + 64, 8 + 9 + 40
    assert _read("re_item_pad_share_pct", state) == pytest.approx(
        100.0 * (1 - rows / slots))
    assert _read("re_programs_per_sweep", state) == 5.0
    assert _read("re_buckets_per_step", state) == 2.5


def test_the_readers_read_nothing_on_an_older_program(kept):
    """Bucket spans without ``re_type``, a configuration that names no
    item, no tree at all, no warm-up tree."""
    state = {"trackers": [[]], "config": {"item_entity": "movieId"}}
    for name in ("re_item_step_s", "re_item_pad_share_pct",
                 "re_programs_per_sweep", "re_group_s"):
        assert _read(name, state) is None
    kept.append(_tree(0, re_type=False))
    assert _read("re_item_step_s", state) is None
    assert _read("re_item_pad_share_pct", state) is None
    assert _read("re_programs_per_sweep", state) is None
    assert _read("re_buckets_per_step", state) == 2.5    # an older reader
    kept[:] = [_tree(0)]
    assert _read("re_item_step_s", dict(state, config={})) is None
    state["warmup_tree"] = _tree(0)
    assert _read("re_group_s", state) is None


def test_the_grouping_reader_sums_the_dataset_spans():
    tree = [("data.re_dataset", 3, 2, 0.0, 1.5, {"re_type": "userId"}),
            ("data.re_dataset", 4, 2, 1.5, 2.0, {"re_type": "movieId"}),
            ("estimator.prepare", 2, 1, 0.0, 2.5, {}),
            ("data.re_dataset", 6, 5, 2.5, 2.75, {"re_type": "userId"}),
            ("estimator.prepare_validation", 5, 1, 2.5, 3.0, {}),
            ("estimator.fit", 1, None, 0.0, 9.0, {})]
    assert _read("re_group_s", {"warmup_tree": tree}) == pytest.approx(2.25)


def test_per_layer_readers_on_a_tiny_crossed_state(bench, config, crossed_mix):
    """Every reader the cell lists reads the state the kind returns."""
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    limits = load("benchmarks", "limits", CELL + ".json")
    out = fit_crossed.run(cell, tiny_crossed(config), crossed_mix, limits, 4,
                          0.2, None, bench_run.T_START, lambda o: None)
    window = out["state"]["window_s"]
    fits = len(out["state"]["trackers"])
    state = dict(out["state"], peak=load("benchmarks", "peaks.json")["TPU v5 lite"],
                 trace={"busy_s": 0.25 * window, "window_s": window,
                        "module_s": {"jit__fit_jitted": 0.1 * window,
                                     "jit_fit_bucket_newton": 0.2 * window}})
    values = {k: v["value"]
              for k, v in bench_run.per_layer(bench, cell, state).items()}
    # the CPU reports no memory peak, so that reader finds nothing to read
    assert set(values) == {m["name"] for m in bench["per_layer"]
                           if bench_run.applies(m, cell, bench)} - {"peak_hbm_gb"}
    assert values["re_buckets_per_step"] == 9.0          # (5 + 13) / 2
    assert values["re_programs_per_sweep"] == 18.0
    assert 0 < values["re_item_pad_share_pct"] < 50
    assert 0 < values["re_pad_share_pct"] < 50
    user_steps = sum(s["seconds"] for t in out["state"]["trackers"]
                     for s in t if s["coordinate"] == "perUser") / fits
    assert values["re_item_step_s"] + user_steps == pytest.approx(
        values["re_step_s"], rel=1e-3)
    assert 0 < values["re_group_s"] < out["end_to_end"]["setup_s"]
    assert 0 < values["fit_mfu"] < 100
    warm = [s for s in out["state"]["warmup_tree"]
            if s[0] == "data.re_dataset"]
    assert sorted((s[5]["re_type"], s[5]["scoring"]) for s in warm) == [
        ("movieId", False), ("movieId", True),
        ("userId", False), ("userId", True)]
    assert {s[5]["classes"] for s in warm if not s[5]["scoring"]} == {5, 13}
