"""The kind ``fit_factored`` and the cell ``game_fit_factored`` on the CPU
at a small size: a run's result line, the comparison against the planted
faults and the control, the pairing, the parent's clean stop, and the
cell's per-layer readers."""
import copy
import json
import subprocess
import sys

import numpy as np
import pytest

from benchmarks import run as bench_run
from benchmarks.kinds import fit_crossed, fit_factored, fit_ragged
from benchmarks.tests import faults_factored
from benchmarks.tests.conftest import ROOT, load

CELL = "game_fit_factored"
COMPARED = {"offsets", "scores", "val_auc", "val_logistic_loss", "loss3",
            "grad0", "grad3", "loss_mid", "grad_mid", "final_loss",
            "early_stop", "init", "latent_resid", "proj_iterations",
            "proj_grad0", "proj_final_loss"}
FACTORED_METRICS = ("factored_step_s", "factored_latent_s",
                    "factored_projection_s", "factored_projection_roofline")


def tiny_factored(config: dict) -> dict:
    """The configuration at a size a test can hold: 96 users of 20 to 60
    rows (three size classes, local widths to 64 columns: past 32) and 256
    movies of 1 to 64 rows by the same rules, 8 latent factors, 3
    iterations a sub-step at reg_weight 1 (at this size, 4 iterations at
    the cell's 10 reach float32's floor, where gradient norms compare as
    noise)."""
    c = copy.deepcopy(config)
    d = c["data"]
    d.update(users=96, movies=256,
             validation={"rows_per_user": 4, "unseen_users": 4,
                         "unseen_rows": 2, "unseen_movies": 4,
                         "unseen_movie_rows": 2})
    d["rows_per_user"].update(of_users=96, every=1, max=60)
    d["rows_per_movie"].update(of_movies=256, max=64)
    d["factors"].update(rank=8)
    for coord in c["coordinates"]:
        if coord["kind"] == "factored":
            coord.update(latent_dim=8, max_iterations=3, reg_weight=1.0)
    return c


@pytest.fixture(scope="module")
def config():
    return load("benchmarks", "configs", "game-logistic-factored-re.json")


@pytest.fixture(scope="module")
def factored_mix():
    return load("benchmarks", "traffic", "fit_from_zero_factored.json")


def run_tiny(bench, config, mix, seed=3):
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    limits = load("benchmarks", "limits", CELL + ".json")
    said = []
    line = bench_run.run_cell(bench, cell, tiny_factored(config), mix,
                              limits, seed, 0.3, False, said.append)
    return line, said


def test_a_run_gives_a_correct_result_line(bench, config, factored_mix):
    line, said = run_tiny(bench, config, factored_mix)
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) == {"fit_s", "setup_s"}
    assert set(line["compared"]) == COMPARED
    # every fit's sub-steps: the init, two alternations and the refresh in
    # the first sweep, two alternations and the refresh in the second
    subs = said[-1]["sub_steps"][-1]
    assert [s[0] for s in subs] == (
        ["init"] + ["latent", "projection"] * 2 + ["latent"]
        + ["latent", "projection"] * 2 + ["latent"])
    assert all(s[1] >= 1 and s[2] >= 1 for s in subs[1:])


@pytest.mark.parametrize("fault", sorted(faults_factored.FAULTS))
def test_a_planted_fault_is_not_correct(fault, bench, config, factored_mix):
    with faults_factored.FAULTS[fault]():
        line, _ = run_tiny(bench, config, factored_mix)
    assert line["correct"] is False, line["compared"]


def test_the_bfloat16_control_is_not_correct(bench, config, factored_mix):
    with faults_factored.CONTROL():
        line, _ = run_tiny(bench, config, factored_mix)
    assert line["correct"] is False, line["compared"]
    over = [k for k, (v, lim) in line["compared"].items() if v > lim]
    assert {"scores", "proj_grad0"} <= set(over), line["compared"]


def test_the_pairing_keeps_the_counts_and_repeats_no_pair(config):
    """At the cell's own counts: every user its rows, every movie its rows,
    no (user, movie) pair twice, so that a user's local width is its rows
    and every seed gives the same buckets."""
    d = config["data"]
    per_user = fit_ragged.user_counts(d)
    per_movie = fit_crossed.movie_counts(d, int(per_user.sum()))
    rng = np.random.default_rng([2**31 + 44, 4])
    users, movies = fit_factored.pairing(per_user, per_movie, rng)
    assert np.array_equal(np.bincount(users, minlength=len(per_user)),
                          per_user)
    assert np.array_equal(np.bincount(movies, minlength=len(per_movie)),
                          per_movie)
    pairs = users.astype(np.int64) * len(per_movie) + movies
    assert len(np.unique(pairs)) == len(pairs)


def test_the_reference_products_are_the_dense_ones():
    """``references/factored_re.py``'s products through the entries grouped
    by entity against the dense matrices they stand for: two entries a
    row, absent columns, rows of no entity and an entity without rows."""
    from benchmarks.references import factored_re

    rng = np.random.default_rng(5)
    n, dim, ents, p = 300, 17, 12, 4
    idx = rng.integers(0, dim + 2, (n, 2))          # dim and above: absent
    val = rng.normal(size=(n, 2))
    ent = rng.integers(-1, ents - 1, n)             # -1: none; the last: no rows
    rows = factored_re.FactoredRows(idx=idx, val=val, ent=ent,
                                    y=rng.random(n) < 0.5, dim=dim,
                                    n_entities=ents, l2=1.0)
    x = np.zeros((n, dim))
    for k in range(2):
        live = idx[:, k] < dim
        np.add.at(x, (np.flatnonzero(live), idx[live, k]), val[live, k])
    e = np.zeros((n, ents))
    e[ent >= 0, ent[ent >= 0]] = 1.0
    table, b, scale = (rng.normal(size=(dim, p)), rng.normal(size=(ents, p)),
                       rng.normal(size=n))
    np.testing.assert_allclose(rows.entity_times(scale, table),
                               e.T @ (scale[:, None] * x) @ table, atol=1e-12)
    np.testing.assert_allclose(rows.entity_transpose_times(scale, b),
                               (e.T @ (scale[:, None] * x)).T @ b, atol=1e-12)
    np.testing.assert_allclose(rows.pair_dots(table, b),
                               np.sum((x @ table) * (e @ b), 1), atol=1e-12)
    np.testing.assert_allclose(rows.times(table), x @ table, atol=1e-12)
    np.testing.assert_allclose(rows.transpose_times(x @ table),
                               x.T @ x @ table, atol=1e-12)
    np.testing.assert_allclose(rows.by_entity(x @ table), e.T @ x @ table,
                               atol=1e-12)


def test_every_seed_gives_the_same_buckets(config):
    from photon_tpu.data.random_effect import build_random_effect_dataset

    c = tiny_factored(config)

    def shapes(seed):
        ds = fit_factored.generate(c["data"], seed)
        idx, val = fit_factored.factored_shard(ds.train, ds.n_movies)
        red = build_random_effect_dataset(
            "userId", ds.train.users, idx, val, ds.train.y, ds.n_movies)
        return [tuple(b.idx.shape) + (b.local_dim,) for b in red.buckets]

    first = shapes(5)
    assert max(s[3] for s in first) > 32
    assert shapes(2**31 + 7) == first


def test_the_parent_program_stops_at_once(tmp_path):
    """A program whose factored random effect has no latent-space path
    stops the kind with exit 1 and one line on standard error."""
    script = tmp_path / "parent.py"
    script.write_text(
        "import sys\n"
        f"sys.path.insert(0, {ROOT!r})\n"
        "from photon_tpu.game import factored_random_effect as f\n"
        "del f.latent_step\n"
        "from benchmarks.kinds import fit_factored\n"
        "fit_factored.require_program()\n")
    done = subprocess.run([sys.executable, str(script)], capture_output=True,
                          text=True, env={"JAX_PLATFORMS": "cpu",
                                          "PATH": "/usr/bin:/bin"})
    assert done.returncode == 1
    assert done.stderr.strip().count("\n") == 0
    assert "latent_step" in done.stderr


def test_the_cell_is_declared_with_its_files(bench, config):
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["config"] == "game-logistic-factored-re"
    assert cell["chips"] == 1
    assert cell["traffic"] == "fit_from_zero_factored"
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["file"] == "benchmarks/configs/game-logistic-factored-re.json"
    assert set(entry["reduced"]) == set(config["reduced"])
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in FACTORED_METRICS:
        assert by_name[name]["workloads"] == [CELL]
    for name in ("fit_mfu", "re_step_s", "re_entities_per_s"):
        assert CELL not in by_name[name]["workloads"]
    limits = load("benchmarks", "limits", CELL + ".json")
    assert COMPARED <= set(limits)


def _made_up_state(config, tree):
    sh = {"rows": 1000, "factored_nnz": 1, "latent_dim": 32,
          "global_dim": 21, "global_nnz": 3}
    return {"config": config, "shapes": sh, "trackers": [[]],
            "trace": {"module_s": {"jit__projection_step": 0.5}},
            "peak": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "_tree": tree}


def test_the_factored_readers_on_a_made_up_tree(monkeypatch, config):
    import importlib

    from benchmarks.layer_metrics import _spans

    tree = [("factored.latent", 1, 9, 0.0, 0.25, {"iterations": 8,
                                                   "passes": 10}),
            ("factored.projection", 2, 9, 0.25, 0.75, {"iterations": 8,
                                                        "passes": 18}),
            ("descent.step", 9, None, 0.0, 1.0, {"coordinate": "x"})]
    monkeypatch.setattr(_spans, "trees", lambda state: [state["_tree"]])
    state = _made_up_state(config, tree)
    read = {n: importlib.import_module(f"benchmarks.layer_metrics.{n}").read
            for n in FACTORED_METRICS}
    assert read["factored_latent_s"](state) == pytest.approx(0.25)
    assert read["factored_projection_s"](state) == pytest.approx(0.5)
    share = read["factored_projection_roofline"](state)
    # 18 passes x 1,000 rows x 140 B over 819 GB/s, in 0.5 s
    assert share == pytest.approx(100 * 18 * 1000 * 140 / 819e9 / 0.5)
    # an older program keeps no such span: nothing is read, not 0
    older = _made_up_state(config, tree[2:])
    assert read["factored_latent_s"](older) is None
    assert read["factored_projection_s"](older) is None
    assert read["factored_projection_roofline"](older) is None


def test_per_layer_readers_on_a_tiny_factored_state(bench, config,
                                                    factored_mix):
    """Every reader the cell lists reads a number from a tiny run's state
    (a made-up trace), but ``peak_hbm_gb``, which reads the device's own
    memory and finds none on the CPU; a share of a roofline stays at or
    under 100%."""
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    limits = load("benchmarks", "limits", CELL + ".json")
    out = fit_factored.run(cell, tiny_factored(config), factored_mix,
                           limits, 3, 0.3, None, 0.0, lambda o: None)
    state = dict(out["state"],
                 trace={"busy_s": 1.0, "window_s": 2.0, "device_ops": [],
                        "idle_gaps": [], "module_s": {
                            "jit__projection_step": 10.0,
                            "jit__fit_jitted": 1.0}},
                 peak={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    metrics = bench_run.per_layer(bench, cell, state)
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [])}
    assert set(FACTORED_METRICS) <= listed
    assert listed - set(metrics) == {"peak_hbm_gb"}
    assert 0 < metrics["factored_projection_roofline"]["value"] <= 100
    assert 0 < metrics["fixed_solve_roofline"]["value"] <= 100
    assert "fit_mfu" not in metrics


def test_the_config_states_the_source_sizes(config):
    assert json.dumps(config)   # a JSON object
    assert config["published"]["movies"] == config["movies"] == 26744
    assert config["data"]["users"] == config["users"] == 69246
    c = next(c for c in config["coordinates"] if c["kind"] == "factored")
    assert c["latent_dim"] == 32 and c["alternations"] == 2
