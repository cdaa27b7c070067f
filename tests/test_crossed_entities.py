"""A GAME fit with two crossed per-entity coordinates (PR 36): a fixed
effect, a random effect keyed by user and a random intercept keyed by
movie, alternated by coordinate descent. The program against the plain
reference on every number the cell compares, the offsets a step is handed,
the movies' penalized intercepts, what an entity a coordinate never saw
scores, the spans that tell the two coordinates apart, no retrace on a
second fit, and bucket shapes that do not follow the seed.

The data is the benchmark's own at a small size: 120 users of 20 to 300
rows (five size classes at 21 -> 32 local columns) and 48 movies of 1 to
about 3,000 (local width 1) by the configuration's two log-normals;
everything float32 on the CPU, as the cell runs it.
"""
import copy
import json
import os

import numpy as np
import pytest

from benchmarks import reference
from benchmarks.kinds import fit, fit_crossed
from photon_tpu.estimators.game_estimator import build_re_dataset_from_bundle
from photon_tpu.obs import retrace
from photon_tpu.obs.trace import recent_trees

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME, SPAN_ID, PARENT_ID, START, END, ARGS = range(6)
COMPARED = ("offsets", "scores", "grad0", "loss3", "loss_mid", "grad3",
            "grad_mid", "final_loss", "early_stop", "re_resid_user",
            "re_resid_movie", "val_auc", "val_logistic_loss")
KEYS = {"perUser": "userId", "perMovie": "movieId"}
SEED = 2**31 + 36


def _load(*parts):
    with open(os.path.join(ROOT, "benchmarks", *parts)) as f:
        return json.load(f)


def small(config: dict) -> dict:
    c = copy.deepcopy(config)
    d = c["data"]
    d.update(users=120, movies=48,
             validation={"rows_per_user": 4, "unseen_users": 4,
                         "unseen_rows": 2, "unseen_movies": 4,
                         "unseen_movie_rows": 2})
    d["rows_per_user"].update(of_users=120, every=1, max=300)
    d["rows_per_movie"].update(of_movies=48, max=3000)
    return c


@pytest.fixture(scope="module")
def config():
    return small(_load("configs", "game-logistic-crossed-re.json"))


@pytest.fixture(scope="module")
def limits():
    return _load("limits", "game_fit_crossed.json")


@pytest.fixture(scope="module")
def ds(config):
    return fit_crossed.generate(config["data"], SEED)


@pytest.fixture(scope="module")
def two_fits(config, ds):
    """Two whole fits on one bundle, as a window makes them: the compared
    numbers of the second and its steps, the compile requests and solver
    traces of each, both span trees, the estimator and the last model."""
    import jax.monitoring

    compiled = []          # one entry a program handed to the compiler

    def on_duration(event, duration, **kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(duration)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    estimator, train, validation, opt = fit_crossed.build(config, ds)
    probe = fit.Probe()
    probe.install()
    try:
        marks = []
        for _ in range(2):
            result, _ = fit.one_fit(estimator, train, validation, opt, probe)
            marks.append((len(compiled), dict(retrace.all_traces())))
        steps = fit_crossed._plain_steps(probe.steps, ds)
    finally:
        probe.remove()
    return {"numbers": fit_crossed.check(config, ds, steps,
                                         fit._tracker(result)),
            "steps": steps, "marks": marks, "model": result.model,
            "trees": recent_trees("estimator.fit", 2),
            "estimator": estimator, "validation": validation}


# ------------------------------------------------ against the plain reference


@pytest.mark.parametrize("number", COMPARED)
def test_the_program_agrees_with_the_crossed_reference(number, two_fits,
                                                       limits):
    """Every number the cell compares, each under the cell's own limit
    (chip readings at 10 M rows: a float32 CPU fit of a thousandth of the
    rows sums less and reads well inside them)."""
    assert set(limits) == set(COMPARED)
    ok, compared = fit.judge({number: two_fits["numbers"][number]},
                             {number: limits[number]})
    assert ok, compared


def test_nothing_else_is_compared(two_fits):
    assert set(two_fits["numbers"]) == set(COMPARED)


@pytest.mark.parametrize("step", range(6))
def test_a_steps_offsets_are_the_other_two_coordinates_scores(step, two_fits):
    """What descent hands step ``k`` is the sum of the latest training
    scores of the two coordinates the step is not of, as the program gave
    them (zero for one that has not run); 1e-5: a float32 sum of two."""
    steps = two_fits["steps"]
    order = ["fixed", "user", "movie"] * 2
    assert [s["kind"] if s["kind"] == "fixed" else s["entity"]
            for s in steps] == order
    latest = {}
    for name, s in zip(order[:step], steps[:step]):
        latest[name] = s["scores"]
    want = sum((v for k, v in latest.items() if k != order[step]),
               np.zeros_like(steps[step]["offsets"]))
    np.testing.assert_allclose(steps[step]["offsets"], want, atol=1e-5)
    if step >= 2:
        assert np.abs(want).max() > 0.1


@pytest.mark.parametrize("which", ["a_single_row_movie", "the_largest_movie"])
def test_a_movies_intercept_is_the_penalized_optimum(which, two_fits, ds,
                                                     config):
    """The movie shard declares no intercept, so its one column is under
    the L2 term: ``b`` minimizes ``sum logloss(b + o_i, y_i) + l2/2 b^2``
    over the movie's rows, finite for a movie of one row or one label. A
    scalar Newton solve in float64 from the offsets the last per-movie
    step was handed."""
    tr = ds.train
    counts = np.bincount(tr.movies, minlength=ds.n_movies)
    movie = (int(np.flatnonzero(counts == 1)[0]) if which.startswith("a_")
             else int(np.argmax(counts)))
    step = two_fits["steps"][-1]
    assert step["entity"] == "movie" and step["w"].shape == (ds.n_movies, 1)
    rows = np.flatnonzero(tr.movies == movie)
    assert len(rows) == counts[movie] and (len(rows) == 1 or len(rows) > 1000)
    l2 = config["coordinates"][2]["reg_weight"]
    o, y, b = step["offsets"][rows], tr.y[rows], 0.0
    for _ in range(50):
        p = reference.sigmoid(b + o)
        b -= ((p - y).sum() + l2 * b) / ((p * (1 - p)).sum() + l2)
    assert abs((reference.sigmoid(b + o) - y).sum() + l2 * b) < 1e-9
    assert abs(b) > 1e-3
    assert step["w"][movie, 0] == pytest.approx(b, abs=2e-4)


@pytest.mark.parametrize("cid", sorted(KEYS))
def test_an_entity_a_coordinate_never_saw_scores_zero_from_it(cid, two_fits,
                                                              ds):
    """Validation rows of an unseen user score exactly 0 from the per-user
    model and whatever their (seen) movie's intercept gives from the
    per-movie model, and the other way round; the seen rows score what
    the reference's lookup of the trained coefficients gives."""
    va = ds.validation
    estimator = two_fits["estimator"]
    scorer = estimator._validation_cache[2].scorers[cid]
    got = np.asarray(scorer(two_fits["model"].models[cid]), np.float64)
    ids, idx, val = ((va.users, va.ui, va.uv) if cid == "perUser"
                     else (va.movies, va.mi, va.mv))
    unseen = ids < 0
    assert unseen.sum() == 8 and got.shape == (va.n_rows,)
    assert np.all(got[unseen] == 0.0)
    _, w = fit_crossed._entity_coefficients(two_fits["model"].models[cid], ds)
    want = reference.user_scores(ids, idx, val, w)
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert np.abs(got[~unseen]).max() > 0.1
    # the other key's unseen rows are on entities this coordinate knows
    other = (va.movies if cid == "perUser" else va.users) < 0
    assert not np.any(other & unseen)


# ---------------------------------------------------------------- the spans


@pytest.mark.parametrize("cid, width, classes", [
    ("perUser", 32, 5), ("perMovie", 1, None)])
def test_bucket_spans_name_their_coordinate_and_width(cid, width, classes,
                                                      two_fits):
    """Every ``optim.re_bucket`` span of a step carries the step's
    coordinate's entity column; the users' buckets are 32 local columns
    wide, the movies' 1."""
    tree = two_fits["trees"][1]
    dataset = two_fits["estimator"]._prep_cache[1]["train"][cid]
    steps = [s for s in tree if s[NAME] == "descent.step"
             and s[ARGS].get("coordinate") == cid]
    assert len(steps) == 2
    for step in steps:
        held = [s[ARGS] for s in tree if s[NAME] == "optim.re_bucket"
                and s[PARENT_ID] == step[SPAN_ID]]
        assert len(held) == len(dataset.buckets) == len(dataset.size_classes)
        assert {b["re_type"] for b in held} == {KEYS[cid]} == {dataset.re_type}
        assert {b["local_dim"] for b in held} == {width}
        assert [b["padded_rows"] for b in held] == list(dataset.size_classes)
        assert {b["solver"] for b in held} == {"newton_primal"}
        assert sum(b["rows"] for b in held) == step[ARGS]["rows"]
    if classes:
        assert len(dataset.size_classes) == classes
    else:
        assert dataset.size_classes[0] == 1          # movies of one row
        assert dataset.size_classes[-1] >= 2048


@pytest.mark.parametrize("scoring", [False, True])
@pytest.mark.parametrize("cid", sorted(KEYS))
def test_a_dataset_span_a_key(cid, scoring, two_fits, ds):
    """The first fit groups its rows by each key, the training rows and
    the validation rows: one ``data.re_dataset`` span each, none in the
    second fit."""
    first, second = two_fits["trees"]
    assert not [s for s in second if s[NAME] == "data.re_dataset"]
    spans = [s[ARGS] for s in first if s[NAME] == "data.re_dataset"]
    assert len(spans) == 4
    (args,) = [a for a in spans
               if (a["re_type"], a["scoring"]) == (KEYS[cid], scoring)]
    split = ds.validation if scoring else ds.train
    ids = split.users if cid == "perUser" else split.movies
    assert args["rows"] == split.n_rows
    assert args["entities"] == len(np.unique(ids))
    assert args["classes"] == args["buckets"] >= 3
    assert args["row_slots"] >= args["rows"]
    if not scoring:
        dataset = two_fits["estimator"]._prep_cache[1]["train"][cid]
        assert args["classes"] == len(dataset.size_classes)
        assert args["row_slots"] == dataset.row_slots


def test_a_second_fit_on_the_bundle_compiles_nothing(two_fits):
    (first, first_traces), (second, second_traces) = two_fits["marks"]
    assert first > 0                    # the listener hears this process
    assert second == first
    assert second_traces == first_traces
    classes = sum(len(d.size_classes) for d in
                  two_fits["estimator"]._prep_cache[1]["train"].values()
                  if hasattr(d, "size_classes"))
    assert first_traces.get("fit_bucket_newton", 0) >= classes


# ------------------------------------------------- shapes that keep to a seed


def _bucket_shapes(config: dict, seed: int) -> dict:
    """``(entities, padded rows, local width)`` of every bucket the
    program's own grouping builds, by coordinate and by whether the rows
    are the training or the validation rows."""
    data = fit_crossed.generate(config["data"], seed)
    estimator, train, validation, _ = fit_crossed.build(config, data)
    shapes = {}
    for cid in KEYS:
        dcfg = estimator.coordinate_data_configs[cid]
        for scoring, bundle in ((False, train), (True, validation)):
            dataset = build_re_dataset_from_bundle(
                bundle, dcfg, estimator._intercept_for(dcfg.feature_shard),
                for_scoring=scoring)
            shapes[cid, scoring] = [
                (b.n_entities, b.max_samples, b.local_dim)
                for b in dataset.buckets]
    return shapes


@pytest.fixture(scope="module")
def shapes_of_the_first_seed(config):
    return _bucket_shapes(config, 1)


@pytest.mark.parametrize("seed", [2, 3, 5, 8, 13, 21, 2**31 + 34,
                                  2**31 + 5555])
def test_every_seed_gives_the_same_bucket_shapes(seed, config,
                                                 shapes_of_the_first_seed):
    """For both keys, of the training and of the validation rows: a
    bucket's solver and a validation bucket's scorer are programs of its
    shape, and a fit's work is its buckets' shapes (PERF.md §7: hold a
    cell's work equal over the seeds)."""
    want = shapes_of_the_first_seed
    assert set(want) == {(c, s) for c in KEYS for s in (False, True)}
    assert _bucket_shapes(config, seed) == want
    assert {p for _, _, p in want["perUser", False]} == {32}
    assert {p for _, _, p in want["perMovie", False]} == {1}
