"""Job kind ``fit_crossed``: the jobs of kind ``fit`` (whole
``GameEstimator.fit`` calls from zero on resident data, back to back) on a
configuration with **two crossed per-entity coordinates**: a fixed effect,
a random effect keyed by user and a random effect keyed by movie, each row
holding one user and one movie.

What it shares it imports: from ``fit`` the coordinates' ``--coordinate``
strings (``_spec``), the wrappers around the coordinates (``Probe``), one
timed fit (``one_fit``), a fixed-effect step as plain arrays
(``_plain_steps``, a step at a time), the comparison of a fixed-effect step
with the stated optimizer (``_fixed_step``), ``_max_gap``, ``_tracker``,
``_counters`` and the judgement (``judge``); from ``fit_ragged`` the users'
counts (``user_counts``: the same sequence and the same cut as
``game-logistic-ragged-re``'s, to the letter); from ``datagen`` the rows
(``_rows``); from ``references/entities_ragged.py`` the per-entity problems
over any entity column. Its own are:

* **the movies' column**: ``movie_counts`` gives every movie its number of
  rows from a fixed sequence, the same for every seed, scaled to the rows
  the users hold; the seed shuffles which movie gets which count and lays
  the repeated movie list over the rows (the pairing). Both keys' bucket
  shapes are therefore the same for every seed: one set of programs.
* **three shards and two id tags** (``build``): ``global``, ``user`` and
  ``movie`` (one column of value 1, no intercept declared: a penalized
  random intercept).
* **the comparison** (``check``): ``fit_ragged.check``'s numbers, with
  ``offsets`` of each step against the sum of the *other two* coordinates'
  latest scores, ``re_resid`` apart for each per-entity coordinate
  (``re_resid_user``, ``re_resid_movie``: the worst over that coordinate's
  size classes), ``grad_mid`` (the median gradient gap over a path from
  zero: what holds L-BFGS's memory at this cell's short cap), and
  validation from all three models, an unseen user or movie scoring zero
  from the coordinate that does not know it.

README_fit_crossed.md has the parameters.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import statistics
import time

import numpy as np

from benchmarks import datagen, reference
from benchmarks.kinds import fit, fit_ragged
# Bound here, by name: ``tests/faults_crossed.py`` puts this kind's
# ``_plain_steps`` in ``fit``'s place for ``faults_ragged.main``.
from benchmarks.kinds.fit import _plain_steps as _fixed_plain_steps
from benchmarks.layer_metrics import _spans
from benchmarks.references import entities_ragged

USER_KEY, MOVIE_KEY = "user", "movie"


# ------------------------------------------------------------ the data


@dataclasses.dataclass(frozen=True)
class Split(datagen.Split):
    """``datagen.Split`` and the row's movie (``-1 - k`` for the k-th
    unseen movie). The movie shard is one column of value 1: ``mi``,
    ``mv``."""

    movies: np.ndarray = None

    @property
    def mi(self) -> np.ndarray:
        return np.zeros((self.n_rows, 1), np.int64)

    @property
    def mv(self) -> np.ndarray:
        return np.ones((self.n_rows, 1))


@dataclasses.dataclass(frozen=True)
class Dataset(datagen.Dataset):
    n_movies: int = 0
    movie_dim: int = 1


def _scaled(counts: np.ndarray, rows: int, least: int) -> np.ndarray:
    """``counts`` scaled to sum to ``rows``: each count's whole part of
    ``count x rows / sum``, at least ``least``, and one more for the
    largest remainders until the sum is ``rows``."""
    scaled = counts * (rows / counts.sum())
    out = np.maximum(np.floor(scaled), least).astype(np.int64)
    short = rows - int(out.sum())
    if not 0 <= short <= len(out):
        raise ValueError(f"{len(out)} counts of at least {least} cannot be "
                         f"scaled to {rows} rows: {short} left after rounding")
    out[np.argsort(out - scaled, kind="stable")[:short]] += 1
    return out


def movie_counts(data: dict, rows: int) -> np.ndarray:
    """Rows a movie, summing to ``rows``: the quantiles
    ``(i + 0.5) / of_movies`` of a log-normal with the stated ``median``
    and ``sigma``, rounded and clipped to ``[min, max]`` (the source's
    counts), then scaled to ``rows`` with every movie at least ``min``."""
    r = data["rows_per_movie"]
    normal = statistics.NormalDist()
    q = np.array([normal.inv_cdf((i + 0.5) / r["of_movies"])
                  for i in range(r["of_movies"])])
    source = np.clip(np.rint(np.exp(np.log(r["median"]) + r["sigma"] * q)),
                     r["min"], r["max"])
    return _scaled(source, rows, r["min"])


def _truth(data: dict, seed: int) -> dict:
    """The true effects: ``datagen.generate``'s draws (fixed effect, per-
    user coefficients and intercepts) and per-movie intercepts N(0, 1)."""
    d, rng = data, np.random.default_rng([seed, 0])
    wg = rng.normal(size=d["named_features"]) * 0.3
    wg[: d["head_features"]] = rng.normal(size=d["head_features"]) * 1.5
    return {"wg": wg,
            "wu": rng.normal(size=(d["users"], d["user_features"])),
            "bu": rng.normal(size=d["users"]) * 2.0,
            "bm": rng.normal(size=d["movies"])}


def _split(rng, d: dict, users, movies, truth: dict) -> Split:
    """``datagen._rows``' two shards for these rows, and labels drawn from
    the logistic model with the row's movie's intercept added (an unseen
    user or movie adds nothing)."""
    n = len(users)
    s = datagen._rows(rng, d, users, n, truth["wg"], truth["wu"], truth["bu"])
    kg, ku = d["named_nnz"], d["user_nnz"]
    u, m = np.maximum(users, 0), np.maximum(movies, 0)
    z = (s.gv[:, :kg] * truth["wg"][s.gi[:, :kg]]).sum(1)
    z += (users >= 0) * (truth["bu"][u] + (
        s.uv[:, :ku] * truth["wu"][u[:, None], s.ui[:, :ku]]).sum(1))
    z += (movies >= 0) * truth["bm"][m]
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-z))).astype(np.float64)
    return Split(gi=s.gi, gv=s.gv, y=y, users=users, ui=s.ui, uv=s.uv,
                 movies=movies)


def generate(data: dict, seed: int) -> Dataset:
    """The two splits for ``seed``. The seed shuffles which user and which
    movie gets which count (streams 3 and 4), the order of the users'
    rows and, independently, the order of the repeated movie list laid
    over them (the pairing), and draws the rows. The counts themselves,
    of the training and of the validation rows, are the same for every
    seed."""
    d = data
    per_user = fit_ragged.user_counts(d)
    if len(per_user) != d["users"]:
        raise ValueError(f"data.users is {d['users']}, and "
                         f"data.rows_per_user gives {len(per_user)} users")
    per_movie = movie_counts(d, int(per_user.sum()))
    if len(per_movie) != d["movies"]:
        raise ValueError(f"data.movies is {d['movies']}, and "
                         f"data.rows_per_movie gives {len(per_movie)} movies")
    np.random.default_rng([seed, 3]).shuffle(per_user)
    pairing = np.random.default_rng([seed, 4])
    pairing.shuffle(per_movie)
    truth = _truth(d, seed)

    rng = np.random.default_rng([seed, 1])
    users = np.repeat(np.arange(d["users"]), per_user)
    rng.shuffle(users)
    movies = np.repeat(np.arange(d["movies"]), per_movie)
    pairing.shuffle(movies)
    train = _split(rng, d, users, movies, truth)

    # Validation rows have fixed counts a user and a movie too, so that
    # the validation buckets' shapes do not follow the seed either: every
    # user its rows, the unseen users theirs, these rows' movies by
    # popularity (a movie's training count scaled to them, movies left
    # with none absent); then the unseen movies' rows, each movie's on a
    # seen user of its own.
    v = d["validation"]
    seen = np.repeat(np.arange(d["users"]), v["rows_per_user"])
    strangers = -1 - np.repeat(np.arange(v["unseen_users"]), v["unseen_rows"])
    by_popularity = np.repeat(
        np.arange(d["movies"]),
        _scaled(per_movie, len(seen) + len(strangers), 0))
    pairing.shuffle(by_popularity)
    premieres = -1 - np.repeat(np.arange(v["unseen_movies"]),
                               v["unseen_movie_rows"])
    their_users = np.repeat(
        pairing.permutation(d["users"])[: v["unseen_movies"]],
        v["unseen_movie_rows"])
    val = _split(np.random.default_rng([seed, 2]), d,
                 np.concatenate([seen, strangers, their_users]),
                 np.concatenate([by_popularity, premieres]), truth)
    return Dataset(train=train, validation=val,
                   global_dim=d["named_features"] + 1,
                   user_dim=d["user_features"] + 1, n_users=d["users"],
                   n_movies=d["movies"])


def entity_keys(prefix: str, ids: np.ndarray) -> np.ndarray:
    """Entity ids per row as the program's id-tag column wants them:
    ``<prefix><id>``, and ``new_<prefix><k>`` for the k-th unseen one."""
    return np.array([f"{prefix}{i}" if i >= 0 else f"new_{prefix}{-1 - i}"
                     for i in ids.tolist()], dtype=object)


def shapes(config: dict, ds: Dataset) -> dict:
    """What the per-layer readers count work from. ``benchmarks/work.py``
    states one kind of entity, so ``rows_per_user``, ``user_nnz`` and
    ``user_dim`` are **pooled over both per-entity coordinates**: a row is
    in one user's problem and in one movie's, so the mean rows an entity
    are twice the rows over users + movies, the mean entries a row those
    of the two shards, the mean width the entities' (PERF.md §7)."""
    d = config["data"]
    n, entities = ds.train.n_rows, ds.n_users + ds.n_movies
    return {"rows": n, "validation_rows": ds.validation.n_rows,
            "global_dim": ds.global_dim, "global_nnz": d["named_nnz"] + 1,
            "users": ds.n_users, "movies": ds.n_movies,
            "rows_per_user": 2 * n / entities,
            "user_nnz": (d["user_nnz"] + 1 + ds.movie_dim) / 2,
            "user_dim": (ds.n_users * ds.user_dim
                         + ds.n_movies * ds.movie_dim) / entities}


# ------------------------------------------------------------- the program


def _bundle(split: Split, ds: Dataset, dtype, tags: dict):
    import jax.numpy as jnp

    from photon_tpu.data.batch import SparseFeatures
    from photon_tpu.io.data_reader import GameDataBundle

    def shard(idx, val, dim):
        return SparseFeatures(idx=jnp.asarray(idx, jnp.int32),
                              val=jnp.asarray(val, dtype), dim=dim)

    n = split.n_rows
    return GameDataBundle(
        features={"global": shard(split.gi, split.gv, ds.global_dim),
                  "user": shard(split.ui, split.uv, ds.user_dim),
                  "movie": shard(split.mi, split.mv, ds.movie_dim)},
        labels=split.y.astype(dtype), offsets=np.zeros(n, dtype),
        weights=np.ones(n, dtype), uids=np.arange(n),
        id_tags={tags["user"]: entity_keys(USER_KEY, split.users),
                 tags["movie"]: entity_keys(MOVIE_KEY, split.movies)})


def build(config: dict, ds: Dataset):
    """(estimator, training bundle, validation bundle, optimization
    configurations), as ``fit.build`` gives them, for three shards and two
    id tags. The movie shard names no intercept: its one column is
    penalized (the configuration's ``assumed.movie_intercept``)."""
    from photon_tpu.cli.params import configs_from_specs, parse_coordinates
    from photon_tpu.estimators.game_estimator import GameEstimator
    from photon_tpu.types import TaskType

    coords = config["coordinates"]
    data_configs, opt_configs = configs_from_specs(
        parse_coordinates([fit._spec(c) for c in coords]))
    dtype = np.dtype(config["dtype"])
    tags = {c["shard"]: c["entity"] for c in coords if c["kind"] == "random"}
    estimator = GameEstimator(
        task=TaskType[config["task"]],
        coordinate_data_configs=data_configs,
        update_sequence=tuple(c["id"] for c in coords),
        n_sweeps=config["sweeps"],
        evaluator_specs=tuple(config["evaluators"]),
        intercept_indices={"global": ds.global_dim - 1,
                           "user": ds.user_dim - 1},
    )
    return (estimator, _bundle(ds.train, ds, dtype, tags),
            _bundle(ds.validation, ds, dtype, tags), opt_configs)


def _entity_coefficients(model, ds: Dataset) -> tuple[str, np.ndarray]:
    """(``"user"`` or ``"movie"``, the per-entity coefficients ``[E, P]``
    of a RandomEffectModel in its shard's own columns, rows by the entity
    the generator's key names). Which of the two the model is, its keys
    say."""
    which = (MOVIE_KEY if str(model.entity_keys[0]).startswith(MOVIE_KEY)
             else USER_KEY)
    n, dim = ((ds.n_movies, ds.movie_dim) if which == MOVIE_KEY
              else (ds.n_users, ds.user_dim))
    out = np.zeros((n, dim))
    named = np.array([int(str(k)[len(which):]) for k in model.entity_keys])
    for coefs, proj, ids in zip(model.bucket_coefs, model.bucket_proj,
                                model.bucket_entity_ids):
        coefs, proj, ids = (np.asarray(a) for a in (coefs, proj, ids))
        live = ids >= 0
        e = np.broadcast_to(named[np.where(live, ids, 0)][:, None], proj.shape)
        ok = live[:, None] & (proj < dim)
        out[e[ok], proj[ok]] = coefs[ok]
    return which, out


def _plain_steps(steps: list, ds: Dataset) -> list:
    """The kept steps as NumPy, as ``fit._plain_steps`` gives them; a
    random-effect step also says which key it is of (``entity``)."""
    out = []
    for s in steps:
        if s["kind"] == "fixed":
            out.extend(_fixed_plain_steps([s], ds))
            continue
        entity, w = _entity_coefficients(s["out"][0], ds)
        out.append({"kind": s["kind"], "entity": entity, "w": w,
                    "offsets": np.asarray(s["offsets"], np.float64),
                    "scores": np.asarray(s["scores"], np.float64)})
    return out


# ---------------------------------------------------------- the comparison


def _problems(config: dict, ds: Dataset) -> dict:
    """Each coordinate's stated problem, by coordinate id; a per-entity
    coordinate's with the column of its rows that names its entity."""
    tr = ds.train
    per_entity = {
        "user": (tr.users, tr.ui, tr.uv, ds.n_users, ds.user_dim,
                 ds.user_dim - 1),
        "movie": (tr.movies, tr.mi, tr.mv, ds.n_movies, ds.movie_dim, None)}
    problems = {}
    for c in config["coordinates"]:
        if c["kind"] == "fixed":
            problems[c["id"]] = reference.objective(config["task"])(
                idx=tr.gi, val=tr.gv, y=tr.y, offsets=np.zeros(tr.n_rows),
                dim=ds.global_dim, l2=c["reg_weight"],
                intercept=ds.global_dim - 1)
        else:
            ids, idx, val, n, dim, intercept = per_entity[c["shard"]]
            problems[c["id"]] = entities_ragged.RaggedUserLogistic.build(
                ids, idx, val, tr.y, n, dim, c["reg_weight"], intercept)
    return problems


def _validation_scores(latest: dict, va: Split) -> np.ndarray:
    """The reference's validation scores of the latest model of every
    coordinate; an unseen user or movie (negative id) scores 0 from the
    coordinate that does not know it."""
    scores = np.zeros(va.n_rows)
    for s in latest.values():
        if s["kind"] == "fixed":
            scores += reference.sparse_scores(va.gi, va.gv, s["w"])
        elif s["entity"] == MOVIE_KEY:
            scores += reference.user_scores(va.movies, va.mi, va.mv, s["w"])
        else:
            scores += reference.user_scores(va.users, va.ui, va.uv, s["w"])
    return scores


def check(config: dict, ds: Dataset, steps: list, tracker: list,
          paths: list = None) -> dict:
    """The numbers ``fit_ragged.check`` compares, by the same names and of
    the same steps (``PERF.md`` §2), over three coordinates: ``offsets`` of
    a step is held against the reference's sum of the other two
    coordinates' latest scores, and the per-entity residual apart for each
    key (``re_resid_user``, ``re_resid_movie``), each the worst of any
    size class of that key after any of its steps. One number is this
    kind's own: ``grad_mid``, of a step from zero the median, over the
    iterations the reference can vouch for, of the relative gap between
    the two gradient norms (``loss_mid``'s statistic on the gradient).
    Under this cell's cap of 6 at 21 columns ``grad3`` (the worst of the
    first three) reads 2.6e-3 on a sound seed whose third line search
    rounding decided, half of what one curvature pair for ten reads
    there; the median over the path reads that fault at 0.27, because a
    short memory's gradient gap grows with every iteration where a parted
    sound path's stays where it parted (PERF.md §2)."""
    coords = config["coordinates"]
    by_id = {c["id"]: c for c in coords}
    order = [c["id"] for c in coords] * config["sweeps"]
    said = [s["kind"] if s["kind"] == "fixed" else s["entity"] for s in steps]
    want = [by_id[c]["kind"] if by_id[c]["kind"] == "fixed"
            else by_id[c]["shard"] for c in order]
    if said != want:
        return {"steps_missing": float(max(1, abs(len(order) - len(steps))))}
    tr, va = ds.train, ds.validation
    problems = _problems(config, ds)

    latest: dict = {}
    train_scores: dict = {}
    gaps: dict = {}

    def hold(name: str, value: float) -> None:
        value = float(value) if np.isfinite(value) else float("inf")
        gaps[name] = max(gaps.get(name, 0.0), value)

    for cid, s, t in zip(order, steps, tracker):
        c, problem = by_id[cid], problems[cid]
        expected = sum((v for k, v in train_scores.items() if k != cid),
                       np.zeros(tr.n_rows))
        hold("offsets", fit._max_gap(s["offsets"], expected))
        if s["kind"] == "fixed":
            problem.offsets = s["offsets"]
            said: list = []
            fit._fixed_step(c, problem, s, hold, said)
            for path in said:
                sure = min(path["ran"], path["sure"])
                if path["cold"] and sure >= fit.FIRST_STEPS:
                    hold("grad_mid", np.median(path["grad_gap"][1:sure + 1]))
            if paths is not None:
                paths.extend(said)
            train_scores[cid] = reference.sparse_scores(tr.gi, tr.gv, s["w"])
        else:
            by_class = problem.residual_by_class(s["w"], s["offsets"])
            hold("re_resid_" + s["entity"], max(by_class.values()))
            if paths is not None:
                paths.append({"coordinate": cid,
                              "re_resid_by_class": by_class})
            train_scores[cid] = problem.scores(s["w"])
        hold("scores", fit._max_gap(s["scores"], train_scores[cid]))
        latest[cid] = s
        scores = _validation_scores(latest, va)
        for name in config["evaluators"]:
            hold("val_" + name.lower(), reference.evaluator_gap(
                name, t["validation"][name], scores, va.y))
    return gaps


# ------------------------------------------------------------------ a run


def _warmup_tree():
    """The span tree the program kept of the fit just made (the warm-up
    fit: the one that prepares), or None from a program that keeps none."""
    kept = _spans.trees({"trackers": [None]})
    return kept[0] if kept else None


def run(cell: dict, config: dict, mix: dict, limits: dict, seed: int,
        seconds: float, trace_dir, t_start: float, say) -> dict:
    """Set-up, window and comparison of one run, as ``fit_ragged.run``
    makes them (the same earlier lines, the same state for the readers,
    and the warm-up fit's span tree beside it: ``warmup_tree``)."""
    import jax

    from photon_tpu.runtime import compile_store

    if mix.get("start") != "zero" or mix.get("checkpointing"):
        raise ValueError(f"job mix not understood by kind 'fit_crossed': {mix}")
    compile_store.install_accounting()
    cache_dir = compile_store.enable_compilation_cache(min_compile_secs=0.0)
    marks = {"imports_s": time.perf_counter() - t_start}

    t = time.perf_counter()
    ds = generate(config["data"], seed)
    marks["datagen_s"] = time.perf_counter() - t
    t = time.perf_counter()
    estimator, train, validation, opt_configs = build(config, ds)
    marks["bundle_s"] = time.perf_counter() - t

    probe = fit.Probe()
    probe.install()
    try:
        result, warm_s = fit.one_fit(estimator, train, validation,
                                     opt_configs, probe)
        marks["warmup_fit_s"] = warm_s
        warmup_tree = _warmup_tree()
        setup_counters = fit._counters()
        setup_s = time.perf_counter() - t_start
        say({"setup": marks, "setup_s": setup_s, "cache_dir": cache_dir,
             "counters": setup_counters, "shapes": shapes(config, ds),
             "warmup_tracker": fit._tracker(result),
             "warmup_spans": {
                 k: round(v, 3) for k, v in
                 _spans.self_seconds(warmup_tree or []).items()}})

        trackers, fit_seconds = [], []
        tracing = contextlib.nullcontext()
        if trace_dir:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            tracing = jax.profiler.trace(trace_dir, profiler_options=options)
        with tracing:
            w0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.window"):
                while time.perf_counter() - w0 < seconds:
                    result, s = fit.one_fit(estimator, train, validation,
                                            opt_configs, probe)
                    trackers.append(fit._tracker(result))
                    fit_seconds.append(s)
            window_s = time.perf_counter() - w0
        window_counters = fit._counters()
        steps = _plain_steps(probe.steps, ds)
    finally:
        probe.remove()

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices())
    del estimator, train, validation, result, probe
    gc.collect()

    t = time.perf_counter()
    correct, compared = fit.judge(check(config, ds, steps, trackers[-1]),
                                  limits)
    check_s = time.perf_counter() - t
    say({"window_s": window_s, "fits": len(trackers),
         "fit_seconds": fit_seconds, "check_s": check_s,
         "trackers": trackers})
    return {
        "correct": correct, "compared": compared,
        "attempted": len(trackers), "failed": 0,
        "end_to_end": {"fit_s": window_s / len(trackers), "setup_s": setup_s},
        "state": {
            "cell": cell, "config": config, "shapes": shapes(config, ds),
            "window_s": window_s, "trackers": trackers,
            "fit_seconds": fit_seconds, "memory_peak_bytes": peak,
            "setup_counters": setup_counters,
            "window_counters": window_counters,
            "warmup_tree": warmup_tree,
        },
    }
