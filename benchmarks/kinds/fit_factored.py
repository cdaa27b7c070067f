"""Job kind ``fit_factored``: the jobs of kind ``fit`` (whole
``GameEstimator.fit`` calls from zero on resident data, back to back) on a
configuration with a fixed effect and a **factored random effect**: each
user's model over the item ids is ``w_u = P b_u``, a projection over the
items every user shares and a latent vector a user (the regression-based
latent factor model, matrix factorization written as a GAME coordinate).

What it shares it imports: from ``fit`` the coordinate strings' form
(``_spec``), ``Probe``, a fixed-effect step as plain arrays and its
comparison with the stated optimizer (``_plain_steps``, ``_fixed_step``),
``_max_gap``, ``_tracker``, ``_counters``, ``judge``, ``FIRST_STEPS``;
from ``fit_ragged``
the users' counts (``user_counts``) and from ``fit_crossed`` the movies'
(``movie_counts``, ``_scaled``): the same sequences and the same cut as
``game-logistic-crossed-re``'s, to the letter; from ``datagen`` the fixed
shard's rows (``_rows``); from ``references/factored_re.py`` the factored
coordinate's mathematics. Its own are:

* **the pairing** (``pairing``): which user rates which movie, with no
  pair twice (a rating is one user's of one movie). The users' row slots
  are dealt round by round (every user's first row, then every second
  row, ...), in an order the seed draws, and the movies' rows, the most
  rated first, are laid along them: a movie's rows fall on as many
  different users. Every user's local width (the movies it rated) is
  then its rows, so every seed gives the same buckets;
* **the labels**: the fixed effect on the genres and a rank-``r`` term
  ``u_f[user] . v_f[movie]`` (``truth``);
* **the program's sub-steps**: the probe also keeps what each of the
  factored step's sub-steps (``factored_random_effect.subspace_init``,
  ``latent_step``, ``projection_step``) was given and gave;
* **the comparison** (``check``): for the fixed steps ``fit``'s numbers
  that a fault read in this cell moves (``FIXED_HELD``) and
  ``fit_crossed``'s ``grad_mid``; for a factored step ``init`` (the
  initial projection's distance from the reference's), ``latent_resid``
  (the worst size class's residual of the latent problems after every
  latent step, at the projection that step was given), ``proj_grad0``,
  ``proj_final_loss`` and ``proj_iterations`` (iterations short of the
  stated cap) of every projection step, ``scores`` (the training rows'
  scores against the reference's latent-space scores of the returned
  model) and validation from both models, an unseen user or movie
  scoring zero from the factored one.

README_fit_factored.md has the parameters.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import sys
import time

import numpy as np

from benchmarks import datagen, reference
from benchmarks.kinds import fit, fit_crossed, fit_ragged
from benchmarks.layer_metrics import _spans
from benchmarks.references import factored_re

# What the program has to offer for this kind to run: a factored random
# effect that trains and scores in its latent space.
PROGRAM_API = ("subspace_init", "latent_step", "projection_step",
               "flat_rows", "FlatRows")


# ------------------------------------------------------------ the data


@dataclasses.dataclass(frozen=True)
class Split(datagen.Split):
    """``datagen.Split`` and the row's movie (``-1 - k`` for the k-th
    unseen movie). The factored shard holds one entry a row: the movie's
    column with value 1 (an unseen movie: none)."""

    movies: np.ndarray = None


@dataclasses.dataclass(frozen=True)
class Dataset(datagen.Dataset):
    n_movies: int = 0


def pairing(per_user: np.ndarray, per_movie: np.ndarray, rng
            ) -> tuple[np.ndarray, np.ndarray]:
    """(users, movies) of the rows, in slot order: user ``u`` holds
    ``per_user[u]`` rows and movie ``m`` ``per_movie[m]``, and no pair
    occurs twice. The slots are dealt round-major (round ``j`` holds the
    ``j``-th row of every user with more than ``j``, in an order ``rng``
    draws) and the movies, most rated first, are laid along them: while a
    movie's rows fit in the rounds they span they fall on different users,
    and the most rated go to the fullest rounds. A pair that still repeats
    (none at game-logistic-factored-re's counts; some at a test's) swaps
    its movie with another slot's where neither pair then repeats."""
    order = rng.permutation(len(per_user))
    counts = per_user[order]
    position = np.repeat(np.arange(len(counts)), counts)
    within = np.arange(len(position)) - np.repeat(
        np.cumsum(counts) - counts, counts)
    users = order[position[np.lexsort((position, within))]]
    most_first = np.argsort(-per_movie, kind="stable")
    movies = np.repeat(most_first, per_movie[most_first])
    stride = np.int64(len(per_movie))
    pairs = users * stride + movies
    _, first = np.unique(pairs, return_index=True)
    repeated = np.setdiff1d(np.arange(len(pairs)), first)
    taken = set(pairs[first].tolist())
    for at in repeated.tolist():
        for other in rng.integers(0, len(pairs), 10000).tolist():
            mine = int(users[at] * stride + movies[other])
            theirs = int(users[other] * stride + movies[at])
            if (mine not in taken and theirs not in taken
                    and mine != theirs):
                taken.discard(int(users[other] * stride + movies[other]))
                taken.update((mine, theirs))
                movies[at], movies[other] = movies[other], movies[at]
                break
        else:
            raise ValueError("the counts cannot be paired without "
                             "repeating a (user, movie) pair")
    return users, movies


def truth(data: dict, seed: int) -> dict:
    """The true effects: ``datagen.generate``'s fixed-effect draw, and the
    factors: ``u_f [users, r]`` N(0, 1) and ``v_f [movies, r]``
    N(0, scale^2 / r), so that the planted term has variance scale^2."""
    d, rng = data, np.random.default_rng([seed, 0])
    wg = rng.normal(size=d["named_features"]) * 0.3
    wg[: d["head_features"]] = rng.normal(size=d["head_features"]) * 1.5
    r = d["factors"]["rank"]
    return {"wg": wg,
            "uf": rng.normal(size=(d["users"], r)),
            "vf": rng.normal(size=(d["movies"], r))
            * (d["factors"]["scale"] / np.sqrt(r))}


def _split(rng, d: dict, users, movies, t: dict) -> Split:
    """``datagen._rows``' fixed shard for these rows, and labels drawn from
    the logistic model with the planted term (an unseen user or movie adds
    nothing)."""
    n = len(users)
    s = datagen._rows(rng, d, None, n, t["wg"], None, None)
    kg = d["named_nnz"]
    z = (s.gv[:, :kg] * t["wg"][s.gi[:, :kg]]).sum(1)
    known = (users >= 0) & (movies >= 0)
    u, m = np.maximum(users, 0), np.maximum(movies, 0)
    for lo in range(0, n, 1 << 20):      # a block of rows at a time
        b = slice(lo, lo + (1 << 20))
        z[b] += known[b] * np.einsum("nr,nr->n", t["uf"][u[b]], t["vf"][m[b]])
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-z))).astype(np.float64)
    return Split(gi=s.gi, gv=s.gv, y=y, users=users, movies=movies)


def generate(data: dict, seed: int) -> Dataset:
    """The two splits for ``seed``. The seed shuffles which user and which
    movie gets which count (streams 3 and 4), the order the users are
    dealt in, the order of the rows, and draws them. The counts, of the
    training and of the validation rows, are the same for every seed."""
    d = data
    per_user = fit_ragged.user_counts(d)
    if len(per_user) != d["users"]:
        raise ValueError(f"data.users is {d['users']}, and "
                         f"data.rows_per_user gives {len(per_user)} users")
    per_movie = fit_crossed.movie_counts(d, int(per_user.sum()))
    if len(per_movie) != d["movies"]:
        raise ValueError(f"data.movies is {d['movies']}, and "
                         f"data.rows_per_movie gives {len(per_movie)} movies")
    np.random.default_rng([seed, 3]).shuffle(per_user)
    dealing = np.random.default_rng([seed, 4])
    dealing.shuffle(per_movie)
    t = truth(d, seed)

    rng = np.random.default_rng([seed, 1])
    users, movies = pairing(per_user, per_movie, dealing)
    rows = rng.permutation(len(users))
    train = _split(rng, d, users[rows], movies[rows], t)

    # Validation as game-logistic-crossed-re's: every user its rows, the
    # unseen users theirs, these rows' movies by popularity; then the
    # unseen movies' rows, each movie's on a seen user of its own.
    v = d["validation"]
    seen = np.repeat(np.arange(d["users"]), v["rows_per_user"])
    strangers = -1 - np.repeat(np.arange(v["unseen_users"]), v["unseen_rows"])
    by_popularity = np.repeat(
        np.arange(d["movies"]),
        fit_crossed._scaled(per_movie, len(seen) + len(strangers), 0))
    dealing.shuffle(by_popularity)
    premieres = -1 - np.repeat(np.arange(v["unseen_movies"]),
                               v["unseen_movie_rows"])
    their_users = np.repeat(
        dealing.permutation(d["users"])[: v["unseen_movies"]],
        v["unseen_movie_rows"])
    val = _split(np.random.default_rng([seed, 2]), d,
                 np.concatenate([seen, strangers, their_users]),
                 np.concatenate([by_popularity, premieres]), t)
    return Dataset(train=train, validation=val,
                   global_dim=d["named_features"] + 1, user_dim=0,
                   n_users=d["users"], n_movies=d["movies"])


def factored_shard(split: Split, n_movies: int) -> tuple:
    """``(idx, val)`` ``[n, 1]`` of the factored shard: the row's movie,
    value 1; an unseen movie's row holds the padding column (``n_movies``),
    value 0."""
    known = split.movies >= 0
    return (np.where(known, split.movies, n_movies)[:, None],
            known.astype(np.float64)[:, None])


def _factored(config: dict) -> dict:
    return next(c for c in config["coordinates"] if c["kind"] == "factored")


def shapes(config: dict, ds: Dataset) -> dict:
    """What the per-layer readers count work from: ``fit``'s, the users and
    movies, and the factored shard's width, entries a row and latent
    dimension."""
    d, c = config["data"], _factored(config)
    return {"rows": ds.train.n_rows, "validation_rows": ds.validation.n_rows,
            "global_dim": ds.global_dim, "global_nnz": d["named_nnz"] + 1,
            "users": ds.n_users, "movies": ds.n_movies,
            "factored_dim": ds.n_movies, "factored_nnz": 1,
            "latent_dim": c["latent_dim"]}


# ------------------------------------------------------------- the program


def require_program() -> None:
    """Stop, with one line on standard error, where the program has no
    latent-space factored random effect (exit 1, before any data)."""
    try:
        from photon_tpu.game import factored_random_effect as f
    except ImportError as e:
        missing = str(e)
    else:
        absent = [n for n in PROGRAM_API if not hasattr(f, n)]
        if not absent:
            return
        missing = ", ".join(absent)
    print(f"fit_factored: the program's factored random effect does not "
          f"train in its latent space (missing: {missing})", file=sys.stderr)
    raise SystemExit(1)


def _spec(c: dict) -> str:
    """``fit._spec`` and a factored coordinate's own keys."""
    s = fit._spec(c)
    if c["kind"] == "factored":
        s += (f",re_type={c['entity']},latent={c['latent_dim']},"
              f"alternations={c['alternations']}")
    return s


def _bundle(split: Split, ds: Dataset, dtype, config: dict):
    import jax.numpy as jnp

    from photon_tpu.data.batch import SparseFeatures
    from photon_tpu.io.data_reader import GameDataBundle

    c = _factored(config)
    fi, fv = factored_shard(split, ds.n_movies)
    n = split.n_rows
    return GameDataBundle(
        features={
            "global": SparseFeatures(idx=jnp.asarray(split.gi, jnp.int32),
                                     val=jnp.asarray(split.gv, dtype),
                                     dim=ds.global_dim),
            c["shard"]: SparseFeatures(idx=jnp.asarray(fi, jnp.int32),
                                       val=jnp.asarray(fv, dtype),
                                       dim=ds.n_movies)},
        labels=split.y.astype(dtype), offsets=np.zeros(n, dtype),
        weights=np.ones(n, dtype), uids=np.arange(n),
        # A user's key is its number; the k-th unseen user's -1 - k.
        id_tags={c["entity"]: split.users.astype(np.int64)})


def build(config: dict, ds: Dataset):
    """(estimator, training bundle, validation bundle, optimization
    configurations), as ``fit.build`` gives them, for the fixed shard and
    the factored one. The factored shard names no intercept: a user's
    model is over the movies alone."""
    from photon_tpu.cli.params import configs_from_specs, parse_coordinates
    from photon_tpu.estimators.game_estimator import GameEstimator
    from photon_tpu.types import TaskType

    coords = config["coordinates"]
    data_configs, opt_configs = configs_from_specs(
        parse_coordinates([_spec(c) for c in coords]))
    dtype = np.dtype(config["dtype"])
    estimator = GameEstimator(
        task=TaskType[config["task"]],
        coordinate_data_configs=data_configs,
        update_sequence=tuple(c["id"] for c in coords),
        n_sweeps=config["sweeps"],
        evaluator_specs=tuple(config["evaluators"]),
        intercept_indices={"global": ds.global_dim - 1},
        seed=config["init_seed"],
    )
    return (estimator, _bundle(ds.train, ds, dtype, config),
            _bundle(ds.validation, ds, dtype, config), opt_configs)


class Probe(fit.Probe):
    """``fit.Probe`` and the factored coordinate: its steps are kept as
    ``kind`` ``factored``, with ``subs``, what each sub-step was given and
    gave (device arrays, read only after the window)."""

    def install(self) -> None:
        from photon_tpu.game import coordinates as co
        from photon_tpu.game import factored_random_effect as f

        super().install()
        subs: list = []
        steps = self.steps

        def wrap_train(original):
            import jax

            def train(coord, offsets, init=None):
                subs.clear()
                with jax.profiler.TraceAnnotation("bench.factored.train"):
                    out = original(coord, offsets, init)
                steps.append({"kind": "factored", "offsets": offsets,
                              "init": init, "out": out, "subs": list(subs)})
                return out
            return train

        def keep(name, record):
            original = getattr(f, name)

            def wrapped(*args):
                out = original(*args)
                subs.append(record(args, out))
                return out
            setattr(f, name, wrapped)
            self._undo.append((f, name, original))

        original = co.FactoredRandomEffectCoordinate.train
        co.FactoredRandomEffectCoordinate.train = wrap_train(original)
        self._undo.append((co.FactoredRandomEffectCoordinate, "train",
                           original))
        self._wrap(co.FactoredRandomEffectCoordinate, "score",
                   "bench.factored.score", "score")
        keep("subspace_init", lambda a, out: {"sub": "init", "P": out})
        keep("latent_step", lambda a, out: {
            "sub": "latent", "P": a[3], "lats": out[0]})
        keep("projection_step", lambda a, out: {
            "sub": "projection", "P_in": a[2], "lats": a[4], "P": out[0],
            "result": out[1]})


def one_fit(estimator, train, validation, opt_configs, probe):
    """``fit.one_fit`` for this kind's models: each fit ends in a
    device-to-host read of every coefficient (a factored model's
    projection and latent vectors)."""
    import jax

    probe.steps.clear()
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.fit"):
        result = estimator.fit(train, validation, opt_configs)[0]
        for model in result.model.models.values():
            if hasattr(model, "projection"):
                np.asarray(model.projection)
                for b in model.bucket_latent:
                    np.asarray(b)
            else:
                np.asarray(model.model.coefficients.means)
    return result, time.perf_counter() - t0


def _latent_by_user(model, n_users: int) -> callable:
    """A function of a list of per-bucket latent stacks (laid out as
    ``model``'s) giving ``[users, p]``, rows by the user the key names."""
    slots = np.concatenate([np.asarray(e) for e in model.bucket_entity_ids])
    keys = np.asarray(model.entity_keys, np.int64)
    user_of_slot = np.where(slots >= 0, keys[np.maximum(slots, 0)], -1)
    live = user_of_slot >= 0

    def by_user(lats) -> np.ndarray:
        flat = np.concatenate([np.asarray(b, np.float64) for b in lats])
        out = np.zeros((n_users, flat.shape[1]))
        out[user_of_slot[live]] = flat[live]
        return out
    return by_user


def _plain_steps(steps: list, ds: Dataset) -> list:
    """The kept steps as NumPy: ``fit._plain_steps`` for a fixed step; for
    a factored one the returned ``P`` and ``b`` (``[users, p]``), and each
    sub-step's."""
    out = []
    for s in steps:
        if s["kind"] == "fixed":
            out.extend(fit._plain_steps([s], ds))
            continue
        model = s["out"][0]
        by_user = _latent_by_user(model, ds.n_users)
        subs = []
        for sub in s["subs"]:
            rec = {"sub": sub["sub"], "P": np.asarray(sub["P"], np.float64)}
            if sub["sub"] != "init":
                rec["b"] = by_user(sub["lats"])
            if sub["sub"] == "projection":
                r = sub["result"]
                rec.update(P_in=np.asarray(sub["P_in"], np.float64),
                           value=float(r.value),
                           values=np.asarray(r.values, np.float64),
                           grad_norms=np.asarray(r.grad_norms, np.float64))
            subs.append(rec)
        out.append({"kind": "factored", "subs": subs,
                    "P": np.asarray(model.projection, np.float64),
                    "b": by_user(model.bucket_latent),
                    "offsets": np.asarray(s["offsets"], np.float64),
                    "scores": np.asarray(s["scores"], np.float64)})
    return out


# ---------------------------------------------------------- the comparison


def factored_rows(split: Split, ds: Dataset, c: dict
                  ) -> factored_re.FactoredRows:
    fi, fv = factored_shard(split, ds.n_movies)
    return factored_re.FactoredRows(
        idx=fi, val=fv, ent=np.where(split.users >= 0, split.users, -1),
        y=split.y, dim=ds.n_movies, n_entities=ds.n_users,
        l2=c["reg_weight"])


class FixedOnThreads(reference.SparseLogistic):
    """``reference.SparseLogistic``'s problem, its sums over the rows a
    block at a time on threads (``factored_re.in_blocks``): the fixed
    steps' reference over 10 M rows within a run's time."""

    def matvec(self, w: np.ndarray) -> np.ndarray:
        out = np.empty(len(self.y))

        def one(b):
            out[b] = (self.val[b] * w[self.idx[b]]).sum(1)
        factored_re.in_blocks(one, len(self.y))
        return out

    def value_from_margins(self, z: np.ndarray, w: np.ndarray) -> float:
        data = sum(factored_re.in_blocks(lambda b: np.sum(
            self.weights[b] * (reference.log1pexp(z[b]) - self.y[b] * z[b])),
            len(self.y)))
        return float(data + 0.5 * np.sum(self.lam * w * w))

    def grad_from_margins(self, z: np.ndarray, w: np.ndarray) -> np.ndarray:
        dz = np.empty(len(self.y))

        def one(b):
            dz[b] = self.weights[b] * (reference.sigmoid(z[b]) - self.y[b])
        factored_re.in_blocks(one, len(self.y))
        return self.rmatvec(dz) + self.lam * w


def fixed_problem(config: dict, c: dict, ds: Dataset, offsets
                  ) -> FixedOnThreads:
    """A fixed coordinate's problem over the training rows (the logistic
    task: the only one ``references/factored_re.py`` states)."""
    if reference.objective(config["task"]) is not reference.SparseLogistic:
        raise ValueError(f"kind 'fit_factored' states the logistic task, "
                         f"not {config['task']}")
    tr = ds.train
    return FixedOnThreads(idx=tr.gi, val=tr.gv, y=tr.y, offsets=offsets,
                          dim=ds.global_dim, l2=c["reg_weight"],
                          intercept=ds.global_dim - 1)


# The fixed steps' numbers this cell holds: those a fault planted in its
# fixed effect moves above a sound reading on the chip (PERF.md section 2).
FIXED_HELD = ("grad0", "loss3", "grad3", "loss_mid", "grad_mid",
              "final_loss", "early_stop")


def _rel(got: np.ndarray, want: np.ndarray) -> float:
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want),
                                                  1e-300))


def _projection(c: dict, rows, sub: dict, offsets, hold) -> None:
    """One projection step against the stated projection problem at the
    same ``b``: the first gradient from the same ``P`` (against
    ``reference.lbfgs``' own start), the objective at the returned ``P``,
    and the iterations run."""
    cap = c["max_iterations"]
    problem = factored_re.ProjectionProblem(rows, sub["b"], offsets)
    ran = int(np.isfinite(sub["values"]).sum()) - 1
    hold("proj_iterations", float(cap - ran))
    want_g = reference.lbfgs(problem, 0, sub["P_in"].ravel())["grad_norms"][0]
    with np.errstate(divide="ignore", invalid="ignore"):
        hold("proj_grad0", abs(sub["grad_norms"][0] - want_g) / want_g)
    w = sub["P"].ravel()
    value = problem.value_from_margins(problem.margins(w), w)
    hold("proj_final_loss", abs(sub["value"] - value) / value)


def _fixed_numbers(c: dict, problem, s: dict, hold, paths) -> None:
    """``fit._fixed_step``'s numbers of a fixed step that this cell holds
    (``FIXED_HELD``), and ``fit_crossed``'s ``grad_mid`` of a step from
    zero: the same fixed effect as ``game-logistic-crossed-re``'s."""
    said: dict = {}
    steps: list = []
    fit._fixed_step(c, problem, s, lambda n, v: said.setdefault(n, v),
                    steps)
    for path in steps:
        sure = min(path["ran"], path["sure"])
        if path["cold"] and sure >= fit.FIRST_STEPS:
            said["grad_mid"] = np.median(path["grad_gap"][1:sure + 1])
    for name, value in said.items():
        if name in FIXED_HELD:
            hold(name, value)
    if paths is not None:
        paths.extend(steps)


def check(config: dict, ds: Dataset, steps: list, tracker: list,
          paths: list = None) -> dict:
    """The numbers compared (module doc), by short name, of the last fit's
    steps. ``offsets`` of a step is held against the reference's scores of
    the other coordinate's latest model."""
    coords = config["coordinates"]
    by_id = {c["id"]: c for c in coords}
    order = [c["id"] for c in coords] * config["sweeps"]
    if [s["kind"] for s in steps] != [by_id[c]["kind"] for c in order]:
        return {"steps_missing": float(max(1, abs(len(order) - len(steps))))}
    tr, va = ds.train, ds.validation
    c_f = _factored(config)
    rows = factored_rows(tr, ds, c_f)
    v_rows = factored_rows(va, ds, c_f)
    fixed = {c["id"]: fixed_problem(config, c, ds, np.zeros(tr.n_rows))
             for c in coords if c["kind"] == "fixed"}

    latest: dict = {}
    train_scores: dict = {}
    gaps: dict = {}

    def hold(name: str, value: float) -> None:
        value = float(value) if np.isfinite(value) else float("inf")
        gaps[name] = max(gaps.get(name, 0.0), value)

    for cid, s, t in zip(order, steps, tracker):
        c = by_id[cid]
        expected = sum((v for k, v in train_scores.items() if k != cid),
                       np.zeros(tr.n_rows))
        hold("offsets", fit._max_gap(s["offsets"], expected))
        if s["kind"] == "fixed":
            problem = fixed[cid]
            problem.offsets = s["offsets"]
            _fixed_numbers(c, problem, s, hold, paths)
            train_scores[cid] = reference.sparse_scores(tr.gi, tr.gv, s["w"])
            val_scores = reference.sparse_scores(va.gi, va.gv, s["w"])
        else:
            train_scores[cid] = _factored_step(config, c, rows, s, hold,
                                               paths, cid)
            val_scores = factored_re.scores(v_rows, s["P"], s["b"])
        hold("scores", fit._max_gap(s["scores"], train_scores[cid]))
        latest[cid] = val_scores
        scores = sum(latest.values())
        for name in config["evaluators"]:
            hold("val_" + name.lower(), reference.evaluator_gap(
                name, t["validation"][name], scores, va.y))
    return gaps


def _factored_step(config: dict, c: dict, rows, s: dict, hold, paths,
                   cid: str) -> np.ndarray:
    """A factored step's sub-steps against the reference, in order: the
    init, every latent step's residual at the projection it was given, and
    every projection step. The training rows' scores of the returned
    model."""
    if not any(u["sub"] == "latent" for u in s["subs"]):
        hold("latent_resid", float("inf"))
    for sub in s["subs"]:
        if sub["sub"] == "init":
            hold("init", _rel(sub["P"], factored_re.subspace_init(
                rows, s["offsets"], c["latent_dim"], config["init_seed"],
                factored_re.INIT_ROUNDS, np.dtype(config["dtype"]))))
        elif sub["sub"] == "projection":
            _projection(c, rows, sub, s["offsets"], hold)
        else:
            by_class = factored_re.LatentProblems.at(
                rows, sub["P"], s["offsets"]).residual_by_class(sub["b"])
            hold("latent_resid", max(by_class.values()))
            if paths is not None:
                paths.append({"coordinate": cid,
                              "latent_resid_by_class": by_class})
    return factored_re.scores(rows, s["P"], s["b"])


def fixed_check(config: dict, ds: Dataset, steps: list) -> dict:
    """The fixed steps' numbers alone (``_fixed_numbers``): what a fault
    planted in the fixed effect is read by."""
    gaps: dict = {}

    def hold(name: str, value: float) -> None:
        value = float(value) if np.isfinite(value) else float("inf")
        gaps[name] = max(gaps.get(name, 0.0), value)

    for c, s in zip(config["coordinates"] * config["sweeps"], steps):
        if s["kind"] == "fixed":
            _fixed_numbers(c, fixed_problem(config, c, ds, s["offsets"]),
                           s, hold, None)
    return gaps


# ------------------------------------------------------------------ a run


SUB_SPANS = ("factored.init", "factored.latent", "factored.projection")


def sub_steps(tree: list) -> list:
    """A fit's factored sub-steps from its kept span tree, in order:
    ``[name, iterations, passes]`` (the init has neither)."""
    out = []
    for s in sorted(tree, key=lambda s: s[_spans.START]):
        if s[_spans.NAME] in SUB_SPANS:
            a = s[_spans.ARGS]
            out.append([s[_spans.NAME].split(".")[1], a.get("iterations"),
                        a.get("passes")])
    return out


def reading(config: dict, ds: Dataset, fits: int = 1) -> dict:
    """Outside a run (the tests, ``tests/faults_factored.py``): build the
    estimator, fit ``fits`` times and compare the last fit."""
    estimator, train, validation, opt = build(config, ds)
    probe = Probe()
    probe.install()
    try:
        seconds = []
        for _ in range(fits):
            result, s = one_fit(estimator, train, validation, opt, probe)
            seconds.append(s)
        steps = _plain_steps(probe.steps, ds)
    finally:
        probe.remove()
    tracker = fit._tracker(result)
    paths: list = []
    return {"numbers": check(config, ds, steps, tracker, paths),
            "paths": paths, "fit_seconds": seconds, "tracker": tracker}


def run(cell: dict, config: dict, mix: dict, limits: dict, seed: int,
        seconds: float, trace_dir, t_start: float, say) -> dict:
    """Set-up, window and comparison of one run, as ``fit_crossed.run``
    makes them, and each fit's factored sub-steps (``sub_steps``) beside
    its tracker."""
    require_program()
    import jax

    from photon_tpu.runtime import compile_store

    if mix.get("start") != "zero" or mix.get("checkpointing"):
        raise ValueError(f"job mix not understood by kind 'fit_factored': "
                         f"{mix}")
    compile_store.install_accounting()
    cache_dir = compile_store.enable_compilation_cache(min_compile_secs=0.0)
    marks = {"imports_s": time.perf_counter() - t_start}

    t = time.perf_counter()
    ds = generate(config["data"], seed)
    marks["datagen_s"] = time.perf_counter() - t
    t = time.perf_counter()
    estimator, train, validation, opt_configs = build(config, ds)
    marks["bundle_s"] = time.perf_counter() - t

    probe = Probe()
    probe.install()
    try:
        result, warm_s = one_fit(estimator, train, validation, opt_configs,
                                 probe)
        marks["warmup_fit_s"] = warm_s
        warmup_tree = fit_crossed._warmup_tree()
        setup_counters = fit._counters()
        setup_s = time.perf_counter() - t_start
        say({"setup": marks, "setup_s": setup_s, "cache_dir": cache_dir,
             "counters": setup_counters, "shapes": shapes(config, ds),
             "warmup_tracker": fit._tracker(result),
             "warmup_sub_steps": sub_steps(warmup_tree or []),
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
                    result, s = one_fit(estimator, train, validation,
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
    kept = _spans.trees({"trackers": trackers}) or []
    del estimator, train, validation, result, probe
    gc.collect()

    t = time.perf_counter()
    correct, compared = fit.judge(check(config, ds, steps, trackers[-1]),
                                  limits)
    check_s = time.perf_counter() - t
    say({"window_s": window_s, "fits": len(trackers),
         "fit_seconds": fit_seconds, "check_s": check_s,
         "trackers": trackers,
         "sub_steps": [sub_steps(tree) for tree in kept]})
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
