"""Factored random effects (SURVEY.md §2.2 Projectors / L5
FactoredRandomEffectCoordinate): alternating latent/projection training,
estimator integration, DSL parsing, and save/score round trip."""
import numpy as np
import pytest

import jax.numpy as jnp

from photon_tpu.data.batch import SparseFeatures
from photon_tpu.data.random_effect import build_random_effect_dataset
from photon_tpu.estimators.config import (
    FactoredRandomEffectDataConfig,
    FixedEffectDataConfig,
    GLMOptimizationConfiguration,
    RandomEffectDataConfig,
)
from photon_tpu.functions.problem import GLMOptimizationProblem
from photon_tpu.game.factored_random_effect import (
    train_factored_random_effects,
)
from photon_tpu.optim import (
    OptimizerConfig,
    OptimizerType,
    RegularizationContext,
    RegularizationType,
)
from photon_tpu.types import TaskType


def _low_rank_game_data(seed, n_users=150, rows_per_user=6, d_user=24, rank=3):
    """Per-user blocks whose true weights live in a shared rank-3 space —
    the regime factored REs exist for (scarce per-entity data, shared
    low-dimensional structure)."""
    rng = np.random.default_rng(seed)
    truth = np.random.default_rng(99)
    P_true = truth.normal(size=(d_user, rank)) / np.sqrt(rank)
    B_true = truth.normal(size=(n_users, rank)) * 1.5
    n = n_users * rows_per_user
    users = rng.permutation(np.repeat(np.arange(n_users), rows_per_user))
    k = 6
    # One SHARED d_user-dim feature space; the per-USER response surface
    # w_u = P_true·b_u is what the factorization shares across entities
    # (reference regime: every entity sees the same feature shard).
    idx = rng.integers(0, d_user, size=(n, k)).astype(np.int32)
    val = (rng.normal(size=(n, k)) / np.sqrt(k)).astype(np.float32)
    w_user = P_true @ B_true.T                      # [d_user, n_users]
    z = (val * w_user[idx, users[:, None]]).sum(axis=1)
    y = (rng.random(n) < 1 / (1 + np.exp(-z))).astype(np.float32)
    keys = np.array([f"u{u:03d}" for u in users], object)
    return idx, val, y, z, keys, users, d_user


def _problem(max_iter=40, lam=1.0):
    return GLMOptimizationProblem(
        task=TaskType.LOGISTIC_REGRESSION,
        optimizer_type=OptimizerType.LBFGS,
        optimizer_config=OptimizerConfig(max_iterations=max_iter),
        regularization=RegularizationContext(RegularizationType.L2),
        reg_weight=lam,
    )


def _auc(scores, y):
    order = np.argsort(scores)
    ranks = np.empty_like(order, float)
    ranks[order] = np.arange(len(scores))
    pos = y > 0
    n1, n0 = pos.sum(), (~pos).sum()
    return (ranks[pos].sum() - n1 * (n1 - 1) / 2) / (n1 * n0)


def test_factored_training_learns_low_rank_structure():
    idx, val, y, z, keys, users, dim = _low_rank_game_data(1)
    ds = build_random_effect_dataset("userId", keys, idx, val, y, dim)
    model, results = train_factored_random_effects(
        _problem(), ds, jnp.zeros(len(y)), latent_dim=3, n_alternations=2,
    )
    assert model.latent_dim == 3
    assert model.projection.shape == (dim, 3)
    # every solve of the step: three latent steps' buckets (two
    # alternations and the refresh) and the two projection steps
    assert len(results) == 3 * len(ds.buckets) + 2
    scores = np.asarray(model.score_dataset(ds))
    auc = _auc(scores, y)
    assert auc > 0.75, auc

    # Factored (rank-matched) beats the plain per-user fit on HELD-OUT rows
    # in this scarce-data regime: 6 rows/user cannot pin down 24 free
    # weights, but 3 latent ones they can (the component's raison d'être).
    from photon_tpu.game.random_effect import train_random_effects

    plain, _ = train_random_effects(_problem(), ds, jnp.zeros(len(y)))
    vi, vv, vy, _, vkeys, _, _ = _low_rank_game_data(71)   # same truth
    vds = build_random_effect_dataset("userId", vkeys, vi, vv, vy, dim)
    auc_f = _auc(np.asarray(model.score_new_dataset(vds)), vy)
    auc_p = _auc(np.asarray(plain.score_new_dataset(vds)), vy)
    assert auc_f > auc_p + 0.02, (auc_f, auc_p)
    # effective coefficients expose the factorization
    gi, gv = model.coefficients_for(f"u{users[0]:03d}")
    assert len(gi) > 0 and np.isfinite(gv).all()


def test_factored_warm_start_and_alternation_improves():
    idx, val, y, z, keys, users, dim = _low_rank_game_data(2)
    ds = build_random_effect_dataset("userId", keys, idx, val, y, dim)
    m1, _ = train_factored_random_effects(
        _problem(max_iter=25), ds, jnp.zeros(len(y)), latent_dim=3,
        n_alternations=1,
    )
    m2, _ = train_factored_random_effects(
        _problem(max_iter=25), ds, jnp.zeros(len(y)), latent_dim=3,
        n_alternations=1, init=m1,
    )
    # warm start reuses structure and keeps improving (or at least not
    # regressing) the training objective proxy
    a1 = _auc(np.asarray(m1.score_dataset(ds)), y)
    a2 = _auc(np.asarray(m2.score_dataset(ds)), y)
    assert a2 >= a1 - 0.02


def test_estimator_end_to_end_with_factored_coordinate(tmp_path):
    from photon_tpu.estimators.game_estimator import GameEstimator
    from photon_tpu.io.data_reader import GameDataBundle
    from photon_tpu.index.index_map import DefaultIndexMap, feature_key
    from photon_tpu.io.model_io import load_game_model, save_game_model

    idx, val, y, z, keys, users, dim = _low_rank_game_data(3)
    n = len(y)
    bundle = GameDataBundle(
        features={"global": SparseFeatures(jnp.asarray(idx), jnp.asarray(val), dim)},
        labels=y.astype(np.float64),
        offsets=np.zeros(n),
        weights=np.ones(n),
        uids=np.arange(n).astype(object),
        id_tags={"userId": keys},
    )
    est = GameEstimator(
        task=TaskType.LOGISTIC_REGRESSION,
        coordinate_data_configs={
            "perUserLatent": FactoredRandomEffectDataConfig(
                re_type="userId", feature_shard="global",
                latent_dim=3, n_alternations=2,
            ),
        },
        n_sweeps=1,
        evaluator_specs=("AUC",),
    )
    cfg = {
        "perUserLatent": GLMOptimizationConfiguration(
            regularization=RegularizationContext(RegularizationType.L2),
            reg_weight=1.0, max_iterations=30),
    }
    r = est.fit(bundle, bundle, [cfg])
    assert r[0].evaluation.values["AUC"] > 0.7

    # save: effective coefficients in the standard RE layout + projection
    imap = DefaultIndexMap([feature_key(f"f{i}", "") for i in range(dim)])
    out = tmp_path / "m"
    save_game_model(
        str(out), r[0].model, {"global": imap},
        {"perUserLatent": "global"},
    )
    assert (out / "random-effect" / "perUserLatent" / "projection.npy").exists()
    import json

    meta = json.load(open(out / "game-metadata.json"))
    assert meta["coordinates"]["perUserLatent"]["factored_latent_dim"] == 3

    loaded, _ = load_game_model(str(out), {"global": imap})
    lscore = loaded["perUserLatent"]
    # loaded (effective) model scores equal the trained factored model
    ds = est._prepare(bundle)["train"]["perUserLatent"]
    np.testing.assert_allclose(
        np.asarray(lscore.score_new_dataset(ds)),
        np.asarray(r[0].model["perUserLatent"].score_dataset(ds)),
        rtol=0, atol=1e-4,
    )


def test_dsl_parses_factored():
    from photon_tpu.cli.params import parse_coordinate_spec

    spec = parse_coordinate_spec(
        "perUser:type=factored,re_type=userId,latent=4,alternations=3,"
        "reg=L2,reg_weights=1"
    )
    assert isinstance(spec.data, FactoredRandomEffectDataConfig)
    assert spec.data.latent_dim == 4
    assert spec.data.n_alternations == 3
    with pytest.raises(ValueError, match="factored"):
        parse_coordinate_spec("x:type=random,re_type=u,latent=4")
    with pytest.raises(ValueError, match="random-effect only"):
        parse_coordinate_spec("x:type=fixed,latent=4")


def test_factored_warm_start_from_loaded_model(tmp_path):
    """Save → load → warm start: the loaded EFFECTIVE model re-factors
    spectrally and the refit does not regress."""
    from photon_tpu.estimators.game_estimator import GameEstimator
    from photon_tpu.io.data_reader import GameDataBundle
    from photon_tpu.index.index_map import DefaultIndexMap, feature_key
    from photon_tpu.io.model_io import load_game_model, save_game_model

    idx, val, y, z, keys, users, dim = _low_rank_game_data(4)
    n = len(y)
    bundle = GameDataBundle(
        features={"global": SparseFeatures(jnp.asarray(idx), jnp.asarray(val), dim)},
        labels=y.astype(np.float64), offsets=np.zeros(n), weights=np.ones(n),
        uids=np.arange(n).astype(object), id_tags={"userId": keys},
    )
    est = GameEstimator(
        task=TaskType.LOGISTIC_REGRESSION,
        coordinate_data_configs={
            "u": FactoredRandomEffectDataConfig(
                re_type="userId", feature_shard="global", latent_dim=3,
                n_alternations=1),
        },
        n_sweeps=1, evaluator_specs=("AUC",),
    )
    cfg = {"u": GLMOptimizationConfiguration(
        regularization=RegularizationContext(RegularizationType.L2),
        reg_weight=1.0, max_iterations=25)}
    r1 = est.fit(bundle, bundle, [cfg])
    imap = DefaultIndexMap([feature_key(f"f{i}", "") for i in range(dim)])
    out = tmp_path / "m"
    save_game_model(str(out), r1[0].model, {"global": imap}, {"u": "global"})
    loaded, _ = load_game_model(str(out), {"global": imap})
    r2 = est.fit(bundle, bundle, [cfg], initial_model=loaded)
    assert (
        r2[0].evaluation.values["AUC"]
        >= r1[0].evaluation.values["AUC"] - 0.03
    )


def test_factored_rejects_unsupported_options():
    from photon_tpu.estimators.game_estimator import GameEstimator
    from photon_tpu.io.data_reader import GameDataBundle

    idx, val, y, z, keys, users, dim = _low_rank_game_data(5, n_users=20)
    n = len(y)
    bundle = GameDataBundle(
        features={"global": SparseFeatures(jnp.asarray(idx), jnp.asarray(val), dim)},
        labels=y.astype(np.float64), offsets=np.zeros(n), weights=np.ones(n),
        uids=np.arange(n).astype(object), id_tags={"userId": keys},
    )
    est = GameEstimator(
        task=TaskType.LOGISTIC_REGRESSION,
        coordinate_data_configs={
            "u": FactoredRandomEffectDataConfig(
                re_type="userId", feature_shard="global", latent_dim=2),
        },
        n_sweeps=1,
    )
    cfg = {"u": GLMOptimizationConfiguration(
        regularization=RegularizationContext(RegularizationType.L2),
        reg_weight=1.0, max_iterations=5, down_sampling_rate=0.5)}
    with pytest.raises(ValueError, match="down-sampling"):
        est.fit(bundle, None, [cfg])


# ------------------------------------------- the latent space vs the reference
#
# The factored coordinate trains and scores in its latent space over the
# rows laid flat (game/factored_random_effect.py). These hold each piece to
# the plain NumPy float64 reference the benchmark states
# (benchmarks/references/factored_re.py), on users whose local subspaces
# (the movies they rated) are up to 60 columns wide: past the 32 at which a
# bucket's per-entity scorer changes path. Tolerances: the program runs in
# float32, whose rounding over sums of a few hundred terms reads about
# 1e-6 of a result; each limit below is that with room, and far under the
# 4e-3 that bfloat16 projected rows read (``test_bfloat16_rows_fail_...``).

F32_ROOM = 2e-5       # a flat product, a score, an objective or a gradient
INIT_ROOM = 1e-4      # four rounds of subspace iteration, each re-orthogonal
LATENT_ROOM = 1e-3    # latent residual after 8 Newton steps: float32 optimum


def _factored_data(seed, n_users=40, n_movies=96, rank=4, entries=1):
    """Each user rates 20 to 60 distinct movies; labels from a planted
    rank-``rank`` term. The movie shard: one entry a row, value 1 (with
    ``entries`` 2, a second movie beside it, value 0.5)."""
    rng = np.random.default_rng(seed)
    counts = rng.integers(20, 61, n_users)
    users = np.repeat(np.arange(n_users), counts)
    movies = np.concatenate([rng.choice(n_movies, c, replace=False)
                             for c in counts])
    uf = rng.normal(size=(n_users, rank))
    vf = rng.normal(size=(n_movies, rank)) * (1.5 / np.sqrt(rank))
    z = np.einsum("nr,nr->n", uf[users], vf[movies])
    y = (rng.random(len(z)) < 1 / (1 + np.exp(-z))).astype(np.float32)
    order = rng.permutation(len(y))
    users, movies, y = users[order], movies[order], y[order]
    idx = np.stack([movies, (7 * movies + 1) % n_movies][:entries],
                   axis=1).astype(np.int32)
    val = np.array([1.0, 0.5][:entries], np.float32)[None].repeat(len(y), 0)
    return users, idx, val, y, n_movies


def _factored_setup(seed=11, latent=8, lam=1.0, entries=1):
    from benchmarks.references import factored_re

    users, idx, val, y, dim = _factored_data(seed, entries=entries)
    ds = build_random_effect_dataset("userId", users, idx, val, y, dim)
    ref_rows = factored_re.FactoredRows(
        idx=idx, val=val, ent=users, y=y, dim=dim,
        n_entities=int(users.max()) + 1, l2=lam)
    return ds, ref_rows, users


def _by_user(lats, ds):
    """Per-bucket latent stacks as ``[users, p]`` rows by user key."""
    flat = np.concatenate([np.asarray(b, np.float64) for b in lats])
    slots = np.concatenate([np.asarray(b.entity_ids) for b in ds.buckets])
    keys = np.asarray(ds.entity_keys)
    out = np.zeros((len(keys), flat.shape[1]))
    out[keys[slots]] = flat
    return out


def _rel(got, want):
    return float(np.linalg.norm(np.asarray(got, np.float64) - want)
                 / np.linalg.norm(want))


def test_flat_products_match_the_reference_on_wide_buckets():
    from photon_tpu.game import factored_random_effect as f

    ds, ref, users = _factored_setup()
    assert max(b.local_dim for b in ds.buckets) > 32   # the wide path
    rows = f.flat_rows(ds)
    rng = np.random.default_rng(0)
    P = rng.normal(size=(ref.dim, 8)).astype(np.float32)
    beta = rng.normal(size=(rows.n_slots, 8)).astype(np.float32)
    r = rng.normal(size=rows.idx.shape[0]).astype(np.float32)
    b_user = _by_user(np.split(beta, np.cumsum(
        [b.n_entities for b in ds.buckets])[:-1]), ds)

    xp = ref.times(P.astype(np.float64))
    for b in ds.buckets:                       # XP laid into the slots
        got = np.asarray(f.project_slots(jnp.asarray(P), rows.idx, rows.val,
                                         b.row_ids))
        live = np.asarray(b.row_ids) < len(users)
        want = xp[np.asarray(b.row_ids)[live]]
        assert _rel(got[live], want) < F32_ROOM
        assert not got[~live].any()
    margins = f.latent_scores(jnp.asarray(P), jnp.asarray(beta), rows.idx,
                              rows.val, rows.slot, n_rows=rows.n_rows)
    assert _rel(margins, np.sum(xp * ref.of_entity(b_user), 1)) < F32_ROOM
    rr = np.asarray(r[: len(users)], np.float64)
    got = f._transpose_dot(jnp.asarray(r), jnp.asarray(beta), rows.idx,
                           rows.val, rows.slot, ref.dim)
    assert _rel(got, ref.transpose_times(rr[:, None] * ref.of_entity(b_user))
                ) < F32_ROOM


def test_the_init_is_the_reference_subspace():
    from benchmarks.references import factored_re
    from photon_tpu.game import factored_random_effect as f

    ds, ref, users = _factored_setup()
    offsets = np.random.default_rng(1).normal(size=len(users)) * 0.5
    got = f.subspace_init(_problem(), f.flat_rows(ds),
                          jnp.asarray(offsets, jnp.float32), ref.dim, 8,
                          seed=0)
    want = factored_re.subspace_init(ref, offsets.astype(np.float32), 8,
                                     seed=0, rounds=f.INIT_ROUNDS)
    assert _rel(got, want) < INIT_ROOM
    np.testing.assert_allclose(np.asarray(got).T @ np.asarray(got),
                               np.eye(8), atol=1e-5)


def test_a_latent_step_solves_the_stated_latent_problems():
    from benchmarks.references import factored_re
    from photon_tpu.game import factored_random_effect as f

    ds, ref, users = _factored_setup()
    rng = np.random.default_rng(2)
    P = (rng.normal(size=(ref.dim, 8)) * 0.3).astype(np.float32)
    offsets = (rng.normal(size=len(users)) * 0.5).astype(np.float32)
    lats0 = [jnp.zeros((b.n_entities, 8)) for b in ds.buckets]
    lats, results = f.latent_step(_problem(max_iter=8), ds, f.flat_rows(ds),
                                  jnp.asarray(P), jnp.asarray(offsets), lats0)
    assert len(results) == len(ds.buckets)
    problems = factored_re.LatentProblems.at(ref, P, offsets)
    b = _by_user(lats, ds)
    assert max(problems.residual_by_class(b).values()) < LATENT_ROOM
    # the objective at the program's answer is the reference's optimum's
    best = problems.solve(np.zeros_like(b))
    assert abs(problems.value(b) - problems.value(best)) \
        < F32_ROOM * problems.value(best)


@pytest.mark.parametrize("entries,latent,formulation", [
    (1, 8, "sorted"),      # one movie a row: the rows sorted by movie
    (2, 8, "flat"),        # two entries a row
    (1, 43, "flat"),       # three parts of 43 latent columns pass 128 rows
])
def test_a_projection_step_runs_the_stated_lbfgs(entries, latent,
                                                 formulation):
    from benchmarks import reference
    from benchmarks.references import factored_re
    from photon_tpu.game import factored_random_effect as f
    from photon_tpu.obs.trace import recent_trees, trace_span

    ds, ref, users = _factored_setup(entries=entries)
    rng = np.random.default_rng(3)
    P0 = (rng.normal(size=(ref.dim, latent)) * 0.3).astype(np.float32)
    lats = [jnp.asarray(rng.normal(size=(b.n_entities, latent)), jnp.float32)
            for b in ds.buckets]
    offsets = (rng.normal(size=len(users)) * 0.5).astype(np.float32)
    with trace_span("test.projection_step").keep_tree():
        P, result = f.projection_step(_problem(max_iter=4), f.flat_rows(ds),
                                      jnp.asarray(P0), jnp.asarray(offsets),
                                      lats)
    span = next(s for s in recent_trees("test.projection_step", 1)[0]
                if s[0] == "factored.projection")[5]
    assert span["formulation"] == formulation
    assert ("window_passes" in span) == (formulation == "sorted")
    problem = factored_re.ProjectionProblem(ref, _by_user(lats, ds), offsets)
    want = reference.lbfgs(problem, 4, P0.ravel().astype(np.float64))
    got_v = np.asarray(result.values, np.float64)[:5]
    got_g = np.asarray(result.grad_norms, np.float64)[:5]
    # objective and gradient at the start, then the stated path
    assert abs(got_v[0] - want["values"][0]) < F32_ROOM * want["values"][0]
    assert abs(got_g[0] - want["grad_norms"][0]) \
        < F32_ROOM * want["grad_norms"][0]
    assert want["sure"] == 4
    np.testing.assert_allclose(got_v, want["values"], rtol=F32_ROOM)
    w = np.asarray(P, np.float64).ravel()
    assert abs(float(result.value) - problem.value_from_margins(
        problem.margins(w), w)) < F32_ROOM * float(result.value)


def test_the_sorted_plan_is_built_once_and_kept_while_the_dataset_lives():
    """A dataset's rows are sorted by movie at its first projection step
    and kept with its flat rows: later steps build nothing, another
    dataset builds its own, and the plan goes with its dataset."""
    import gc

    from photon_tpu.game import factored_random_effect as f
    from photon_tpu.obs.metrics import REGISTRY

    builds = REGISTRY.counter("factored_projection_plan_builds_total")
    ds, ref, users = _factored_setup()
    rng = np.random.default_rng(5)
    P0 = jnp.asarray(rng.normal(size=(ref.dim, 8)) * 0.3, jnp.float32)
    lats = [jnp.zeros((b.n_entities, 8)) for b in ds.buckets]
    offsets = jnp.zeros(len(users))
    before = builds.value()
    for _ in range(2):
        f.projection_step(_problem(max_iter=1), f.flat_rows(ds), P0,
                          offsets, lats)
    assert builds.value() == before + 1
    rows = f.flat_rows(ds)
    assert f.sorted_rows(rows, P0) is f._kept(rows)["sorted"]
    other, _, _ = _factored_setup(seed=12)
    f.sorted_rows(f.flat_rows(other), P0)
    assert builds.value() == before + 2
    key = id(rows)
    del ds, rows
    gc.collect()
    assert key not in f._KEPT
    assert builds.value() == before + 2


def test_bfloat16_sorted_products_fail_the_tolerances():
    """The limits above are tight enough to see the sorted products'
    operands rounded to bfloat16: the projection step's first gradient."""
    from benchmarks.references import factored_re
    from photon_tpu.game import factored_random_effect as f
    from photon_tpu.ops import item_sorted
    from photon_tpu.ops.losses import loss_for_task

    ds, ref, users = _factored_setup()
    rows = f.flat_rows(ds)
    rng = np.random.default_rng(6)
    P0 = (rng.normal(size=(ref.dim, 8)) * 0.3).astype(np.float32)
    lats = [jnp.asarray(rng.normal(size=(b.n_entities, 8)), jnp.float32)
            for b in ds.buckets]
    offsets = (rng.normal(size=len(users)) * 0.5).astype(np.float32)
    problem = factored_re.ProjectionProblem(ref, _by_user(lats, ds), offsets)
    want = ref.entity_transpose_times(ref.d1(problem.margins(
        P0.ravel().astype(np.float64))), problem.b).ravel()
    plan = f.sorted_rows(rows, jnp.asarray(P0))
    loss = loss_for_task(TaskType.LOGISTIC_REGRESSION)

    def first_gradient(rounded):
        def low(x):
            return x.astype(jnp.bfloat16).astype(x.dtype) if rounded else x

        times, transpose, o = f._sorted_products(
            plan, low(jnp.concatenate(lats)), jnp.asarray(offsets),
            P0.shape)
        z = times(low(jnp.asarray(P0))) + o
        return transpose(plan.weights * loss.d1(z, plan.labels))

    assert _rel(first_gradient(False).ravel(), want) < F32_ROOM
    assert _rel(first_gradient(True).ravel(), want) > 1e-3


def test_latent_space_scores_equal_the_effective_models():
    from benchmarks.references import factored_re

    ds, ref, users = _factored_setup()
    model, _ = train_factored_random_effects(
        _problem(max_iter=6), ds, jnp.zeros(len(users)), latent_dim=8,
        n_alternations=1)
    latent = np.asarray(model.score_dataset(ds))
    # the materialized per-entity coefficients score the rows alike, by
    # the per-entity scorer's gather at these widths
    effective = np.asarray(model.effective.score_dataset(ds))
    assert _rel(latent, effective) < F32_ROOM
    want = factored_re.scores(ref, np.asarray(model.projection, np.float64),
                              _by_user(model.bucket_latent, ds))
    assert _rel(latent, want) < F32_ROOM
    # rows of another dataset: a user the model never saw scores 0
    vu, vi, vv, vy, _ = _factored_data(12, n_users=50)
    vds = build_random_effect_dataset("userId", vu, vi, vv, vy, ref.dim)
    got = np.asarray(model.score_new_dataset(vds))
    assert not got[vu >= 40].any() and got[vu < 40].any()
    vref = factored_re.FactoredRows(idx=vi, val=vv, ent=np.where(
        vu < 40, vu, -1), y=vy, dim=ref.dim, n_entities=40, l2=1.0)
    assert _rel(got, factored_re.scores(
        vref, np.asarray(model.projection, np.float64),
        _by_user(model.bucket_latent, ds))) < F32_ROOM


def test_a_whole_factored_step_matches_the_reference_alternation():
    from benchmarks.references import factored_re
    from photon_tpu.game import factored_random_effect as f

    ds, ref, users = _factored_setup()
    offsets = (np.random.default_rng(4).normal(size=len(users)) * 0.3
               ).astype(np.float32)
    model, results = train_factored_random_effects(
        _problem(max_iter=4), ds, jnp.asarray(offsets), latent_dim=8,
        n_alternations=2)
    want = factored_re.alternation(ref, offsets, 8, seed=0,
                                   rounds=f.INIT_ROUNDS, alternations=2,
                                   iterations=4)
    got = np.asarray(model.score_dataset(ds))
    # float32 and float64 paths part by rounding over 2 alternations of a
    # nonconvex problem: the scores read 3.3e-5 apart, and 7.7e-4 with the
    # latent steps' projected rows rounded to bfloat16
    assert _rel(got, factored_re.scores(ref, want["P"], want["b"])) < 2e-4


def test_bfloat16_rows_fail_the_tolerances():
    """The limits above are tight enough to see projected rows rounded
    to bfloat16."""
    from photon_tpu.game import factored_random_effect as f

    ds, ref, users = _factored_setup()
    rows = f.flat_rows(ds)
    rng = np.random.default_rng(0)
    P = rng.normal(size=(ref.dim, 8)).astype(np.float32)
    beta = rng.normal(size=(rows.n_slots, 8)).astype(np.float32)
    b_user = _by_user(np.split(beta, np.cumsum(
        [b.n_entities for b in ds.buckets])[:-1]), ds)
    rounded = jnp.asarray(P).astype(jnp.bfloat16).astype(jnp.float32)
    margins = f.latent_scores(rounded, jnp.asarray(beta), rows.idx,
                              rows.val, rows.slot, n_rows=rows.n_rows)
    want = np.sum(ref.times(P.astype(np.float64)) * ref.of_entity(b_user), 1)
    assert _rel(margins, want) > 1e-3


def test_the_flat_rows_are_kept_while_the_dataset_lives():
    """The factored path keeps a dataset's flat rows and latent plan in a
    holder of its own, built once and let go with the dataset; the latent
    buckets' column maps are made for the step and kept nowhere."""
    import gc

    from photon_tpu.game import factored_random_effect as f

    ds, _, _ = _factored_setup()
    rows = f.flat_rows(ds)
    assert f.flat_rows(ds) is rows
    P = jnp.zeros((ds.global_dim, 8), jnp.float32)
    buckets = f._latent_buckets(ds, rows, P)
    assert [tuple(b.idx.shape) for b in buckets] == [
        tuple(b.idx.shape[:2]) + (8,) for b in ds.buckets]
    assert np.array_equal(np.asarray(buckets[0].idx[0, 0]), np.arange(8))
    assert set(f._kept(ds)) == {"rows"}
    assert "rows" not in ds.kept_projection
    key = id(ds)
    del ds, buckets
    gc.collect()
    assert key not in f._KEPT
