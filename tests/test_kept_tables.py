"""The fast-path tables live with the prepared bundle
(``GameEstimator._with_tables``): built by the first fit that needs them,
served to later fits by identity, dropped with the bundle. The tables are a
TPU default; here the CPU is named an accelerator so that they are built."""
import dataclasses
import gc
import weakref

import numpy as np
import pytest

from photon_tpu.data import batch as batch_mod
from photon_tpu.data.batch import SparseFeatures, ell_from_rows
from photon_tpu.data.sampling import DownSampler
from photon_tpu.estimators import (
    FixedEffectDataConfig,
    GLMOptimizationConfiguration,
    GameEstimator,
    RandomEffectDataConfig,
)
from photon_tpu.estimators import game_estimator as estimator_mod
from photon_tpu.io.data_reader import GameDataBundle
from photon_tpu.obs.trace import recent_trees
from photon_tpu.ops import fast_sparse
from photon_tpu.optim import RegularizationContext, RegularizationType
from photon_tpu.types import TaskType

L2 = RegularizationContext(RegularizationType.L2)
NAME, ARGS = 0, 5
TABLES, BUILD = "data.accel_tables", "estimator.build_coordinates"


def _bundle(seed, n_users=8, rows_per_user=8, d_global=5, d_user=3):
    r = np.random.default_rng(seed)
    n = n_users * rows_per_user
    users = r.permutation(np.repeat(np.arange(n_users), rows_per_user))
    g_rows = [(np.arange(d_global), r.normal(size=d_global)) for _ in range(n)]
    u_rows = [(u * d_user + np.arange(d_user), r.normal(size=d_user))
              for u in users]
    return GameDataBundle(
        features={"global": ell_from_rows(g_rows, d_global),
                  "user": ell_from_rows(u_rows, n_users * d_user)},
        labels=(r.random(n) < 0.5).astype(np.float64),
        offsets=np.zeros(n), weights=np.ones(n),
        uids=np.asarray([str(i) for i in range(n)], object),
        id_tags={"userId": np.asarray([f"u{u}" for u in users], object)})


def _estimator(random_effect=False, **kwargs):
    configs = {"fixed": FixedEffectDataConfig(feature_shard="global")}
    if random_effect:
        configs["perUser"] = RandomEffectDataConfig(re_type="userId",
                                                    feature_shard="user")
    return GameEstimator(task=TaskType.LOGISTIC_REGRESSION,
                         coordinate_data_configs=configs, **kwargs)


def _config(estimator, **kwargs):
    return {cid: GLMOptimizationConfiguration(
        max_iterations=5, regularization=L2, reg_weight=1.0, **kwargs)
        for cid in estimator.coordinate_data_configs}


class _Builds:
    """``build_fast_aux`` counted, and the CPU named an accelerator."""

    def __init__(self, mp):
        self.calls = 0
        original = fast_sparse.build_fast_aux

        def counted(*args, **kwargs):
            self.calls += 1
            return original(*args, **kwargs)

        mp.setattr(fast_sparse, "build_fast_aux", counted)
        mp.setattr(batch_mod, "REAL_ACCELERATOR_BACKENDS", ("cpu",))


@pytest.fixture
def builds(monkeypatch):
    return _Builds(monkeypatch)


def _fit(estimator, bundle, configs=None):
    """(results, the fit's kept tree)."""
    results = estimator.fit(bundle, None,
                            configs or [_config(estimator)])
    return results, recent_trees("estimator.fit", 1)[0]


def _table_counts(tree):
    """(tables_reused, tables_built) of each ``estimator.build_coordinates``
    of the tree, in order."""
    return [(s[ARGS]["tables_reused"], s[ARGS]["tables_built"])
            for s in tree if s[NAME] == BUILD]


def _kept(estimator):
    return estimator._prep_cache[1]["tables"]


def _coefficients(results):
    models = results[0].model.models
    out = [np.asarray(models["fixed"].model.coefficients.means)]
    out.extend(np.asarray(c) for c in models["perUser"].bucket_coefs)
    return out


@pytest.fixture(scope="module")
def three_fits():
    """Three fits of one estimator (fixed + per-user) on one bundle."""
    with pytest.MonkeyPatch.context() as mp:
        builds = _Builds(mp)
        estimator = _estimator(random_effect=True, n_sweeps=2)
        bundle = _bundle(1)
        fits = [_fit(estimator, bundle) for _ in range(3)]
        return {"calls": builds.calls,
                "trees": [tree for _, tree in fits],
                "coefficients": [_coefficients(r) for r, _ in fits],
                "kept": dict(_kept(estimator)),
                "prepared": estimator._prep_cache[1]["batches"]}


def test_three_fits_on_one_bundle_build_once(three_fits):
    assert three_fits["calls"] == 1


@pytest.mark.parametrize("fit, tables_spans, counts", [
    (0, 1, [(0, 1)]), (1, 0, [(1, 0)]), (2, 0, [(1, 0)])])
def test_only_the_first_fit_has_a_table_span(three_fits, fit, tables_spans,
                                             counts):
    tree = three_fits["trees"][fit]
    assert [s[NAME] for s in tree].count(TABLES) == tables_spans
    assert _table_counts(tree) == counts


@pytest.mark.parametrize("later", [1, 2])
def test_a_fit_on_kept_tables_equals_the_first_bit_for_bit(three_fits, later):
    first = three_fits["coefficients"][0]
    other = three_fits["coefficients"][later]
    assert len(first) == len(other) >= 2
    for a, b in zip(first, other):
        assert a.dtype == b.dtype and np.array_equal(a, b)


def test_only_the_fixed_effects_shard_is_kept(three_fits):
    """The per-user shard feeds a random effect: it has no tables."""
    assert set(three_fits["kept"]) == {"global"}
    kept = three_fits["kept"]["global"]
    assert kept.fast is not None
    assert kept.idx is three_fits["prepared"]["global"].features.idx


def test_a_second_bundle_builds_again_and_frees_the_first(builds):
    estimator = _estimator()
    first, second = _bundle(1), _bundle(2)
    _fit(estimator, first)
    old = weakref.ref(_kept(estimator)["global"].fast)
    assert old() is not None and builds.calls == 1
    _, tree = _fit(estimator, second)
    assert builds.calls == 2 and _table_counts(tree) == [(0, 1)]
    gc.collect()
    assert old() is None
    assert _kept(estimator)["global"].idx is second.features["global"].idx
    _, tree = _fit(estimator, first)        # and back: the first was dropped
    assert builds.calls == 3 and _table_counts(tree) == [(0, 1)]


def test_the_tables_go_with_the_estimator(builds):
    estimator = _estimator()
    _fit(estimator, _bundle(1))
    tables = weakref.ref(_kept(estimator)["global"].fast)
    del estimator
    gc.collect()
    assert tables() is None


def test_kept_tables_are_served_by_identity_only(builds):
    """Straight on the cache. Its entry's source is the feature object of
    ``prep["batches"][shard]``, which ``prep`` holds for as long as it holds
    the entry: an equal object, or one that came by a freed object's ``id``,
    is another object and builds its own."""
    estimator = _estimator()
    _fit(estimator, _bundle(1))
    prep = estimator._prep_cache[1]
    prepared, kept = prep["batches"]["global"], prep["tables"]["global"]

    batch, how = estimator._with_tables(prep, "global", prepared)
    assert how == "reused" and batch.features is kept
    reweighted = dataclasses.replace(prepared, weights=prepared.weights * 2)
    batch, how = estimator._with_tables(prep, "global", reweighted)
    assert how == "reused" and batch.features is kept
    assert batch.weights is reweighted.weights

    twin = dataclasses.replace(
        prepared, features=dataclasses.replace(prepared.features))
    assert twin.features is not prepared.features
    batch, how = estimator._with_tables(prep, "global", twin)
    assert how == "built" and builds.calls == 2
    assert batch.features.fast is not None
    assert batch.features.fast is not kept.fast
    assert prep["tables"] == {"global": kept}       # nothing of it is kept


def test_a_sweep_of_one_call_builds_once(builds):
    estimator = _estimator()
    configs = [_config(estimator), _config(estimator)]
    _, tree = _fit(estimator, _bundle(1), configs)
    assert builds.calls == 1
    assert _table_counts(tree) == [(0, 1), (1, 0)]


@pytest.mark.parametrize("arrangement", ["window", "planes"])
def test_a_kept_bundle_hands_its_x_w_table_to_the_second_fit(
        builds, monkeypatch, arrangement):
    """Whichever arrangement the build chose for ``X.w`` (here: was made to
    choose, by the margin it chooses by), the second fit on the bundle
    takes that table by identity: no build, no table span, the same
    coefficients bit for bit."""
    monkeypatch.setattr(fast_sparse, "PLANES_MARGIN",
                        {"window": 0.0, "planes": float("inf")}[arrangement])
    estimator = _estimator()
    bundle = _bundle(1)
    first, tree = _fit(estimator, bundle)
    kept = _kept(estimator)["global"].fast
    assert kept.formulation("matvec") == arrangement
    assert isinstance(kept.xw, fast_sparse.PlaneTable) == (
        arrangement == "planes")
    spans = [s[ARGS] for s in tree if s[NAME] == TABLES]
    assert [s["formulation_matvec"] for s in spans] == [arrangement]
    assert spans[0]["passes_per_slot_matvec"] == 1.0

    second, tree = _fit(estimator, bundle)
    assert builds.calls == 1
    assert _table_counts(tree) == [(1, 0)]
    assert not [s for s in tree if s[NAME] == TABLES]
    assert _kept(estimator)["global"].fast is kept

    def fixed(results):
        return np.asarray(
            results[0].model.models["fixed"].model.coefficients.means)

    np.testing.assert_array_equal(fixed(first), fixed(second))


def test_two_fixed_effects_on_one_shard_share_one_build(builds):
    estimator = GameEstimator(
        task=TaskType.LOGISTIC_REGRESSION,
        coordinate_data_configs={
            "a": FixedEffectDataConfig(feature_shard="global"),
            "b": FixedEffectDataConfig(feature_shard="global")})
    _, tree = _fit(estimator, _bundle(1))
    assert builds.calls == 1 and _table_counts(tree) == [(1, 1)]
    assert set(_kept(estimator)) == {"global"}


def test_a_down_sampled_fixed_effect_takes_the_prepared_shards_tables(builds):
    """Down-sampling replaces the weights and shares the prepared feature
    object, of which the tables are a function: it takes the kept ones, and
    a tuning loop keeps one set a shard however many fits it makes."""
    estimator = _estimator()
    bundle = _bundle(1)
    sampled = [_config(estimator, down_sampling_rate=0.5)] * 2
    trees = [_fit(estimator, bundle, sampled)[1] for _ in range(3)]
    assert builds.calls == 1
    assert [_table_counts(t) for t in trees] == [
        [(0, 1), (1, 0)], [(1, 0), (1, 0)], [(1, 0), (1, 0)]]
    assert set(_kept(estimator)) == {"global"}


def test_a_batch_of_new_features_builds_in_each_call_and_keeps_nothing(
        builds, monkeypatch):
    made = []

    class CopyingSampler(DownSampler):
        """Its batch is a new feature object, as one that repacked the
        kept rows (``data.sampling.compact``) would hand back."""

        def down_sample(self, key, batch):
            out = super().down_sample(key, batch)
            out = dataclasses.replace(
                out, features=dataclasses.replace(out.features))
            made.append(weakref.ref(out.features))
            return out

    monkeypatch.setattr(estimator_mod, "down_sampler_for_task",
                        lambda task, rate: CopyingSampler(rate))
    estimator = _estimator()
    bundle = _bundle(1)
    sampled = [_config(estimator, down_sampling_rate=0.5)]
    for fit in (1, 2, 3):
        _, tree = _fit(estimator, bundle, sampled)
        assert builds.calls == fit and _table_counts(tree) == [(0, 1)]
        assert [s[NAME] for s in tree].count(TABLES) == 1
        assert _kept(estimator) == {}
    gc.collect()
    assert len(made) == 3 and all(ref() is None for ref in made)


@pytest.mark.parametrize("where", ["cpu", "mesh"])
def test_a_cpu_or_mesh_fit_keeps_nothing(where, monkeypatch):
    kwargs = {}
    if where == "mesh":
        from photon_tpu.parallel.mesh import make_mesh

        builds = _Builds(monkeypatch)       # an "accelerator", but sharded
        kwargs["mesh"] = make_mesh()
    estimator = _estimator(**kwargs)
    bundle = _bundle(1)
    for _ in range(2):
        _, tree = _fit(estimator, bundle)
        assert TABLES not in [s[NAME] for s in tree]
        assert _table_counts(tree) == [(0, 0)]
        assert _kept(estimator) == {}
    if where == "mesh":
        assert builds.calls == 0


def test_features_over_the_budget_keep_nothing(builds, monkeypatch):
    monkeypatch.setattr(batch_mod, "ACCEL_TABLE_BUDGET_BYTES", 0)
    estimator = _estimator()
    _, tree = _fit(estimator, _bundle(1))
    assert builds.calls == 0 and _table_counts(tree) == [(0, 0)]
    assert _kept(estimator) == {}


def test_labeled_batch_attaches_without_a_cache(builds):
    """``LabeledBatch.with_accelerator_paths`` keeps nothing itself: two
    calls are two builds, and who comes back keeps the result."""
    batch = _bundle(1).batch("global")
    a, b = batch.with_accelerator_paths(), batch.with_accelerator_paths()
    assert builds.calls == 2
    assert isinstance(a.features, SparseFeatures)
    assert a.features.fast is not b.features.fast
