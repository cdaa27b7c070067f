"""A per-entity model projected into another dataset's subspaces through a
plan built once for the pair (``RandomEffectModel.projection_plan``) and a
gather on the device: element for element what a plain per-entity lookup
gives, and the scores the NumPy join it replaced gave, bit for bit; the plan
kept by the target dataset and reused by every model of one structure."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.data.random_effect import build_random_effect_dataset
from photon_tpu.game.random_effect import (
    _RE_PROJECTION_PLANS,
    EntityScorer,
    RandomEffectModel,
    _pad_bucket,
)
from photon_tpu.types import TaskType


def _rows(rng, keys, global_dim, k, support_of):
    """ELL rows, one a key, each of up to ``k`` columns from its key's
    support (ghost columns fill the rest)."""
    idx = np.full((len(keys), k), global_dim, np.int32)
    val = np.zeros((len(keys), k), np.float32)
    for r, key in enumerate(keys):
        support = support_of(key)
        cols = rng.choice(support, size=min(len(support),
                                            int(rng.integers(1, k + 1))),
                          replace=False)
        idx[r, :len(cols)] = cols
        val[r, :len(cols)] = rng.normal(size=len(cols))
    return idx, val


def _case(name, rng):
    """(trained dataset, target dataset) of one case. ``ragged``: users of
    2 to 60 rows (several size classes, split into buckets of at most 3
    users), 40 columns; the target's users are half the trained ones and
    some never trained, with a fifth of each one's columns never trained
    for it. ``width_one``: one column of value 1 a row, the per-movie
    intercept's shape. ``padded``: ``ragged``'s target with its entity axis
    padded by ``_pad_bucket`` as a mesh pads it."""
    if name == "width_one":
        global_dim, k = 1, 1
        support = {}

        def support_of(key):
            return np.zeros(1, np.int64)
    else:
        global_dim, k = 40, 4
        support = {}

        def support_of(key):
            if key not in support:
                support[key] = rng.choice(global_dim, size=8, replace=False)
            return support[key]
    trained_users = [f"u{i}" for i in range(14)]
    counts = rng.integers(2, 61, size=len(trained_users))
    keys = np.repeat(trained_users, counts)
    keys = keys[rng.permutation(len(keys))]
    idx, val = _rows(rng, keys, global_dim, k, support_of)
    trained = build_random_effect_dataset(
        "userId", keys.astype(object), idx, val,
        (rng.random(len(keys)) < 0.5).astype(np.float32), global_dim,
        max_bucket_entities=3)
    target_users = trained_users[::2] + ["new0", "new1", "new2"]
    if name != "width_one":
        for key in trained_users[::2]:      # columns never trained for it
            extra = np.setdiff1d(np.arange(global_dim), support[key])[:2]
            support[key] = np.concatenate([support[key], extra])
    t_keys = np.repeat(target_users, rng.integers(1, 9, len(target_users)))
    t_idx, t_val = _rows(rng, t_keys, global_dim, k, support_of)
    target = build_random_effect_dataset(
        "userId", t_keys.astype(object), t_idx, t_val,
        np.zeros(len(t_keys), np.float32), global_dim)
    if name == "padded":
        target = dataclasses.replace(target, buckets=tuple(
            _pad_bucket(b, 4, target.n_rows, global_dim)
            for b in target.buckets))
    return trained, target


def _model(trained, rng, variances=True):
    stacks = [rng.normal(size=np.shape(b.proj)).astype(np.float32)
              for b in trained.buckets]
    return RandomEffectModel(
        re_type=trained.re_type, task=TaskType.LOGISTIC_REGRESSION,
        bucket_coefs=[jnp.asarray(s) for s in stacks],
        bucket_proj=[b.proj for b in trained.buckets],
        bucket_entity_ids=[b.entity_ids for b in trained.buckets],
        entity_keys=trained.entity_keys,
        entity_to_slot=trained.entity_to_slot,
        global_dim=trained.global_dim,
        bucket_variances=(
            [jnp.asarray(np.exp(rng.normal(size=np.shape(b.proj))),
                         jnp.float32) for b in trained.buckets]
            if variances else None))


def _oracle(model, target, sources, fills):
    """Each target slot looked up by itself: its entity's key in a dict of
    the model's entities, its global column in that entity's trained
    columns; anything not found (a new entity, a new column, a ghost
    column, a padded lane) takes the fill."""
    table = {}
    for b, proj in enumerate(model.bucket_proj):
        proj = np.asarray(proj)
        for lane, dense in enumerate(np.asarray(model.bucket_entity_ids[b])):
            cols = table.setdefault(model.entity_keys[dense], {})
            for p, col in enumerate(proj[lane]):
                if col < model.global_dim:
                    cols[int(col)] = [np.asarray(s[b])[lane, p]
                                      for s in sources]
    out = [[] for _ in sources]
    seen = {"hit": 0, "new_entity": 0, "new_column": 0, "ghost": 0}
    for bucket in target.buckets:
        proj = np.asarray(bucket.proj)
        eids = np.asarray(bucket.entity_ids)
        want = [np.full(proj.shape, f, np.float32) for f in fills]
        for lane, dense in enumerate(eids):
            cols = (table.get(target.entity_keys[dense]) if dense >= 0
                    else None)
            for p, col in enumerate(proj[lane]):
                if dense < 0 or col >= model.global_dim:
                    seen["ghost"] += 1
                elif cols is None:
                    seen["new_entity"] += 1
                elif int(col) not in cols:
                    seen["new_column"] += 1
                else:
                    seen["hit"] += 1
                    for w, v in zip(want, cols[int(col)]):
                        w[lane, p] = v
        for o, w in zip(out, want):
            o.append(w)
    return out, seen


def _parent_projection(model, dataset, sources, fills):
    """The NumPy join the plan replaced, as it was: the reference the
    scores are held to bit for bit."""
    key_to_dense = {k: i for i, k in enumerate(model.entity_keys)}
    old_proj = [np.asarray(p) for p in model.bucket_proj]
    old_vals = [[np.asarray(c) for c in src] for src in sources]
    slot_bucket, slot_lane = model._slots
    old_of = np.fromiter(
        (key_to_dense.get(k, -1) for k in dataset.entity_keys),
        np.int64, len(dataset.entity_keys))
    stride = model.global_dim + 1
    out = [[] for _ in sources]
    for b in dataset.buckets:
        proj = np.asarray(b.proj)
        eids = np.asarray(b.entity_ids)
        vals = [np.full(proj.shape, fill, src[0].dtype)
                for src, fill in zip(old_vals, fills)]
        dense_old = np.where(eids >= 0, old_of[np.maximum(eids, 0)], -1)
        from_bucket = np.where(
            dense_old >= 0, slot_bucket[np.maximum(dense_old, 0)], -1)
        for bo in np.unique(from_bucket[from_bucket >= 0]):
            lanes = np.flatnonzero(from_bucket == bo)
            from_lanes = slot_lane[dense_old[lanes]]
            row = np.arange(len(lanes), dtype=np.int64)[:, None] * stride
            have = (old_proj[bo][from_lanes] + row).ravel()
            want = proj[lanes] + row
            pos = np.minimum(np.searchsorted(have, want.ravel()),
                             len(have) - 1).reshape(want.shape)
            hit = (have[pos] == want) & (proj[lanes] < model.global_dim)
            lane_at, col = np.nonzero(hit)
            for s, src in enumerate(old_vals):
                vals[s][lanes[lane_at], col] = (
                    src[bo][from_lanes].ravel()[pos[hit]])
        for s, v in enumerate(vals):
            out[s].append(jnp.asarray(v))
    return out


@pytest.mark.parametrize("case", ["ragged", "width_one", "padded"])
@pytest.mark.parametrize("method", ["project_to", "project_posteriors_to",
                                    "project_prior_to", "score_new_dataset"])
def test_the_plan_and_gather_project_as_a_per_entity_lookup(case, method):
    """Every slot of every target bucket holds what the per-entity lookup
    finds, or the fill (0 for a mean, 1 for a variance); the scores are the
    parent's NumPy join's, bit for bit. Each case is seen to hold every
    kind of slot it is for."""
    rng = np.random.default_rng(["ragged", "width_one", "padded"].index(case))
    trained, target = _case(case, rng)
    model = _model(trained, rng)
    sources = [model.bucket_coefs, model.bucket_variances]
    (means, variances), seen = _oracle(model, target, sources, [0.0, 1.0])
    assert seen["hit"] and seen["new_entity"]
    if case == "width_one":
        assert {np.shape(p)[1] for p in model.bucket_proj} == {1}
    else:
        assert seen["new_column"] and seen["ghost"]
        assert len(trained.size_classes) >= 2
    if case == "padded":
        assert any((np.asarray(b.entity_ids) < 0).any()
                   for b in target.buckets)

    if method == "project_to":
        got = [model.project_to(target)]
        want = [means]
    elif method == "project_posteriors_to":
        got = list(model.project_posteriors_to(target))
        want = [means, variances]
    elif method == "project_prior_to":
        priors = model.project_prior_to(target, incremental_weight=2.0)
        got = [[p.means for p in priors], [p.precisions for p in priors]]
        want = [means, [2.0 / v for v in variances]]
    else:
        parent = _parent_projection(model, target, [model.bucket_coefs],
                                    [0.0])[0]
        np.testing.assert_array_equal(
            np.asarray(model.score_new_dataset(target)),
            np.asarray(target.scatter_scores(
                [b.scores(c) for b, c in zip(target.buckets, parent)])))
        got, want = [parent], [means]
    for g_stacks, w_stacks in zip(got, want):
        assert len(g_stacks) == len(target.buckets)
        for g, w in zip(g_stacks, w_stacks):
            assert g.dtype == jnp.float32
            np.testing.assert_array_equal(np.asarray(g), w)


def _plans():
    return {o: _RE_PROJECTION_PLANS.value(outcome=o)
            for o in ("built", "reused")}


def _counted(before):
    return {o: n - before[o] for o, n in _plans().items()}


def test_a_kept_plan_serves_every_model_of_its_structure_and_no_other():
    """The target dataset keeps the last plan built into it: a model with
    the same structure objects and other coefficients reuses it and gets
    its own values; a model with equal but other structure objects (as a
    loaded one has) builds a new plan, which then stands in the old one's
    place."""
    rng = np.random.default_rng(7)
    trained, target = _case("ragged", rng)
    model = _model(trained, rng, variances=False)
    scorer = EntityScorer(target)
    before = _plans()
    first = np.asarray(scorer(model))
    assert scorer.plan == "built" and _counted(before) == {
        "built": 1, "reused": 0}
    plan = target.kept_projection["plan"]

    other = dataclasses.replace(model, bucket_coefs=[
        c * 2.0 for c in model.bucket_coefs])
    np.testing.assert_array_equal(np.asarray(scorer(other)), 2.0 * first)
    assert scorer.plan == "reused" and target.kept_projection["plan"] is plan
    assert _counted(before) == {"built": 1, "reused": 1}

    foreign = dataclasses.replace(
        other, bucket_proj=[np.array(p) for p in model.bucket_proj],
        entity_keys=list(model.entity_keys),
        entity_to_slot=dict(model.entity_to_slot))
    np.testing.assert_array_equal(np.asarray(scorer(foreign)), 2.0 * first)
    assert scorer.plan == "built" and target.kept_projection["plan"] is not plan
    assert scorer(model) is not None and scorer.plan == "built"
    assert _counted(before) == {"built": 3, "reused": 1}


def test_a_loaded_model_builds_its_own_plan(tmp_path):
    """A model saved and loaded has arrays of its own: its first projection
    into a dataset builds a plan, its second reuses it, and it scores the
    target as the model it was saved from does."""
    from photon_tpu.game.descent import GameModel
    from photon_tpu.index import build_index_from_features
    from photon_tpu.io.model_io import load_game_model, save_game_model

    rng = np.random.default_rng(11)
    trained, target = _case("ragged", rng)
    model = _model(trained, rng, variances=False)
    want = np.asarray(model.score_new_dataset(target))
    imap = build_index_from_features(
        [("f", str(j)) for j in range(trained.global_dim)],
        add_intercept=False)
    save_game_model(str(tmp_path), GameModel({"perUser": model}),
                    {"global": imap},
                    shard_by_coordinate={"perUser": "global"})
    loaded = load_game_model(str(tmp_path), {"global": imap})[0]["perUser"]
    scorer = EntityScorer(target)
    before = _plans()
    np.testing.assert_allclose(np.asarray(scorer(loaded)), want,
                               rtol=1e-6, atol=1e-6)
    assert scorer.plan == "built"
    scorer(loaded)
    assert scorer.plan == "reused"
    assert _counted(before) == {"built": 1, "reused": 1}


def test_stacks_of_another_shape_are_refused():
    rng = np.random.default_rng(3)
    trained, target = _case("ragged", rng)
    model = _model(trained, rng, variances=False)
    plan = model.projection_plan(target)
    with pytest.raises(ValueError, match="not the"):
        plan.gather([[c[:-1] for c in model.bucket_coefs]], [0.0])
