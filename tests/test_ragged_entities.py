"""A GAME fit whose users hold unequal numbers of rows (PR 33): the
program against the plain reference over ragged entities, the bucket
builder against its entity-at-a-time oracle, the span arguments that say
what a bucket's padding costs, no retrace on a second fit, and the
validation projection against a per-entity oracle.

The data is the benchmark's own at a small size: 240 users whose rows are
the quantiles of the configuration's log-normal, clipped to [20, 700], so
six size classes of bucket (32 to 1,024 rows); everything float32 on the
CPU, as the cell runs it.
"""
import copy
import json
import os

import numpy as np
import pytest

from benchmarks.kinds import fit, fit_ragged
from benchmarks.references import entities_ragged
from photon_tpu.data.random_effect import (
    _build_reference_loop,
    build_random_effect_dataset,
)
from photon_tpu.obs import retrace
from photon_tpu.obs.trace import recent_trees

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME, SPAN_ID, PARENT_ID, START, END, ARGS = range(6)


def small(config: dict) -> dict:
    c = copy.deepcopy(config)
    d = c["data"]
    d.update(named_features=255, users=240,
             validation={"rows_per_user": 4, "unseen_users": 4,
                         "unseen_rows": 2})
    d["rows_per_user"].update(of_users=240, every=1, max=700)
    return c


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "game-logistic-ragged-re.json")) as f:
        return small(json.load(f))


@pytest.fixture(scope="module")
def limits():
    with open(os.path.join(ROOT, "benchmarks", "limits",
                           "game_fit_ragged.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def ds(config):
    return fit_ragged.generate(config["data"], 2**31 + 32)


@pytest.fixture(scope="module")
def two_fits(config, ds):
    """Two whole fits on one bundle, as a window makes them: the compared
    numbers of the second, the compile requests and solver traces of each,
    and the second fit's span tree."""
    import jax.monitoring

    compiled = []          # one entry a program handed to the compiler

    def on_duration(event, duration, **kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(duration)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    estimator, train, validation, opt = fit.build(config, ds)
    probe = fit.Probe()
    probe.install()
    try:
        marks = []
        for _ in range(2):
            result, _ = fit.one_fit(estimator, train, validation, opt, probe)
            marks.append((len(compiled), dict(retrace.all_traces())))
        steps = fit._plain_steps(probe.steps, ds)
    finally:
        probe.remove()
    dataset = estimator._prep_cache[1]["train"]["perUser"]
    return {"numbers": fit_ragged.check(config, ds, steps,
                                        fit._tracker(result)),
            "marks": marks, "tree": recent_trees("estimator.fit", 1)[0],
            "dataset": dataset}


def test_the_data_has_six_size_classes(config, ds):
    counts = np.bincount(ds.train.users, minlength=ds.n_users)
    assert counts.min() == 20 and counts.max() == 700
    assert sorted(set(entities_ragged.size_classes(counts))) == [
        32, 64, 128, 256, 512, 1024]


def test_the_program_agrees_with_the_ragged_reference(two_fits, limits):
    """Every number the cell compares (per-class residual, scores, offsets,
    the validation evaluators, the fixed steps' paths) under the cell's own
    limits, which are chip readings: a float32 CPU fit of a fiftieth of the
    rows sums less and reads well inside them."""
    ok, compared = fit.judge(two_fits["numbers"], limits)
    assert ok, compared
    assert {"re_resid", "scores", "offsets", "val_auc",
            "val_logistic_loss"} <= set(compared)


def test_the_builder_agrees_with_its_entity_at_a_time_oracle(ds):
    tr = ds.train
    args = ("userId", fit.datagen.user_keys(tr.users),
            tr.ui.astype(np.int32), tr.uv.astype(np.float32),
            tr.y.astype(np.float32), ds.user_dim)
    fast = build_random_effect_dataset(*args, intercept_index=ds.user_dim - 1)
    slow = _build_reference_loop(*args, intercept_index=ds.user_dim - 1)
    assert len(fast.buckets) == len(slow.buckets) == 6
    assert list(fast.entity_keys) == list(slow.entity_keys)
    assert fast.bucket_rows == slow.bucket_rows
    assert sum(fast.bucket_rows) == tr.n_rows
    for a, b in zip(fast.buckets, slow.buckets):
        for field in ("idx", "val", "labels", "weights", "train_weights",
                      "row_ids", "proj", "entity_ids"):
            np.testing.assert_array_equal(          # exact: the same writes
                np.asarray(getattr(a, field)), np.asarray(getattr(b, field)),
                err_msg=field)


def test_bucket_spans_say_what_the_padding_costs(two_fits, ds):
    tree, dataset = two_fits["tree"], two_fits["dataset"]
    steps = [s for s in tree if s[NAME] == "descent.step"
             and "buckets" in s[ARGS]]
    assert len(steps) == 2                      # two sweeps, one RE step each
    for step in steps:
        held = [s[ARGS] for s in tree
                if s[NAME] == "optim.re_bucket" and s[PARENT_ID] == step[SPAN_ID]]
        assert step[ARGS]["buckets"] == len(held) == len(dataset.buckets)
        assert sum(b["rows"] for b in held) == step[ARGS]["rows"] == ds.train.n_rows
        assert (sum(b["row_slots"] for b in held) == step[ARGS]["row_slots"]
                == dataset.row_slots)
        for b, bucket in zip(held, dataset.buckets):
            assert b["padded_rows"] == bucket.max_samples
            assert b["row_slots"] == bucket.n_entities * bucket.max_samples
            assert b["solver"] == "newton_primal" and b["chunk"] is None
    fixed = [s for s in tree if s[NAME] == "descent.step"
             and "buckets" not in s[ARGS]]
    assert len(fixed) == 2 and all("rows" not in s[ARGS] for s in fixed)


def test_a_second_fit_on_the_bundle_compiles_nothing(two_fits):
    (first, first_traces), (second, second_traces) = two_fits["marks"]
    assert first > 0                    # the listener hears this process
    assert second == first
    assert second_traces == first_traces
    assert first_traces.get("fit_bucket_newton", 0) >= 6   # one a class


def test_projection_agrees_with_a_per_entity_oracle(two_fits, ds, config):
    """``RandomEffectModel.project_to`` (validation scoring) matches every
    entity's trained columns in one pass; the oracle looks each entity up
    by itself. Exact: both copy the same float32 values."""
    import jax.numpy as jnp

    from photon_tpu.game.random_effect import RandomEffectModel
    from photon_tpu.types import TaskType

    trained = two_fits["dataset"]
    rng = np.random.default_rng(5)
    model = RandomEffectModel(
        re_type="userId", task=TaskType.LOGISTIC_REGRESSION,
        bucket_coefs=[jnp.asarray(rng.normal(size=np.shape(b.proj)),
                                  jnp.float32) for b in trained.buckets],
        bucket_proj=[b.proj for b in trained.buckets],
        bucket_entity_ids=[b.entity_ids for b in trained.buckets],
        entity_keys=trained.entity_keys,
        entity_to_slot=trained.entity_to_slot, global_dim=ds.user_dim)
    va = ds.validation                # four unseen users; 4 rows hold 9 columns
    other = build_random_effect_dataset(
        "userId", fit.datagen.user_keys(va.users), va.ui.astype(np.int32),
        va.uv.astype(np.float32), va.y.astype(np.float32), ds.user_dim,
        intercept_index=ds.user_dim - 1)
    got = model.project_to(other)
    seen = 0
    for bucket, coefs in zip(other.buckets, got):
        proj, eids = np.asarray(bucket.proj), np.asarray(bucket.entity_ids)
        want = np.zeros(proj.shape, np.float32)
        for lane, dense in enumerate(eids):
            cols, vals = model.coefficients_for(other.entity_keys[dense])
            for p, col in enumerate(proj[lane]):
                hit = np.flatnonzero(cols == col)
                if len(hit):
                    want[lane, p] = vals[hit[0]]
                    seen += 1
        np.testing.assert_array_equal(np.asarray(coefs), want)
    assert seen > ds.n_users * 5


@pytest.mark.parametrize("local_dim", [5, 32, 33, 300])
def test_an_entry_is_picked_alike_by_select_and_by_gather(local_dim):
    """``_bucket_scores`` and ``_dense_design`` pick an entry's column by
    compare-select up to ``SELECT_MAX_COLUMNS`` local columns and by a
    gather / scatter-add above (the pick costs P compares an entry): on
    both sides of the bound they are the scores and the design a float64
    loop gives, ghost entries counting for nothing and entries of a slot
    that share a column adding. 1e-6: float32 sums of at most 7 terms of
    order 1."""
    import jax.numpy as jnp

    from photon_tpu.data import random_effect as re_data
    from photon_tpu.data.batch import LabeledBatch, SparseFeatures
    from photon_tpu.game.newton_re import _dense_design

    assert (local_dim > re_data.SELECT_MAX_COLUMNS) == (local_dim > 32)
    e, s, k, p = 6, 9, 7, local_dim
    rng = np.random.default_rng(local_dim)
    idx = rng.integers(0, p + 1, (e, s, k)).astype(np.int32)   # p: ghost
    idx[:, :, 1] = idx[:, :, 0]                                # a shared column
    val = rng.normal(size=(e, s, k)).astype(np.float32)
    val[idx == p] = 0.0
    coefs = rng.normal(size=(e, p)).astype(np.float32)
    design = np.zeros((e, s, p + 1))
    np.add.at(design, (np.arange(e)[:, None, None],
                       np.arange(s)[None, :, None], idx), val)
    want = np.einsum("esp,ep->es", design[..., :p], coefs.astype(np.float64))

    got = re_data._bucket_scores(
        jnp.asarray(idx), jnp.asarray(val), jnp.asarray(coefs))
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5, rtol=1e-6)
    zeros = jnp.zeros((e, s), jnp.float32)
    x_ext, *_ = _dense_design(LabeledBatch(
        features=SparseFeatures(idx=jnp.asarray(idx), val=jnp.asarray(val),
                                dim=p),
        labels=zeros, offsets=zeros, weights=zeros), jnp.float32)
    np.testing.assert_allclose(np.asarray(x_ext), design, atol=1e-6)
