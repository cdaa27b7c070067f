"""A fit's own span tree (docs/observability.md §"A fit's span tree"):
parents, the kept tree, the profiler mirror, the device's named scopes,
and what a span costs with nothing listening."""
import glob
import json
import os
import subprocess
import sys
import timeit

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.data import batch as batch_mod
from photon_tpu.data.batch import LabeledBatch, ell_from_rows
from photon_tpu.data.random_effect import build_random_effect_dataset
from photon_tpu.estimators import (
    FixedEffectDataConfig,
    GLMOptimizationConfiguration,
    GameEstimator,
    RandomEffectDataConfig,
    fit_breakdown,
)
from photon_tpu.functions.problem import GLMOptimizationProblem, _fit_jitted
from photon_tpu.game import newton_re
from photon_tpu.game.random_effect import _RE_PROJECTION_PLANS as _PLANS
from photon_tpu.io.data_reader import GameDataBundle
from photon_tpu.obs import trace as obs_trace
from photon_tpu.obs.trace import (
    device_wait,
    recent_trees,
    trace_span,
    tracing,
)
from photon_tpu.optim import (
    OptimizerConfig,
    RegularizationContext,
    RegularizationType,
)
from photon_tpu.types import TaskType

L2 = RegularizationContext(RegularizationType.L2)
NAME, SPAN_ID, PARENT_ID, START, END, ARGS = range(6)

# The tree of docs/observability.md: every span name beside its parent's.
FIT_TREE = {
    "estimator.fit": None,
    "estimator.prepare": "estimator.fit",
    "estimator.prepare_validation": "estimator.fit",
    "data.re_dataset": ("estimator.prepare", "estimator.prepare_validation"),
    "estimator.build_coordinates": "estimator.fit",
    "data.accel_tables": "estimator.build_coordinates",
    "descent.run": "estimator.fit",
    "descent.sweep": "descent.run",
    "descent.step": "descent.sweep",
    "optim.fixed_solve": "descent.step",
    "optim.glm_fit": "optim.fixed_solve",
    "optim.re_inputs": "descent.step",
    "optim.re_bucket": "descent.step",
    "descent.score": "descent.step",
    "descent.validate": "descent.sweep",
    "estimator.evaluate": "estimator.fit",
    "validate.score": ("descent.validate", "estimator.evaluate"),
    "validate.evaluate": ("descent.validate", "estimator.evaluate"),
    # by site: step; solver_outcome; project_stacks (a plan being built);
    # evaluator; re_dataset; accel_tables (a traced fit's fixed_solve lies under optim.fixed_solve,
    # a dual bucket's u_max under optim.re_bucket)
    "device.wait": ("descent.step", "descent.sweep", "validate.score",
                    "validate.evaluate", "data.re_dataset",
                    "data.accel_tables"),
}


def _bundle(seed, n_users=6, rows_per_user=12, d_global=5, d_user=3):
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


@pytest.fixture(scope="module")
def fitted():
    """The kept trees of two fits of one estimator on one bundle, fixed +
    one random effect, validation on, and what ``re_projection_plans_total``
    counted over them. The fast-path tables are a TPU default; here the
    CPU is named an accelerator so that they are built."""
    estimator = GameEstimator(
        task=TaskType.LOGISTIC_REGRESSION,
        coordinate_data_configs={
            "fixed": FixedEffectDataConfig(feature_shard="global"),
            "perUser": RandomEffectDataConfig(re_type="userId",
                                              feature_shard="user")},
        n_sweeps=2, evaluator_specs=("AUC", "LOGISTIC_LOSS"))
    config = {cid: GLMOptimizationConfiguration(
        max_iterations=5, regularization=L2, reg_weight=1.0)
        for cid in ("fixed", "perUser")}
    train, validation = _bundle(1), _bundle(2)
    before = len(recent_trees("estimator.fit"))
    plans = {o: _PLANS.value(outcome=o) for o in ("built", "reused")}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(batch_mod, "REAL_ACCELERATOR_BACKENDS", ("cpu",))
        estimator.fit(train, validation, [config])
        estimator.fit(train, validation, [config])
    trees = recent_trees("estimator.fit")
    assert len(trees) == min(before + 2, 256)
    return {"trees": trees[-2:],
            "plans": {o: _PLANS.value(outcome=o) - n
                      for o, n in plans.items()}}


@pytest.fixture(scope="module")
def two_fits(fitted):
    return fitted["trees"]


def _names(tree):
    return [s[NAME] for s in tree]


def test_first_fit_leaves_exactly_the_catalogued_tree(two_fits):
    first = two_fits[0]
    assert set(_names(first)) == set(FIT_TREE)
    assert first[-1][NAME] == "estimator.fit"
    count = {n: _names(first).count(n) for n in FIT_TREE}
    # 2 sweeps x 2 coordinates; one table build (the fixed effect's shard)
    assert count["descent.sweep"] == 2
    assert count["descent.step"] == count["descent.validate"] == 4
    assert count["optim.glm_fit"] == count["optim.re_bucket"] == 2
    assert count["optim.re_inputs"] == 2 and count["descent.score"] == 4
    # a coordinate's scorer a step, every coordinate's on the returned
    # model; two evaluators after a step and two on the returned model
    assert count["validate.score"] == 4 + 2
    assert count["validate.evaluate"] == 2 * 4 + 2
    assert count["data.accel_tables"] == 1
    assert count["data.re_dataset"] == 2       # training rows, validation rows
    assert count["estimator.fit"] == count["descent.run"] == 1


def test_parent_ids_link_the_tree(two_fits):
    for tree in two_fits:
        by_id = {s[SPAN_ID]: s for s in tree}
        assert len(by_id) == len(tree)
        for s in tree:
            parent = by_id.get(s[PARENT_ID])
            wanted = FIT_TREE[s[NAME]]
            wanted = wanted if isinstance(wanted, tuple) else (wanted,)
            assert (parent and parent[NAME]) in wanted, s[NAME]


def test_spans_of_a_fit_share_one_trace_id(two_fits):
    ids = [{s[ARGS]["trace_id"] for s in tree} for tree in two_fits]
    assert all(len(i) == 1 for i in ids)
    assert ids[0] != ids[1]            # a fit is a request of its own


def test_every_child_lies_inside_its_parent(two_fits):
    for tree in two_fits:
        by_id = {s[SPAN_ID]: s for s in tree}
        for s in tree:
            assert s[START] <= s[END]
            if s[PARENT_ID] in by_id:
                parent = by_id[s[PARENT_ID]]
                assert parent[START] <= s[START] and s[END] <= parent[END]


def test_second_fit_on_the_same_bundle_prepares_nothing(two_fits):
    """Datasets, validation structures and the fast-path tables are kept
    with the prepared bundle (tests/test_kept_tables.py has the rest)."""
    second = set(_names(two_fits[1]))
    assert second == set(FIT_TREE) - {"estimator.prepare",
                                      "estimator.prepare_validation",
                                      "data.re_dataset",
                                      "data.accel_tables"}


def test_span_arguments_say_what_the_work_was(two_fits):
    by_name = {s[NAME]: s[ARGS] for s in two_fits[0]}
    assert by_name["estimator.fit"]["rows"] == 72
    assert by_name["estimator.fit"]["configs"] == 1
    assert by_name["estimator.prepare"]["shards"] == 2
    assert by_name["data.accel_tables"]["entries"] == 72 * 5
    # 72 rows by 16 columns are one window on either side: the kernel.
    tables = by_name["data.accel_tables"]
    assert tables["formulation_matvec"] == "window"
    assert tables["formulation_rmatvec"] == "window"
    assert tables["passes_per_slot_matvec"] == 1.0
    assert tables["passes_per_slot_rmatvec"] == 1.0
    build = [{s[NAME]: s[ARGS] for s in tree}["estimator.build_coordinates"]
             for tree in two_fits]
    assert [(b["tables_built"], b["tables_reused"]) for b in build] == [
        (1, 0), (0, 1)]
    assert by_name["descent.run"] == {
        "trace_id": by_name["estimator.fit"]["trace_id"],
        "sweeps": 2, "coordinates": 2}
    assert by_name["descent.validate"]["coordinate"] in ("fixed", "perUser")


def test_a_newton_buckets_span_says_how_its_systems_were_factorized(two_fits):
    """``solve`` is ``newton_re.solve_form()``, what ``_newton_loop`` asks
    while tracing; set at exit beside ``solver``."""
    buckets = [s[ARGS] for s in two_fits[1] if s[NAME] == "optim.re_bucket"]
    assert len(buckets) == 2
    for args in buckets:
        assert args["solver"] == "newton_primal" and args["chunk"] is None
        assert args["solve"] == newton_re.solve_form() == "lanes"


def test_a_buckets_span_names_its_coordinates_entity_column(two_fits):
    """``re_type`` is the dataset's: with two per-entity coordinates a
    fit's tree says which of them a bucket belonged to."""
    for tree in two_fits:
        buckets = [s[ARGS] for s in tree if s[NAME] == "optim.re_bucket"]
        assert [b["re_type"] for b in buckets] == ["userId", "userId"]


@pytest.mark.parametrize("scoring, parent", [
    (False, "estimator.prepare"), (True, "estimator.prepare_validation")])
def test_a_dataset_span_says_what_was_grouped(two_fits, scoring, parent):
    """One ``data.re_dataset`` a key and a bundle: the key, whether the
    rows are scored or trained on, the entities, the size classes and the
    dataset's own ``span_arguments()``."""
    first = two_fits[0]
    by_id = {s[SPAN_ID]: s for s in first}
    (span,) = [s for s in first if s[NAME] == "data.re_dataset"
               and s[ARGS]["scoring"] is scoring]
    assert by_id[span[PARENT_ID]][NAME] == parent
    args = dict(span[ARGS])
    args.pop("trace_id")
    # 6 users x 12 rows, padded to 16: one size class, one bucket
    assert args == {"re_type": "userId", "scoring": scoring, "entities": 6,
                    "classes": 1, "buckets": 1, "rows": 72,
                    "row_slots": 6 * 16}


def _children(tree):
    out = {}
    for s in tree:
        out.setdefault(s[PARENT_ID], []).append(s)
    return out


def _sites(spans):
    return [s[ARGS]["site"] for s in spans if s[NAME] == "device.wait"]


def test_every_blocking_read_is_a_leaf_that_names_its_site(two_fits):
    """The first fit builds the per-entity scorer's projection plan and
    reads the model's columns for it; the second reuses the plan and reads
    nothing of the kind."""
    for tree, sites in zip(two_fits, (
            {"step", "solver_outcome", "project_stacks", "evaluator"},
            {"step", "solver_outcome", "evaluator"})):
        children = _children(tree)
        waits = [s for s in tree if s[NAME] == "device.wait"]
        assert waits and not any(children.get(s[SPAN_ID]) for s in waits)
        assert set(_sites(waits)) - {"re_dataset", "accel_tables"} == sites


def test_a_step_scores_then_waits_once_for_the_device(two_fits):
    """A step is its solve, its scorer and one commit read, in that order;
    its seconds are theirs and its own: the children lie one after another
    inside it."""
    for tree in two_fits:
        children = _children(tree)
        steps = [s for s in tree if s[NAME] == "descent.step"]
        assert len(steps) == 4
        for step in steps:
            inside = sorted(children[step[SPAN_ID]], key=lambda s: s[START])
            assert _sites(inside) == ["step"]
            assert [s[NAME] for s in inside][-2:] == ["descent.score",
                                                       "device.wait"]
            assert inside[-2][ARGS]["coordinate"] == step[ARGS]["coordinate"]
            at, covered = step[START], 0.0
            for s in inside:
                assert at <= s[START] <= s[END] <= step[END]
                at, covered = s[END], covered + s[END] - s[START]
            own = (step[END] - step[START]) - covered
            assert 0.0 <= own <= step[END] - step[START]


def test_a_bucket_is_its_inputs_then_its_solve_under_the_step(two_fits):
    """What the benchmark's bucket readers walk: a bucket span's parent is
    a ``descent.step`` and that one's a ``descent.sweep``; nothing stands
    between. The inputs' span is the sibling before."""
    for tree in two_fits:
        by_id = {s[SPAN_ID]: s for s in tree}
        children = _children(tree)
        buckets = [s for s in tree if s[NAME] == "optim.re_bucket"]
        assert len(buckets) == 2
        for bucket in buckets:
            step = by_id[bucket[PARENT_ID]]
            assert step[NAME] == "descent.step"
            assert by_id[step[PARENT_ID]][NAME] == "descent.sweep"
            inside = sorted(children[step[SPAN_ID]], key=lambda s: s[START])
            assert [s[NAME] for s in inside] == [
                "optim.re_inputs", "optim.re_bucket", "descent.score",
                "device.wait"]
            inputs = inside[0][ARGS]
            assert (inputs["re_type"], inputs["bucket"]) == (
                bucket[ARGS]["re_type"], bucket[ARGS]["bucket"])


def test_validation_is_a_scorer_and_the_evaluators_each_with_its_wait(
        two_fits):
    for tree in two_fits:
        by_id = {s[SPAN_ID]: s for s in tree}
        children = _children(tree)
        for v in (s for s in tree if s[NAME] == "descent.validate"):
            inside = sorted(children[v[SPAN_ID]], key=lambda s: s[START])
            assert [s[NAME] for s in inside] == [
                "validate.score", "validate.evaluate", "validate.evaluate"]
            assert inside[0][ARGS]["coordinate"] == v[ARGS]["coordinate"]
        (final,) = [s for s in tree if s[NAME] == "estimator.evaluate"]
        inside = sorted(children[final[SPAN_ID]], key=lambda s: s[START])
        assert [(s[NAME], s[ARGS].get("coordinate")) for s in inside] == [
            ("validate.score", "fixed"), ("validate.score", "perUser"),
            ("validate.evaluate", None), ("validate.evaluate", None)]
        evaluations = [s for s in tree if s[NAME] == "validate.evaluate"]
        assert [s[ARGS]["evaluator"] for s in evaluations] == [
            "AUC", "LOGISTIC_LOSS"] * 5
        for e in evaluations:
            assert _sites(children[e[SPAN_ID]]) == ["evaluator"]
        # a fixed effect's scorer reads nothing and has no plan
        for s in (s for s in tree if s[NAME] == "validate.score"
                  and s[ARGS]["coordinate"] == "fixed"):
            assert "plan" not in s[ARGS]
            assert not _sites(children.get(s[SPAN_ID], ()))


def test_a_per_entity_scorer_builds_its_plan_once_over_both_fits(fitted):
    """The first ``perUser`` scorer of the first fit builds the projection
    plan and holds every read of site ``project_stacks`` of both fits; every
    later one, in that fit and the next, reuses the plan the validation
    dataset keeps and waits on nothing: the estimator keeps both datasets
    with the bundle, and every model trained on one dataset shares its
    ``proj`` arrays. Counted alike by ``re_projection_plans_total``."""
    scorers = []
    for tree in fitted["trees"]:
        children = _children(tree)
        for s in sorted(tree, key=lambda s: s[START]):
            if (s[NAME] == "validate.score"
                    and s[ARGS]["coordinate"] == "perUser"):
                scorers.append((s, _sites(children.get(s[SPAN_ID], ()))))
    # two steps of the coordinate and the returned model, a fit
    assert len(scorers) == 2 * 3
    (first, first_sites), *later = scorers
    assert first[ARGS]["plan"] == "built"
    reads = [s for tree in fitted["trees"] for s in tree
             if s[NAME] == "device.wait"
             and s[ARGS]["site"] == "project_stacks"]
    assert reads and {s[PARENT_ID] for s in reads} == {first[SPAN_ID]}
    assert set(first_sites) == {"project_stacks"}
    for s, sites in later:
        assert s[ARGS]["plan"] == "reused" and sites == []
    assert fitted["plans"] == {"built": 1, "reused": 2 * 3 - 1}


def test_fit_breakdown_adds_up_to_the_fit(two_fits):
    for tree, first in zip(two_fits, (True, False)):
        parts = fit_breakdown(tree)
        assert list(parts)[0] == "fit" and list(parts)[-2:] == [
            "descent", "waited"]
        assert ("prepare" in parts) == ("tables" in parts) == first
        assert {"fixed", "perUser", "validate"} <= set(parts)
        waited = parts.pop("waited")
        assert waited == pytest.approx(sum(
            s[END] - s[START] for s in tree if s[NAME] == "device.wait"))
        fit = parts.pop("fit")
        assert 0.0 < waited < fit
        assert fit == pytest.approx(tree[-1][END] - tree[-1][START])
        assert sum(parts.values()) == pytest.approx(fit)
        assert all(v > 0 for v in parts.values())


def test_fit_breakdown_of_a_hand_built_tree():
    tree = [
        ("data.accel_tables", 3, 2, 0.1, 1.1, {}),
        ("estimator.build_coordinates", 2, 1, 0.0, 1.2, {}),
        ("optim.glm_fit", 7, 6, 1.3, 1.7, {}),
        ("device.wait", 11, 6, 1.7, 1.8, {"site": "step"}),
        ("descent.step", 6, 5, 1.3, 1.8, {"coordinate": "global"}),
        ("device.wait", 12, 8, 1.85, 1.9, {"site": "evaluator"}),
        ("descent.validate", 8, 5, 1.8, 1.9, {"coordinate": "global"}),
        ("device.wait", 13, 9, 2.0, 2.2, {"site": "step"}),
        ("descent.step", 9, 5, 1.9, 2.2, {"coordinate": "per-user"}),
        ("descent.sweep", 5, 4, 1.25, 2.3, {}),
        ("descent.run", 4, 1, 1.2, 2.3, {}),
        ("estimator.evaluate", 10, 1, 2.3, 2.4, {}),
        ("estimator.fit", 1, None, 0.0, 2.5, {}),
    ]
    parts = fit_breakdown(tree)
    assert list(parts) == ["fit", "tables", "global", "per-user", "validate",
                           "descent", "waited"]
    assert parts == pytest.approx({
        "fit": 2.5, "tables": 1.0, "global": 0.5, "per-user": 0.3,
        "validate": 0.2, "descent": 0.5, "waited": 0.35})


def test_the_ring_holds_256_roots_and_drops_the_oldest():
    for i in range(300):
        with trace_span("test.ring_root", cat="test", i=i).keep_tree():
            with trace_span("test.ring_child", cat="test"):
                pass
    trees = recent_trees("test.ring_root")
    assert len(trees) == 256
    assert [t[-1][ARGS]["i"] for t in trees] == list(range(44, 300))
    assert all(_names(t) == ["test.ring_child", "test.ring_root"]
               for t in trees)
    assert [t[-1][ARGS]["i"] for t in recent_trees("test.ring_root", 3)] == [
        297, 298, 299]
    assert recent_trees("test.ring_root", 0) == []
    assert recent_trees("test.no_such_root") == []


def test_a_tree_over_its_cap_keeps_its_root_and_says_so(monkeypatch):
    monkeypatch.setattr(obs_trace, "_KEPT_SPANS_PER_TREE", 4)
    with trace_span("test.capped_root", cat="test").keep_tree():
        for _ in range(10):
            with trace_span("test.capped_child", cat="test"):
                pass
    tree = recent_trees("test.capped_root")[-1]
    assert _names(tree) == ["test.capped_child"] * 4 + ["test.capped_root"]
    assert tree[-1][ARGS]["truncated"] is True


def test_spans_outside_a_kept_root_are_kept_nowhere():
    before = len(recent_trees("test.unkept_root"))
    with trace_span("test.unkept_root", cat="test") as root:
        with trace_span("test.unkept_child", cat="test") as child:
            assert child.parent_id == root.span_id
    assert root.parent_id is None
    assert len(recent_trees("test.unkept_root")) == before


def test_a_span_left_open_ends_with_its_parent_and_carries_the_error():
    """``descent.sweep`` is entered by hand; an exception in a step unwinds
    past it, and ``descent.run``'s exit ends it."""
    with pytest.raises(KeyError):
        with trace_span("test.outer", cat="test").keep_tree():
            trace_span("test.by_hand", cat="test").__enter__()
            with trace_span("test.inner", cat="test"):
                raise KeyError("boom")
    tree = recent_trees("test.outer")[-1]
    assert _names(tree) == ["test.inner", "test.by_hand", "test.outer"]
    assert [s[ARGS].get("error") for s in tree] == ["KeyError"] * 3
    with trace_span("test.after", cat="test") as after:   # the stack is clean
        assert after.parent_id is None


def test_a_discarded_span_is_recorded_nowhere():
    with tracing() as col:
        with trace_span("test.kept_root", cat="test").keep_tree():
            with trace_span("test.discarded", cat="test") as sp:
                sp.discard()
            with trace_span("test.recorded", cat="test"):
                pass
    assert _names(recent_trees("test.kept_root")[-1]) == [
        "test.recorded", "test.kept_root"]
    assert [e["name"] for e in col.events] == ["test.recorded",
                                               "test.kept_root"]


def test_off_the_accelerator_no_table_span():
    """On this backend ``with_accelerator_paths`` hands the features back
    unchanged: no build, so no span."""
    feats = ell_from_rows([(np.arange(3), np.ones(3))] * 4, 3)
    lb = LabeledBatch(feats, jnp.zeros(4), jnp.zeros(4), jnp.ones(4))
    with trace_span("test.tables_root", cat="test").keep_tree():
        assert lb.with_accelerator_paths() is lb
    assert _names(recent_trees("test.tables_root")[-1]) == ["test.tables_root"]


def test_chrome_export_carries_parent_ids(tmp_path):
    path = tmp_path / "trace.json"
    with tracing(str(path)):
        with trace_span("test.a", cat="test") as a:
            with trace_span("test.b", cat="test") as b:
                pass
    events = {e["name"]: e for e in json.load(open(path))["traceEvents"]
              if e.get("ph") == "X"}
    assert events["test.b"]["args"]["parent_id"] == a.span_id
    assert events["test.b"]["args"]["span_id"] == b.span_id
    assert "parent_id" not in events["test.a"]["args"]


def test_tail_sampled_spans_keep_their_own_ids():
    sampler = obs_trace.TailSampler(min_history=1)
    obs_trace.install_tail_sampler(sampler)
    try:
        with tracing() as col:
            sampler.begin("t-test")
            with obs_trace.trace_context("t-test"):
                with trace_span("test.req", cat="test") as req:
                    with trace_span("test.part", cat="test") as part:
                        pass
            sampler.finish("t-test", 1.0, error=True)
    finally:
        obs_trace.uninstall_tail_sampler()
    args = {e["name"]: e["args"] for e in col.events if e["ph"] == "X"}
    assert args["test.part"]["span_id"] == part.span_id
    assert args["test.part"]["parent_id"] == req.span_id == \
        args["test.req"]["span_id"]


def test_spans_lie_on_the_profilers_host_plane(tmp_path):
    """Under ``jax.profiler.trace`` the program's spans come back from the
    profile, on ``/host:CPU``, nested as they were entered."""
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(tmp_path)):
        with trace_span("estimator.fit", cat="estimator"):
            with trace_span("data.accel_tables", cat="data"):
                with device_wait("accel_tables") as waited:
                    jnp.ones(8).sum().block_until_ready()
    with trace_span("test.after_the_session", cat="test"):
        pass
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    found = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("estimator.fit", "data.accel_tables",
                                      "device.wait",
                                      "test.after_the_session")):
                    found[e.name] = (plane.name, e.start_ns,
                                     e.start_ns + e.duration_ns)
    # a wait's annotation says where (an annotation has no arguments); the
    # kept tree's name stays and the site is its argument
    assert set(found) == {"estimator.fit", "data.accel_tables",
                          "device.wait:accel_tables"}
    assert {f[0] for f in found.values()} == {"/host:CPU"}
    fit, tables = found["estimator.fit"], found["data.accel_tables"]
    wait = found["device.wait:accel_tables"]
    assert fit[1] <= tables[1] <= wait[1] and wait[2] <= tables[2] <= fit[2]
    assert (waited.name, waited.args) == ("device.wait",
                                          {"site": "accel_tables"})


def _sparse_batch(n=16, d=8, k=3):
    r = np.random.default_rng(0)
    rows = [(r.choice(d, k, replace=False), r.normal(size=k))
            for _ in range(n)]
    return LabeledBatch(
        features=ell_from_rows(rows, d, dtype=jnp.float32),
        labels=jnp.asarray(r.random(n) < 0.5, jnp.float32),
        offsets=jnp.zeros(n, jnp.float32), weights=jnp.ones(n, jnp.float32))


@pytest.fixture(scope="module")
def fit_program_text():
    problem = GLMOptimizationProblem(
        task=TaskType.LOGISTIC_REGRESSION,
        optimizer_config=OptimizerConfig(max_iterations=3),
        regularization=L2, reg_weight=1.0)
    batch = _sparse_batch()
    return _fit_jitted.lower(
        problem, batch, jnp.zeros(batch.dim, jnp.float32), None, None, None,
        jnp.asarray(1.0, jnp.float32)).as_text(debug_info=True)


@pytest.mark.parametrize("scope", [
    "sparse.matvec", "sparse.rmatvec", "lbfgs.direction",
    "lbfgs.line_search", "lbfgs.update"])
def test_fit_program_names_its_scopes(fit_program_text, scope):
    assert scope in fit_program_text


@pytest.fixture(scope="module")
def newton_program_text():
    r = np.random.default_rng(3)
    n, d, k = 40, 12, 3
    keys = np.asarray([f"u{i % 5}" for i in range(n)], object)
    idx = np.stack([r.choice(d, k, replace=False) for _ in range(n)])
    ds = build_random_effect_dataset(
        "userId", keys, idx.astype(np.int32),
        r.normal(size=(n, k)).astype(np.float32),
        (r.random(n) < 0.5).astype(np.float32), global_dim=d,
        dtype=np.float32)
    b = ds.buckets[0]
    problem = GLMOptimizationProblem(
        task=TaskType.LOGISTIC_REGRESSION,
        optimizer_config=OptimizerConfig(max_iterations=3),
        regularization=L2, reg_weight=1.0)
    shape = (b.n_entities, b.local_dim)
    return newton_re.fit_bucket_newton.lower(
        problem, b.local_batches(jnp.zeros(ds.n_rows, jnp.float32)),
        jnp.zeros(shape, jnp.float32), jnp.ones(shape, jnp.float32),
        None).as_text(debug_info=True)


@pytest.mark.parametrize("scope", [
    "newton.design", "newton.hessian", "newton.solve", "newton.line_search"])
def test_newton_program_names_its_scopes(newton_program_text, scope):
    assert scope in newton_program_text


SPAN_BUDGET_US = 3.0


def test_a_span_with_nothing_listening_costs_under_the_budget():
    """No collector, no profiler session, no kept root above: the cost of
    ``with trace_span(...)`` is the floor every instrumented call pays."""
    assert not obs_trace.tracing_active()

    def one():
        with trace_span("test.cost", cat="test", rows=1):
            pass

    n = 20_000
    best = min(timeit.repeat(one, number=n, repeat=7)) / n
    assert best * 1e6 < SPAN_BUDGET_US, f"{best * 1e6:.2f} us a span"


def test_a_span_does_not_import_jax():
    """The serving front line's workers never touch JAX: a span there must
    not be what imports it."""
    code = (
        "import sys\n"
        "from photon_tpu.obs.trace import trace_span, recent_trees\n"
        "with trace_span('root', cat='test').keep_tree():\n"
        "    with trace_span('child', cat='test'):\n"
        "        pass\n"
        "assert len(recent_trees('root')[-1]) == 2\n"
        "assert 'jax' not in sys.modules, 'a span imported jax'\n")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    p = subprocess.run([sys.executable, "-c", code], cwd=root,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr
