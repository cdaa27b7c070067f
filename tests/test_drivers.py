"""End-to-end CLI driver runs on tiny Avro fixtures (SURVEY.md §4 E2E tier).

Mirrors the reference's ⟦GameTrainingDriverIntegTest / GameScoringDriverIntegTest
/ FeatureIndexingDriverIntegTest⟧: full driver invocations against small Avro
datasets in a temp dir; assert outputs exist, parse, and metrics are sane.
"""
import json
import os
import re

import numpy as np
import pytest

from photon_tpu.cli import feature_indexing_driver, game_scoring_driver, game_training_driver
from photon_tpu.cli.params import parse_coordinate_spec, parse_feature_shard
from photon_tpu.io.avro import read_records, write_container

RECORD_SCHEMA = {
    "type": "record",
    "name": "TrainingExampleAvro",
    "fields": [
        {"name": "uid", "type": ["null", "string"], "default": None},
        {"name": "response", "type": "double"},
        {"name": "offset", "type": ["null", "double"], "default": None},
        {"name": "weight", "type": ["null", "double"], "default": None},
        {"name": "features", "type": {"type": "array", "items": {
            "type": "record", "name": "FeatureAvro", "fields": [
                {"name": "name", "type": "string"},
                {"name": "term", "type": ["null", "string"], "default": None},
                {"name": "value", "type": "double"},
            ]}}},
        {"name": "metadataMap",
         "type": ["null", {"type": "map", "values": "string"}], "default": None},
    ],
}


def _write_game_avro(path, seed, n_users=8, rows_per_user=24, d_global=5, d_user=3):
    """GLMix data: global features f0..f4 + per-user block features."""
    truth = np.random.default_rng(77)
    wg = truth.normal(size=d_global)
    wu = truth.normal(size=(n_users, d_user)) * 1.5
    rng = np.random.default_rng(seed)
    n = n_users * rows_per_user
    users = rng.permutation(np.repeat(np.arange(n_users), rows_per_user))
    recs = []
    for i in range(n):
        u = int(users[i])
        xg = rng.normal(size=d_global)
        xu = rng.normal(size=d_user)
        z = xg @ wg + xu @ wu[u]
        y = float(rng.random() < 1 / (1 + np.exp(-z)))
        feats = [
            {"name": "g", "term": str(j), "value": float(xg[j])}
            for j in range(d_global)
        ] + [
            {"name": "u", "term": f"{u}_{j}", "value": float(xu[j])}
            for j in range(d_user)
        ]
        recs.append({
            "uid": str(i),
            "response": y,
            "offset": None,
            "weight": None,
            "features": feats,
            "metadataMap": {"userId": f"user{u}"},
        })
    write_container(str(path), RECORD_SCHEMA, recs)
    return n


@pytest.fixture(scope="module")
def game_data(tmp_path_factory):
    d = tmp_path_factory.mktemp("gamedata")
    n_train = _write_game_avro(d / "train.avro", seed=1)
    n_val = _write_game_avro(d / "val.avro", seed=2)
    return d, n_train, n_val


def test_feature_indexing_driver(game_data, tmp_path):
    d, _, _ = game_data
    out = tmp_path / "index"
    summary = feature_indexing_driver.run([
        "--data", str(d / "train.avro"),
        "--output-dir", str(out),
        "--feature-shard", "global:features",
        "--num-partitions", "2",
    ])
    # 5 global + 8*3 user features + intercept
    assert summary["features_per_shard"]["global"] == 5 + 24 + 1
    from photon_tpu.index.index_map import MmapIndexMap

    imap = MmapIndexMap(str(out / "global"))
    assert imap.get_index("g", "0") >= 0
    assert imap.intercept_index is not None


def test_training_and_scoring_drivers_end_to_end(game_data, tmp_path):
    d, n_train, n_val = game_data
    out = tmp_path / "train_out"
    summary = game_training_driver.run([
        "--train-data", str(d / "train.avro"),
        "--validation-data", str(d / "val.avro"),
        "--output-dir", str(out),
        "--task", "LOGISTIC_REGRESSION",
        "--feature-shard", "global:features",
        "--coordinate",
        "fixed:type=fixed,shard=global,reg=L2,max_iter=40,reg_weights=0.1|100",
        "--coordinate",
        "perUser:type=random,re_type=userId,shard=global,reg=L2,max_iter=40,reg_weights=1",
        "--evaluators", "AUC", "LOGISTIC_LOSS",
        "--sweeps", "2",
        "--output-mode", "ALL",
        "--devices", "1",
    ])
    assert summary["n_configs"] == 2
    assert summary["evaluation"]["AUC"] > 0.6
    assert os.path.exists(out / "best" / "game-metadata.json")
    assert os.path.exists(out / "models" / "0")
    assert os.path.exists(out / "index" / "global")
    assert os.path.exists(out / "photon.log")
    # the fit's own account of its seconds, from its span tree
    m = re.search(r"fit (\S+) s = (.*); host waited on the device (\S+)",
                  open(out / "photon.log").read())
    assert 0.0 < float(m.group(3)) < float(m.group(1))
    terms = dict(t.rsplit(" ", 1) for t in m.group(2).split(" + "))
    assert list(terms)[:1] == ["prepare"] and list(terms)[-1] == "descent"
    assert {"fixed", "perUser", "validate"} <= set(terms)
    assert sum(map(float, terms.values())) == pytest.approx(
        float(m.group(1)), abs=0.01 * len(terms))
    metrics = [json.loads(l) for l in open(out / "metrics.jsonl")]
    assert len(metrics) == 2 * 2 * 2  # configs x sweeps x coordinates
    assert all("AUC" in m for m in metrics)

    # scoring driver on validation data with the trained model
    score_out = tmp_path / "score_out"
    ssum = game_scoring_driver.run([
        "--data", str(d / "val.avro"),
        "--model-dir", str(out / "best"),
        "--output-dir", str(score_out),
        "--evaluators", "AUC",
    ])
    assert ssum["n_rows"] == n_val
    # scoring-path evaluation should match training-side validation closely
    assert ssum["evaluation"]["AUC"] == pytest.approx(
        summary["evaluation"]["AUC"], abs=1e-6
    )
    recs = read_records(str(score_out / "scores.avro"))
    assert len(recs) == n_val
    assert all(np.isfinite(r["predictionScore"]) for r in recs)


def test_training_driver_warm_start(game_data, tmp_path):
    d, _, _ = game_data
    out1 = tmp_path / "o1"
    args = [
        "--train-data", str(d / "train.avro"),
        "--validation-data", str(d / "val.avro"),
        "--task", "LOGISTIC_REGRESSION",
        "--feature-shard", "global:features",
        "--coordinate", "fixed:type=fixed,shard=global,reg=L2,max_iter=30,reg_weights=1",
        "--coordinate",
        "perUser:type=random,re_type=userId,shard=global,reg=L2,max_iter=30,reg_weights=1",
        "--evaluators", "AUC",
        "--devices", "1",
    ]
    s1 = game_training_driver.run(args + ["--output-dir", str(out1)])
    out2 = tmp_path / "o2"
    s2 = game_training_driver.run(
        args + ["--output-dir", str(out2),
                "--model-input-dir", str(out1 / "best")]
    )
    assert s2["evaluation"]["AUC"] >= s1["evaluation"]["AUC"] - 0.02


def test_prebuilt_index_dir_path(game_data, tmp_path):
    d, _, n_val = game_data
    idx = tmp_path / "idx"
    feature_indexing_driver.run([
        "--data", str(d / "train.avro"),
        "--output-dir", str(idx),
        "--feature-shard", "global:features",
    ])
    out = tmp_path / "to"
    s = game_training_driver.run([
        "--train-data", str(d / "train.avro"),
        "--output-dir", str(out),
        "--task", "LOGISTIC_REGRESSION",
        "--feature-shard", "global:features",
        "--coordinate", "fixed:type=fixed,shard=global,reg=L2,max_iter=20,reg_weights=1",
        "--index-dir", str(idx),
        "--devices", "1",
    ])
    assert s["evaluation"] is None
    assert os.path.exists(out / "index" / "global" / "index-meta.json")


class TestParamParsing:
    def test_coordinate_spec_full(self):
        c = parse_coordinate_spec(
            "re:type=random,re_type=userId,shard=u,active_bound=100,min_rows=2,"
            "optimizer=TRON,max_iter=7,tol=1e-3,reg=ELASTIC_NET,alpha=0.3,"
            "reg_weights=1|2|3,downsample=0.5,variance=SIMPLE"
        )
        assert c.cid == "re"
        assert c.data.re_type == "userId"
        assert c.data.active_bound == 100
        assert c.optimization.optimizer_type.name == "TRON"
        assert c.optimization.regularization.elastic_net_alpha == 0.3
        assert c.reg_weights == (1.0, 2.0, 3.0)
        assert c.optimization.variance_type.name == "SIMPLE"

    def test_coordinate_spec_errors(self):
        with pytest.raises(ValueError, match="type must be"):
            parse_coordinate_spec("x:shard=g")
        with pytest.raises(ValueError, match="unknown keys"):
            parse_coordinate_spec("x:type=fixed,bogus=1")
        with pytest.raises(ValueError, match="need re_type"):
            parse_coordinate_spec("x:type=random")
        with pytest.raises(ValueError, match="random-effect only"):
            parse_coordinate_spec("x:type=fixed,re_type=u")

    def test_feature_shard_spec(self):
        s = parse_feature_shard("myShard:bagA+bagB:no-intercept")
        assert s.shard == "myShard"
        assert s.feature_bags == ("bagA", "bagB")
        assert s.add_intercept is False
        assert parse_feature_shard("g").feature_bags == ("features",)


def test_best_config_not_first_and_models_subdir_scoring(game_data, tmp_path):
    """Regression: selecting a best config at index > 0 must not crash
    (identity selection, not array __eq__), and scoring from a
    ``models/<i>`` directory must find ``<out>/index`` without --index-dir."""
    d, _, n_val = game_data
    out = tmp_path / "out"
    # reg weight 100 first: the better (0.01) config lands at index 1.
    summary = game_training_driver.run([
        "--train-data", str(d / "train.avro"),
        "--validation-data", str(d / "val.avro"),
        "--output-dir", str(out),
        "--task", "LOGISTIC_REGRESSION",
        "--feature-shard", "global:features",
        "--coordinate",
        "fixed:type=fixed,shard=global,reg=L2,max_iter=30,reg_weights=100|0.01",
        "--evaluators", "AUC",
        "--output-mode", "ALL",
        "--devices", "1",
    ])
    assert summary["best_config_index"] == 1
    score_out = tmp_path / "score_out"
    ssum = game_scoring_driver.run([
        "--data", str(d / "val.avro"),
        "--model-dir", str(out / "models" / "1"),
        "--output-dir", str(score_out),
    ])
    assert ssum["n_rows"] == n_val


def test_scoring_unlabeled_data(game_data, tmp_path):
    """Scoring data with no response column (reference: response optional at
    scoring time)."""
    d, _, _ = game_data
    out = tmp_path / "out"
    game_training_driver.run([
        "--train-data", str(d / "train.avro"),
        "--output-dir", str(out),
        "--task", "LOGISTIC_REGRESSION",
        "--feature-shard", "global:features",
        "--coordinate", "fixed:type=fixed,shard=global,reg=L2,max_iter=20,reg_weights=1",
        "--devices", "1",
    ])
    schema = json.loads(json.dumps(RECORD_SCHEMA))
    schema["fields"][1] = {
        "name": "response", "type": ["null", "double"], "default": None
    }
    rng = np.random.default_rng(9)
    recs = [
        {
            "uid": str(i), "response": None, "offset": None, "weight": None,
            "features": [
                {"name": "g", "term": str(j), "value": float(rng.normal())}
                for j in range(5)
            ],
            "metadataMap": None,
        }
        for i in range(10)
    ]
    unl = tmp_path / "unlabeled.avro"
    write_container(str(unl), schema, recs)
    score_out = tmp_path / "score_out"
    ssum = game_scoring_driver.run([
        "--data", str(unl),
        "--model-dir", str(out / "best"),
        "--output-dir", str(score_out),
    ])
    assert ssum["n_rows"] == 10
    scored = read_records(str(score_out / "scores.avro"))
    assert all(r["label"] is None for r in scored)
    assert all(np.isfinite(r["predictionScore"]) for r in scored)


def test_custom_feature_bags_persist_to_scoring(game_data, tmp_path):
    """Shard configs (bags, intercept) saved in game-metadata.json are used
    by the scoring driver without re-passing --feature-bags."""
    d, _, _ = game_data
    # Rewrite the fixture with features under a custom bag name.
    schema = json.loads(json.dumps(RECORD_SCHEMA))
    schema["fields"][4] = dict(schema["fields"][4], name="myBag")
    recs = [
        {**r, "myBag": r["features"]}
        for r in read_records(str(d / "train.avro"))
    ]
    for r in recs:
        del r["features"]
    data = tmp_path / "custom.avro"
    write_container(str(data), schema, recs)
    out = tmp_path / "out"
    game_training_driver.run([
        "--train-data", str(data),
        "--output-dir", str(out),
        "--task", "LOGISTIC_REGRESSION",
        "--feature-shard", "global:myBag",
        "--coordinate", "fixed:type=fixed,shard=global,reg=L2,max_iter=20,reg_weights=1",
        "--devices", "1",
    ])
    meta = json.load(open(out / "best" / "game-metadata.json"))
    assert meta["feature_shards"]["global"]["feature_bags"] == ["myBag"]
    score_out = tmp_path / "score_out"
    ssum = game_scoring_driver.run([
        "--data", str(data),
        "--model-dir", str(out / "best"),
        "--output-dir", str(score_out),
        # note: no --feature-bags; metadata must supply "myBag"
    ])
    scored = read_records(str(score_out / "scores.avro"))
    # with the right bag, scores are non-trivial (not all just intercept)
    assert np.std([r["predictionScore"] for r in scored]) > 1e-3


def test_training_driver_auto_tuning(game_data, tmp_path):
    """--tuning gp replaces the grid sweep with Bayesian optimization of the
    reg weights (reference: GAME + hyperparameter auto-tuning config)."""
    d, _, _ = game_data
    out = tmp_path / "tuned"
    s = game_training_driver.run([
        "--train-data", str(d / "train.avro"),
        "--validation-data", str(d / "val.avro"),
        "--output-dir", str(out),
        "--task", "LOGISTIC_REGRESSION",
        "--feature-shard", "global:features",
        "--coordinate", "fixed:type=fixed,shard=global,reg=L2,max_iter=25",
        "--coordinate",
        "perUser:type=random,re_type=userId,shard=global,reg=L2,max_iter=25,reg_weights=1",
        "--evaluators", "AUC",
        "--tuning", "gp", "--tuning-iterations", "4",
        "--tuning-range", "fixed:0.001:100",
        "--devices", "1",
    ])
    assert s["n_configs"] == 1
    assert s["evaluation"]["AUC"] > 0.6
    assert 0.001 <= s["best_config"]["fixed"]["reg_weight"] <= 100


def test_training_driver_profile_and_debug_nans(game_data, tmp_path):
    """--profile-dir writes a jax.profiler trace (SURVEY.md §5.1) and
    --debug-nans turns on the NaN guard (§5.2) without disturbing results."""
    import glob

    d, _, _ = game_data
    out = tmp_path / "prof_out"
    prof = tmp_path / "trace"
    s = game_training_driver.run([
        "--train-data", str(d / "train.avro"),
        "--output-dir", str(out),
        "--task", "LOGISTIC_REGRESSION",
        "--feature-shard", "global:features",
        "--coordinate", "fixed:type=fixed,shard=global,reg=L2,max_iter=15,reg_weights=1",
        "--devices", "1",
        "--profile-dir", str(prof),
        "--debug-nans",
    ])
    try:
        assert s["n_configs"] == 1
        # the profiler writes plugins/profile/<ts>/*.trace.json.gz (or .xplane.pb)
        traces = glob.glob(str(prof / "**" / "*.*"), recursive=True)
        assert traces, f"no profiler trace written under {prof}"
    finally:
        import jax

        jax.config.update("jax_debug_nans", False)


def test_legacy_glm_driver_end_to_end(game_data, tmp_path):
    """The legacy single-GLM Driver: reg-weight grid + diagnostics + HTML
    report (SURVEY.md §2.3 legacy Driver; reference ⟦Driver.scala⟧ +
    ⟦diagnostics/⟧)."""
    from photon_tpu.cli import glm_training_driver

    d, _, n_val = game_data
    out = tmp_path / "glm_out"
    s = glm_training_driver.run([
        "--train-data", str(d / "train.avro"),
        "--validation-data", str(d / "val.avro"),
        "--output-dir", str(out),
        "--task", "LOGISTIC_REGRESSION",
        "--reg-weights", "0.01", "1.0", "100.0",
        "--max-iterations", "40",
        "--bootstrap-replicates", "6",
        "--hl-bins", "5",
    ])
    assert len(s["sweep"]) == 3
    assert s["selected_reg_weight"] in (0.01, 1.0, 100.0)
    assert s["evaluation"]["AUC"] > 0.55
    assert 0.0 <= s["hosmer_lemeshow_p"] <= 1.0
    report = open(s["report"]).read()
    assert "Hosmer" in report and "Bootstrap: 6" in report
    assert os.path.exists(out / "best" / "game-metadata.json")
    # the saved model scores through the standard scoring driver
    score_out = tmp_path / "glm_scores"
    ssum = game_scoring_driver.run([
        "--data", str(d / "val.avro"),
        "--model-dir", str(out / "best"),
        "--output-dir", str(score_out),
        "--evaluators", "AUC",
    ])
    assert ssum["n_rows"] == n_val
    assert ssum["evaluation"]["AUC"] == pytest.approx(
        s["evaluation"]["AUC"], abs=0.02
    )


def test_scoring_driver_chunked_matches_whole(game_data, tmp_path):
    """--chunk-rows streams features chunk-by-chunk; scores, score file, and
    evaluation must match the whole-dataset path exactly (SURVEY.md §3.6 at
    scale: the serve path never materializes all features)."""
    from photon_tpu import native

    if native.get_lib() is None:
        # Without the native decoder _score_chunked falls back to the very
        # path we compare against — the test would pass vacuously.
        pytest.skip("native decoder unavailable")
    d, _, n_val = game_data
    out = tmp_path / "train_out"
    game_training_driver.run([
        "--train-data", str(d / "train.avro"),
        "--output-dir", str(out),
        "--task", "LOGISTIC_REGRESSION",
        "--feature-shard", "global:features",
        "--coordinate",
        "fixed:type=fixed,shard=global,reg=L2,max_iter=20,reg_weights=1",
        "--coordinate",
        "perUser:type=random,re_type=userId,shard=global,reg=L2,max_iter=20,reg_weights=1",
        "--devices", "1",
    ])
    # Small container blocks so --chunk-rows actually yields several chunks
    # (chunk boundaries land on block boundaries).
    from photon_tpu.io.avro import read_container, write_container

    schema, it = read_container(str(d / "val.avro"))
    small = tmp_path / "val_small_blocks.avro"
    write_container(str(small), schema, list(it), block_records=16)

    # AUC:userId exercises the chunked grouped-evaluation path: group ids
    # are dictionary-encoded incrementally per chunk and must produce the
    # same grouped metric as the whole-dataset factorization.
    whole = game_scoring_driver.run([
        "--data", str(small),
        "--model-dir", str(out / "best"),
        "--output-dir", str(tmp_path / "s_whole"),
        "--evaluators", "AUC", "AUC:userId",
    ])
    chunked = game_scoring_driver.run([
        "--data", str(small),
        "--model-dir", str(out / "best"),
        "--output-dir", str(tmp_path / "s_chunk"),
        "--evaluators", "AUC", "AUC:userId",
        "--chunk-rows", "48",
    ])
    assert chunked["n_rows"] == whole["n_rows"] == n_val
    for metric in ("AUC", "AUC:userId"):
        assert chunked["evaluation"][metric] == pytest.approx(
            whole["evaluation"][metric], abs=1e-6
        )
    rw = read_records(str(tmp_path / "s_whole" / "scores.avro"))
    rc = read_records(str(tmp_path / "s_chunk" / "scores.avro"))
    assert [r["uid"] for r in rc] == [r["uid"] for r in rw]
    np.testing.assert_allclose(
        [r["predictionScore"] for r in rc],
        [r["predictionScore"] for r in rw],
        rtol=0, atol=1e-5,
    )
    assert [r["label"] for r in rc] == [r["label"] for r in rw]
    # The streaming path really ran, in several chunks (not the fallback).
    log = (tmp_path / "s_chunk" / "photon.log").read_text()
    assert "score (chunked)" in log
    assert log.count("scored ") >= 3


def test_tuning_driver_with_checkpoint_dir(game_data, tmp_path):
    """--tuning now composes with --checkpoint-dir (trial-level snapshots)."""
    d, _, _ = game_data
    out = tmp_path / "out"
    summary = game_training_driver.run([
        "--train-data", str(d / "train.avro"),
        "--validation-data", str(d / "val.avro"),
        "--output-dir", str(out),
        "--task", "LOGISTIC_REGRESSION",
        "--feature-shard", "global:features",
        "--coordinate",
        "fixed:type=fixed,shard=global,reg=L2,max_iter=10,reg_weights=1",
        "--evaluators", "AUC",
        "--tuning", "random", "--tuning-iterations", "2",
        "--tuning-range", "fixed:0.01:10",
        "--checkpoint-dir", str(tmp_path / "ck"),
        "--devices", "1",
    ])
    assert summary["n_configs"] == 1
    assert any(n.startswith("step-") for n in os.listdir(tmp_path / "ck"))


def test_feature_summary_flag(game_data, tmp_path):
    """--feature-summary writes per-shard FeatureSummarizationResultAvro."""
    d, n_train, _ = game_data
    out = tmp_path / "out"
    game_training_driver.run([
        "--train-data", str(d / "train.avro"),
        "--output-dir", str(out),
        "--task", "LOGISTIC_REGRESSION",
        "--feature-shard", "global:features",
        "--coordinate",
        "fixed:type=fixed,shard=global,reg=L2,max_iter=5,reg_weights=1",
        "--feature-summary",
        "--devices", "1",
    ])
    recs = read_records(str(out / "summary" / "global.avro"))
    assert len(recs) == 5 + 24 + 1  # global + user features + intercept
    by_name = {(r["featureName"], r["featureTerm"]): r for r in recs}
    # The intercept column is 1.0 in every row.
    from photon_tpu.index.index_map import INTERCEPT_NAME, INTERCEPT_TERM
    icpt = by_name[(INTERCEPT_NAME, INTERCEPT_TERM)]["metrics"]
    assert icpt["mean"] == pytest.approx(1.0)
    assert icpt["max"] == pytest.approx(1.0)


def test_ingest_workers_flag(game_data, tmp_path):
    """--ingest-workers decodes with worker processes; summary identical to
    the in-process read."""
    from photon_tpu import native

    if native.get_lib() is None:
        pytest.skip("native decoder unavailable")
    d, n_train, _ = game_data
    args = [
        "--train-data", str(d / "train.avro"), str(d / "val.avro"),
        "--task", "LOGISTIC_REGRESSION",
        "--feature-shard", "global:features",
        "--coordinate",
        "fixed:type=fixed,shard=global,reg=L2,max_iter=8,reg_weights=1",
        "--devices", "1",
    ]
    s1 = game_training_driver.run(
        args + ["--output-dir", str(tmp_path / "o1")])
    s2 = game_training_driver.run(
        args + ["--output-dir", str(tmp_path / "o2"), "--ingest-workers", "2"])
    from photon_tpu.io.model_io import load_game_model
    from photon_tpu.index.index_map import MmapIndexMap

    m1, _ = load_game_model(str(tmp_path / "o1" / "best"),
                            {"global": MmapIndexMap(str(tmp_path / "o1" / "index" / "global"))})
    m2, _ = load_game_model(str(tmp_path / "o2" / "best"),
                            {"global": MmapIndexMap(str(tmp_path / "o2" / "index" / "global"))})
    np.testing.assert_array_equal(
        np.asarray(m1["fixed"].model.coefficients.means),
        np.asarray(m2["fixed"].model.coefficients.means),
    )


def test_driver_coefficients_match_sklearn_golden(tmp_path):
    """Known-answer tier (SURVEY.md §4): a CLI-trained fixed-effect logistic
    model must match sklearn's LogisticRegression on the same data with the
    same L2 objective (C = 1/reg_weight, unpenalized intercept) — the e2e
    analog of the reference's precomputed-coefficient integration tests."""
    sklearn = pytest.importorskip("sklearn")
    from sklearn.linear_model import LogisticRegression

    from photon_tpu.index.index_map import MmapIndexMap
    from photon_tpu.io.model_io import load_game_model

    rng = np.random.default_rng(21)
    n, d = 600, 12
    x = rng.normal(size=(n, d))
    w_true = rng.normal(size=d)
    y = (rng.random(n) < 1 / (1 + np.exp(-(x @ w_true - 0.3)))).astype(float)
    recs = [
        {
            "uid": str(i), "response": float(y[i]), "offset": None,
            "weight": None,
            "features": [
                {"name": "f", "term": str(j), "value": float(x[i, j])}
                for j in range(d)
            ],
            "metadataMap": None,
        }
        for i in range(n)
    ]
    path = tmp_path / "golden.avro"
    write_container(str(path), RECORD_SCHEMA, recs)

    out = tmp_path / "out"
    game_training_driver.run([
        "--train-data", str(path),
        "--output-dir", str(out),
        "--task", "LOGISTIC_REGRESSION",
        "--feature-shard", "global:features",
        "--coordinate",
        "fixed:type=fixed,shard=global,reg=L2,max_iter=200,tol=1e-10,reg_weights=1",
        "--dtype", "float64",
        "--devices", "1",
    ])
    imap = MmapIndexMap(str(out / "index" / "global"))
    model, _ = load_game_model(str(out / "best"), {"global": imap},
                               dtype=np.float64)
    w = np.asarray(model["fixed"].model.coefficients.means)
    ours = np.array([w[imap.get_index("f", str(j))] for j in range(d)])
    our_icpt = w[imap.intercept_index]

    sk = LogisticRegression(C=1.0, fit_intercept=True, tol=1e-10, max_iter=5000)
    sk.fit(x, y)
    np.testing.assert_allclose(ours, sk.coef_[0], rtol=0, atol=2e-5)
    np.testing.assert_allclose(our_icpt, sk.intercept_[0], rtol=0, atol=2e-5)
