"""Online serving subsystem (photon_tpu/serving/ — docs/serving.md).

Coverage per ISSUE: registry load + hot-swap under concurrent requests,
LRU coefficient-store eviction + unseen-entity fallback parity with
``GameTransformer``, micro-batcher shape bucketing (no recompile after
warmup, asserted via the kernel's trace counter), and an end-to-end HTTP
round-trip on CPU with score parity against the batch scoring driver.
"""
import json
import http.client
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from photon_tpu.cli import game_scoring_driver, game_training_driver
from photon_tpu.estimators import (
    FixedEffectDataConfig,
    GameTransformer,
    RandomEffectDataConfig,
)
from photon_tpu.estimators.game_transformer import SCORE_KERNEL_STATS
from photon_tpu.index.index_map import MmapIndexMap
from photon_tpu.io.avro import read_records
from photon_tpu.io.data_reader import AvroDataReader, FeatureShardConfig
from photon_tpu.io.model_io import load_game_model
from photon_tpu.faults import FaultPlan, FaultSpec, active_plan
from photon_tpu.serving import (
    CoefficientStore,
    DeadlineExceeded,
    DeviceCoefficientCache,
    MicroBatcher,
    ModelRegistry,
    Overloaded,
    ScoringServer,
    ServingConfig,
)
from tests.test_drivers import _write_game_avro


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """Two trained model dirs (different reg weights) over one dataset —
    the swap test needs genuinely different coefficient sets."""
    d = tmp_path_factory.mktemp("servedata")
    _write_game_avro(d / "train.avro", seed=1, n_users=6, rows_per_user=16)
    n_val = _write_game_avro(d / "val.avro", seed=2, n_users=6,
                             rows_per_user=16)
    outs = []
    for name, reg in (("m1", "1"), ("m2", "100")):
        out = d / name
        game_training_driver.run([
            "--train-data", str(d / "train.avro"),
            "--output-dir", str(out),
            "--task", "LOGISTIC_REGRESSION",
            "--feature-shard", "global:features",
            "--coordinate",
            f"fixed:type=fixed,shard=global,reg=L2,max_iter=25,reg_weights={reg}",
            "--coordinate",
            f"perUser:type=random,re_type=userId,shard=global,reg=L2,"
            f"max_iter=25,reg_weights={reg}",
            "--devices", "1",
        ])
        outs.append(str(out / "best"))
    return d, outs, n_val


def _model_and_transformer(model_dir, index_dir):
    imap = MmapIndexMap(str(index_dir))
    model, _ = load_game_model(str(model_dir), {"global": imap})
    configs = {
        "fixed": FixedEffectDataConfig("global"),
        "perUser": RandomEffectDataConfig(
            re_type="userId", feature_shard="global"),
    }
    reader = AvroDataReader(
        {"global": imap},
        {"global": FeatureShardConfig(("features",), True)},
        id_tag_columns=["userId"],
    )
    transformer = GameTransformer(
        model, configs, intercept_indices={"global": imap.intercept_index}
    )
    return model, reader, transformer


def _payload(rec):
    return {
        "features": rec["features"],
        "entities": rec["metadataMap"],
        "uid": rec["uid"],
    }


def _post(host, port, path, payload):
    conn = http.client.HTTPConnection(host, port, timeout=30)
    conn.request("POST", path, body=json.dumps(payload).encode(),
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    body = json.loads(resp.read())
    conn.close()
    return resp.status, body


def _get(host, port, path):
    conn = http.client.HTTPConnection(host, port, timeout=30)
    conn.request("GET", path)
    resp = conn.getresponse()
    body = json.loads(resp.read())
    conn.close()
    return resp.status, body


# ---------------------------------------------------------------- stores


def test_coefficient_store_matches_model(trained, tmp_path):
    d, (m1, _), _ = trained
    model, _, _ = _model_and_transformer(m1, d / "m1" / "index" / "global")
    re_m = model["perUser"]
    store = CoefficientStore.from_model(re_m)
    assert store.n_entities == re_m.n_entities
    for key in re_m.entity_keys:
        gi, gv = re_m.coefficients_for(key)
        sc, sv = store.lookup(key)
        np.testing.assert_array_equal(np.asarray(sc), np.asarray(gi))
        np.testing.assert_allclose(np.asarray(sv), np.asarray(gv),
                                   rtol=0, atol=1e-7)
    assert store.lookup("ghost-entity") is None

    # mmap round-trip: identical lookups through np.load(mmap_mode="r")
    store.save(str(tmp_path / "store"))
    loaded = CoefficientStore.load(str(tmp_path / "store"))
    assert isinstance(loaded.cols, np.memmap) or loaded.cols.base is not None
    for key in re_m.entity_keys:
        a, b = store.lookup(key), loaded.lookup(str(key))
        np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
        np.testing.assert_allclose(np.asarray(a[1]), np.asarray(b[1]),
                                   rtol=0, atol=0)


def test_device_cache_lru_eviction_and_fallback(trained):
    d, (m1, _), _ = trained
    model, _, _ = _model_and_transformer(m1, d / "m1" / "index" / "global")
    store = CoefficientStore.from_model(model["perUser"])
    cache = DeviceCoefficientCache(store, capacity=2)
    keys = list(store.keys)[:3]
    s0 = cache.slot_for(keys[0])
    s1 = cache.slot_for(keys[1])
    assert cache.slot_for(keys[0]) == s0            # hit, refreshes LRU
    s2 = cache.slot_for(keys[2])                    # evicts keys[1] (LRU)
    assert s2 == s1
    assert cache.stats["evictions"] == 1
    assert cache.stats["hits"] == 1
    # staged rows carry exactly the store's coefficients
    proj, coef = cache.gather([cache.slot_for(keys[2])])
    sc, sv = store.lookup(keys[2])
    np.testing.assert_array_equal(np.asarray(proj[0])[: len(sc)], sc)
    np.testing.assert_allclose(np.asarray(coef[0])[: len(sv)], sv,
                               rtol=0, atol=0)
    # unseen entity and None → fallback zero row, never evicting anything
    fb = cache.slot_for("ghost")
    assert fb == cache.fallback_slot == cache.slot_for(None)
    proj, coef = cache.gather([fb])
    assert int(np.asarray(proj).max()) == store.global_dim  # all-ghost
    assert float(np.abs(np.asarray(coef)).max()) == 0.0
    # batch resolution pins in-batch slots: all 3 distinct keys in ONE
    # batch would need 3 slots with only 2 available → loud error, not
    # silent aliasing (the scorer floors capacity at max_batch).
    with pytest.raises(RuntimeError, match="distinct entities"):
        cache.slots_for(keys)


# ------------------------------------------------------- registry + scorer


def test_registry_scores_match_batch_transformer(trained):
    """Serving scorer parity with GameTransformer on every validation row,
    plus unseen-entity fallback = fixed-effect-only (zero model)."""
    d, (m1, _), _ = trained
    # cache_entities below max_batch exercises the capacity floor: the
    # effective capacity is max_batch (8), so all 6 users stay resident.
    config = ServingConfig(max_batch=8, cache_entities=2, max_row_nnz=32)
    registry = ModelRegistry(m1, config)
    scorer = registry.current.scorer

    _, reader, transformer = _model_and_transformer(
        m1, d / "m1" / "index" / "global")
    bundle = reader.read([str(d / "val.avro")], require_labels=False)
    ref = np.asarray(transformer.transform(bundle))
    ref_rows = np.asarray(transformer.transform_rows(bundle))
    # the shared-kernel row path is the same math as the bucketed path
    np.testing.assert_allclose(ref_rows, ref, rtol=0, atol=1e-5)

    recs = read_records(str(d / "val.avro"))
    rows = [scorer.parse_request(_payload(r)) for r in recs]
    got = scorer.score_rows(rows)
    np.testing.assert_allclose(got, ref, rtol=0, atol=1e-5)
    snap = scorer.cache_snapshot()["perUser"]
    assert snap["capacity"] == 8          # floored at max_batch
    assert snap["misses"] >= 1 and snap["hits"] > 0

    # unseen entity → fixed-effect-only: equals a request with no entity
    p = _payload(recs[0])
    p["entities"] = {"userId": "never-seen"}
    unseen = scorer.score_rows([scorer.parse_request(p)])[0]
    p["entities"] = {}
    no_entity = scorer.score_rows([scorer.parse_request(p)])[0]
    assert unseen == pytest.approx(no_entity, abs=1e-7)
    assert unseen != pytest.approx(float(got[0]), abs=1e-6)  # RE is real


def test_no_recompile_after_warmup(trained):
    """Micro-batch shape bucketing: after registry warmup, no batch size
    1..max_batch may trigger a kernel retrace (compile counter flat)."""
    d, (m1, _), _ = trained
    config = ServingConfig(max_batch=8, cache_entities=16, max_row_nnz=32)
    registry = ModelRegistry(m1, config)
    scorer = registry.current.scorer
    recs = read_records(str(d / "val.avro"))
    rows = [scorer.parse_request(_payload(r)) for r in recs]
    traces0 = SCORE_KERNEL_STATS["traces"]
    for size in (1, 2, 3, 5, 7, 8, len(rows)):  # odd sizes pad to buckets
        scorer.score_rows(rows[:size])
    assert SCORE_KERNEL_STATS["traces"] == traces0


def test_batcher_coalesces_and_recovers(trained):
    d, (m1, _), _ = trained
    registry = ModelRegistry(
        m1, ServingConfig(max_batch=8, cache_entities=16, max_row_nnz=32))
    version = registry.current
    recs = read_records(str(d / "val.avro"))[:8]
    rows = [version.scorer.parse_request(_payload(r)) for r in recs]
    ref = version.scorer.score_rows(rows)

    # start=False: queue everything first, so the first wake coalesces all
    batcher = MicroBatcher(max_batch=8, max_wait_ms=50.0, start=False)
    futures = [batcher.submit(version, row) for row in rows]
    batcher.start()
    got = [f.result(timeout=30) for f in futures]
    np.testing.assert_allclose(got, ref, rtol=0, atol=1e-6)
    assert batcher.stats["batches"] == 1
    assert batcher.stats["max_batch_rows"] == 8
    batcher.close()
    with pytest.raises(RuntimeError):
        batcher.submit(version, rows[0])


# ------------------------------------------------------------- end to end


def test_server_end_to_end_with_hot_swap(trained, tmp_path):
    """Concurrent single-row HTTP requests score with parity against the
    batch scoring driver; a mid-traffic hot-swap completes without
    dropping a single in-flight request and moves new traffic to v2."""
    d, (m1, m2), n_val = trained
    score_out = tmp_path / "batch_scores"
    game_scoring_driver.run([
        "--data", str(d / "val.avro"),
        "--model-dir", m1,
        "--output-dir", str(score_out),
    ])
    batch = {
        r["uid"]: r["predictionScore"]
        for r in read_records(str(score_out / "scores.avro"))
    }

    registry = ModelRegistry(
        m1, ServingConfig(max_batch=8, cache_entities=16, max_row_nnz=32))
    batcher = MicroBatcher(max_batch=8, max_wait_ms=2.0)
    server = ScoringServer(
        registry, batcher, port=0,
        metrics_path=str(tmp_path / "serving-metrics.jsonl"),
        metrics_interval_s=3600,
    )
    server.start()
    host, port = server.address
    try:
        recs = read_records(str(d / "val.avro"))

        def score_one(rec):
            status, body = _post(host, port, "/score", _payload(rec))
            assert status == 200, body
            return body

        with ThreadPoolExecutor(8) as ex:
            outs = list(ex.map(score_one, recs))
        assert len(outs) == n_val
        for o in outs:
            assert o["model_version"] == 1
            assert abs(o["score"] - batch[o["uid"]]) < 1e-4

        # ---- hot-swap under load: fire requests continuously while v2
        # loads + warms; every response must be a 200 from v1 or v2.
        stop = threading.Event()
        results, errors = [], []

        def hammer():
            i = 0
            while not stop.is_set():
                try:
                    status, body = _post(
                        host, port, "/score", _payload(recs[i % len(recs)]))
                    results.append((status, body.get("model_version")))
                except Exception as e:  # noqa: BLE001
                    errors.append(repr(e))
                i += 1

        threads = [threading.Thread(target=hammer) for _ in range(4)]
        for t in threads:
            t.start()
        time.sleep(0.2)
        status, body = _post(host, port, "/admin/swap", {"model_dir": m2})
        assert status == 200, body
        assert body["model_version"] == 2
        time.sleep(0.2)
        stop.set()
        for t in threads:
            t.join(timeout=30)
        assert not errors, errors
        assert results
        assert all(status == 200 for status, _ in results)
        versions = {v for _, v in results}
        assert 1 in versions      # traffic flowed during the swap
        status, body = _post(host, port, "/score", _payload(recs[0]))
        assert status == 200 and body["model_version"] == 2

        # v2 really is the other model: scores differ from v1's
        assert body["score"] != pytest.approx(batch[recs[0]["uid"]],
                                              abs=1e-6)
        status, health = _get(host, port, "/healthz")
        assert status == 200 and health["model_version"] == 2

        # metrics: latency quantiles + throughput + cache stats all live
        status, m = _get(host, port, "/metrics")
        assert status == 200
        assert m["requests"] == len(results) + n_val + 1
        assert m["latency"]["count"] == m["requests"]
        assert m["latency"]["p50_ms"] <= m["latency"]["p99_ms"]
        assert m["batcher"]["rows"] >= m["requests"]
        assert "perUser" in m["coefficient_caches"]

        # client errors are 400s, counted, and never kill the server
        status, body = _post(host, port, "/score", {"features": "nope"})
        assert status == 400
    finally:
        server.shutdown()
    # shutdown flushed a JSONL metrics snapshot through utils/logging
    lines = [
        json.loads(line)
        for line in open(tmp_path / "serving-metrics.jsonl")
    ]
    assert lines and lines[-1]["model_version"] == 2


def test_registry_warm_standby_swap_is_pointer_move(trained, tmp_path):
    """ISSUE 12 acceptance (hot-swap half): a prepared standby makes the
    registry swap a pointer move — ZERO scoring-kernel traces during the
    swap itself, ``swap_to_first_score_seconds`` stamped by the first
    served batch, standby readiness visible on /healthz, and
    POST /admin/standby drives the whole flow over HTTP."""
    from photon_tpu.obs.metrics import REGISTRY

    d, (m1, m2), _ = trained
    config = ServingConfig(max_batch=8, cache_entities=16, max_row_nnz=32)
    registry = ModelRegistry(m1, config)
    recs = read_records(str(d / "val.avro"))
    row = registry.current.scorer.parse_request(_payload(recs[0]))
    before = float(registry.current.scorer.score_rows([row])[0])
    assert registry.standby_snapshot() == {
        "ready": False, "model_dir": None, "prepared_at": None}

    registry.prepare_standby(m2)
    snap = registry.standby_snapshot()
    assert snap["ready"] and snap["model_dir"] == m2

    traces0 = SCORE_KERNEL_STATS["traces"]
    v = registry.swap(m2)
    # The pointer move compiled nothing — the standby was already warm.
    assert SCORE_KERNEL_STATS["traces"] == traces0
    assert registry.current is v and v.version == 2
    assert registry.standby_snapshot()["ready"] is False

    got = float(v.scorer.score_rows(
        [v.scorer.parse_request(_payload(recs[0]))])[0])
    assert got != pytest.approx(before, abs=1e-6)  # m2 really serves
    assert REGISTRY.gauge("swap_to_first_score_seconds").value() > 0
    assert SCORE_KERNEL_STATS["traces"] == traces0  # still zero retraces

    # A swap with NO standby (or a stale one) takes the build path as
    # before — standby is an optimization, never a correctness gate.
    registry.prepare_standby(m2)      # stale: names the OTHER dir
    v3 = registry.swap(m1)
    assert v3.version == 3 and registry.standby_snapshot()["ready"]

    # Re-push detection: the directory changing AFTER prepare_standby
    # must discard the warmed snapshot (build path, never a stale serve).
    import os as _os

    from photon_tpu.serving import registry as _reg_mod

    _os.utime(_os.path.join(m2, "game-metadata.json"))
    builds = []
    orig_build = _reg_mod._build_version

    def counting_build(*a, **kw):
        builds.append(a)
        return orig_build(*a, **kw)

    _reg_mod._build_version = counting_build
    try:
        v4 = registry.swap(m2)
    finally:
        _reg_mod._build_version = orig_build
    assert v4.version == 4 and builds, "stale standby must rebuild"
    assert registry.standby_snapshot()["ready"] is False

    # ---- over HTTP: /admin/standby prepares, /healthz reports, swap
    # publishes, and the recovery block carries the latency watermarks.
    batcher = MicroBatcher(max_batch=8, max_wait_ms=1.0)
    server = ScoringServer(ModelRegistry(m1, config), batcher, port=0)
    server.start()
    host, port = server.address
    try:
        status, body = _get(host, port, "/healthz")
        assert status == 200
        assert body["recovery"]["standby"] == {
            "ready": False, "model_dir": None, "prepared_at": None}
        status, body = _post(host, port, "/admin/standby",
                             {"model_dir": m2})
        assert status == 200 and body["status"] == "prepared"
        status, body = _get(host, port, "/healthz")
        assert body["recovery"]["standby"]["ready"] is True
        status, body = _post(host, port, "/admin/swap", {"model_dir": m2})
        assert status == 200 and body["model_version"] == 2
        status, body = _post(host, port, "/score", _payload(recs[0]))
        assert status == 200 and body["model_version"] == 2
        status, body = _get(host, port, "/healthz")
        assert body["recovery"]["swap_to_first_score_seconds"] > 0
        # missing model_dir is a client error, not a 500
        status, body = _post(host, port, "/admin/standby", {})
        assert status == 400
    finally:
        server.shutdown()


def test_serving_driver_build(trained, tmp_path):
    """The CLI driver builds, warms, and reports through run() (the
    serve_forever=False smoke entry used by deploy checks)."""
    from photon_tpu.cli import serving_driver

    _, (m1, _), _ = trained
    summary = serving_driver.run([
        "--model-dir", m1,
        "--port", "0",
        "--max-batch", "4",
        "--output-dir", str(tmp_path / "serve_out"),
    ], serve_forever=False)
    assert summary["model_version"] == 1
    assert summary["coordinates"] == ["fixed", "perUser"]
    assert (tmp_path / "serve_out" / "photon.log").exists()
    assert (tmp_path / "serve_out" / "serving-metrics.jsonl").exists()


# ----------------------------------------------- robustness (PR-2 hardening)


def test_batcher_sheds_beyond_queue_bound(trained):
    """Bounded admission: submits past max_queue raise Overloaded
    immediately (the server's 503 load-shed path) instead of growing the
    queue and every queued request's latency without bound."""
    d, (m1, _), _ = trained
    registry = ModelRegistry(
        m1, ServingConfig(max_batch=8, cache_entities=16, max_row_nnz=32))
    version = registry.current
    row = version.scorer.parse_request(
        _payload(read_records(str(d / "val.avro"))[0]))
    batcher = MicroBatcher(max_batch=8, max_queue=2, start=False)
    futs = [batcher.submit(version, row) for _ in range(2)]
    with pytest.raises(Overloaded):
        batcher.submit(version, row)
    assert batcher.stats["shed"] == 1
    batcher.start()  # the admitted requests still complete normally
    assert all(isinstance(f.result(timeout=30), float) for f in futs)
    assert batcher.snapshot()["queued"] == 0
    batcher.close()


def test_batcher_drops_expired_rows_before_kernel(trained):
    """Deadline propagation: a row whose deadline passed while queued is
    failed with DeadlineExceeded BEFORE scoring; live rows in the same
    round still score."""
    d, (m1, _), _ = trained
    registry = ModelRegistry(
        m1, ServingConfig(max_batch=8, cache_entities=16, max_row_nnz=32))
    version = registry.current
    row = version.scorer.parse_request(
        _payload(read_records(str(d / "val.avro"))[0]))
    batcher = MicroBatcher(max_batch=8, max_wait_ms=1.0, start=False)
    rows0 = batcher.stats["rows"]
    expired = batcher.submit(version, row, deadline=time.monotonic() - 0.01)
    live = batcher.submit(version, row, deadline=time.monotonic() + 30.0)
    batcher.start()
    assert isinstance(live.result(timeout=30), float)
    with pytest.raises(DeadlineExceeded):
        expired.result(timeout=30)
    assert batcher.stats["expired"] == 1
    assert batcher.stats["rows"] - rows0 == 1  # expired row never scored
    batcher.close()


def test_breaker_degrades_to_fixed_effect_only(trained):
    """Store outage behind the circuit breaker: rows needing a store
    lookup degrade to fixed-effect-only (score == entity-less request,
    flagged), cached entities still get full RE scores, and the breaker
    closes again after the cooldown probe."""
    d, (m1, _), _ = trained
    config = ServingConfig(
        max_batch=8, cache_entities=16, max_row_nnz=32,
        breaker_failures=3, breaker_cooldown_s=0.2,
    )
    scorer = ModelRegistry(m1, config).current.scorer
    rec = read_records(str(d / "val.avro"))[0]

    # Reference: entity-less request = pure fixed-effect score.
    p0 = _payload(rec)
    p0["entities"] = {}
    fixed_only = float(scorer.score_rows([scorer.parse_request(p0)])[0])
    # Cache the real entity BEFORE the outage (the resident hot set).
    p_cached = _payload(rec)
    cached_ref, cached_flags = scorer.score_rows_flagged(
        [scorer.parse_request(p_cached)])
    assert cached_flags[0] == ()

    p_ghost = _payload(rec)
    p_ghost["entities"] = {"userId": "chaos-ghost"}
    ghost_row = scorer.parse_request(p_ghost)
    outage = FaultPlan(seed=0, specs=[
        FaultSpec(site="serving.store_lookup", error="os"),
    ])
    with active_plan(outage):
        scores, flags = scorer.score_rows_flagged([ghost_row])
        # Request survives, degraded to the fixed-effect-only score.
        assert flags[0] == ("perUser",)
        assert float(scores[0]) == pytest.approx(fixed_only, abs=1e-6)
        for _ in range(4):  # push past breaker_failures
            scorer.score_rows_flagged([ghost_row])
        snap = scorer.cache_snapshot()["perUser"]
        assert snap["breaker"]["state"] == "open"
        assert snap["breaker"]["short_circuited"] >= 1
        assert snap["degraded"] >= 3
        # Degradation ladder: a CACHED entity still scores full RE even
        # with the breaker open (hits never touch the store).
        s, f = scorer.score_rows_flagged([scorer.parse_request(p_cached)])
        assert f[0] == () and float(s[0]) == pytest.approx(
            float(cached_ref[0]), abs=1e-7)
    # Outage over + cooldown elapsed: the half-open probe succeeds and
    # un-degrades traffic (unseen entity is a clean fallback again).
    time.sleep(0.25)
    s2, f2 = scorer.score_rows_flagged([ghost_row])
    assert f2[0] == ()
    assert scorer.cache_snapshot()["perUser"]["breaker"]["state"] == "closed"
    assert scorer.breaker_snapshot()["perUser"]["opens"] == 1


# ------------------------------------------------------------- chaos (HTTP)


@pytest.mark.chaos
@pytest.mark.slow
def test_chaos_server_outage_and_overload(trained, tmp_path):
    """ISSUE acceptance: under an injected coefficient-store outage (errors
    + latency spikes) and overload (tiny admission queue), EVERY request
    gets a non-hanging response — success, degraded, or 503 — and none is
    stuck past its deadline."""
    d, (m1, _), _ = trained
    timeout_s = 3.0
    config = ServingConfig(
        max_batch=4, max_wait_ms=1.0, cache_entities=16, max_row_nnz=32,
        max_queue=8, request_timeout_s=timeout_s,
        breaker_failures=3, breaker_cooldown_s=60.0,  # stays open once hit
        breaker_slow_call_s=0.05,
    )
    registry = ModelRegistry(m1, config)
    batcher = MicroBatcher(max_batch=4, max_wait_ms=1.0, max_queue=8)
    server = ScoringServer(registry, batcher, port=0,
                           request_timeout_s=timeout_s)
    server.start()
    host, port = server.address
    recs = read_records(str(d / "val.avro"))
    plan = FaultPlan(seed=2, specs=[
        FaultSpec(site="serving.store_lookup", error="os",
                  probability=0.5),
        FaultSpec(site="serving.store_lookup", delay_s=0.1,
                  probability=0.3),
    ])
    results, errors = [], []

    def one(i):
        p = _payload(recs[i % len(recs)])
        if i % 2:  # half the traffic needs a store lookup (unseen entity)
            p["entities"] = {"userId": f"chaos-{i}"}
        t0 = time.monotonic()
        try:
            status, body = _post(host, port, "/score", p)
            results.append((status, body, time.monotonic() - t0))
        except Exception as e:  # noqa: BLE001 - a hang/transport failure
            errors.append(repr(e))

    try:
        with active_plan(plan) as inj:
            with ThreadPoolExecutor(16) as ex:
                list(ex.map(one, range(80)))
        assert inj.fired("serving.store_lookup") >= 1  # the outage was real
        assert not errors, errors
        assert len(results) == 80                      # nothing hung
        statuses = {s for s, _, _ in results}
        assert statuses <= {200, 503}, statuses
        assert 200 in statuses
        # Bounded: no response took longer than the deadline + slack.
        worst = max(dt for _, _, dt in results)
        assert worst < timeout_s + 2.0, worst
        # The degradation ladder showed up: degraded 200s and/or sheds.
        degraded = [b for s, b, _ in results if s == 200 and b.get("degraded")]
        shed = [b for s, b, _ in results if s == 503]
        assert degraded or shed
        for b in degraded:
            assert b["degraded"] == ["perUser"]
        status, m = _get(host, port, "/metrics")
        assert status == 200
        assert m["breakers"]["perUser"]["opens"] >= 1
        assert m["shed"] + m["expired"] == len(shed)
        assert m["degraded"] == len(degraded)
        # Server is still healthy — shedding is not dying — and the open
        # store breaker is VISIBLE as a degradation reason, not hidden
        # behind a bare "ok" (docs/robustness.md §/healthz).
        status, health = _get(host, port, "/healthz")
        assert status == 200 and health["status"] in ("ok", "degraded")
        if m["breakers"]["perUser"]["state"] != "closed":
            assert health["status"] == "degraded"
            assert any(r.endswith("store:perUser")
                       for r in health["degraded"])
    finally:
        server.shutdown()


@pytest.mark.chaos
@pytest.mark.slow
def test_chaos_store_stall_expires_requests_not_hangs(trained):
    """A stalled store (big latency injection, breaker disabled) must turn
    into bounded 503s — queued rows expire inside the batcher before the
    kernel, waiters get Retry-After, nothing waits out a 30s default."""
    d, (m1, _), _ = trained
    timeout_s = 0.6
    config = ServingConfig(
        max_batch=2, max_wait_ms=1.0, cache_entities=16, max_row_nnz=32,
        request_timeout_s=timeout_s, breaker_failures=0,  # raw stall
    )
    registry = ModelRegistry(m1, config)
    batcher = MicroBatcher(max_batch=2, max_wait_ms=1.0)
    server = ScoringServer(registry, batcher, port=0,
                           request_timeout_s=timeout_s)
    server.start()
    host, port = server.address
    rec = read_records(str(d / "val.avro"))[0]
    stall = FaultPlan(seed=0, specs=[
        FaultSpec(site="serving.store_lookup", delay_s=0.5),
    ])
    results = []

    def one(i):
        p = _payload(rec)
        p["entities"] = {"userId": f"stall-{i}"}  # every row hits the store
        t0 = time.monotonic()
        status, body = _post(host, port, "/score", p)
        results.append((status, time.monotonic() - t0))

    try:
        with active_plan(stall):
            with ThreadPoolExecutor(6) as ex:
                list(ex.map(one, range(6)))
        assert len(results) == 6
        assert {s for s, _ in results} <= {200, 503}
        assert any(s == 503 for s, _ in results)   # some rows gave up
        assert max(dt for _, dt in results) < timeout_s + 2.5
        assert server.counters["expired"] >= 1
        assert batcher.stats["expired"] >= 1       # dropped pre-kernel
        # Stall over: the server recovered without a restart.
        status, body = _post(host, port, "/score", _payload(rec))
        assert status == 200
    finally:
        server.shutdown()


def test_healthz_reports_backend_degraded_and_restarts(trained):
    """ISSUE 10 satellite: /healthz carries backend identity, an explicit
    degraded-reason list, and restart/recovery counts — not just
    alive/dead (docs/robustness.md §/healthz)."""
    d, (m1, _), _ = trained
    registry = ModelRegistry(
        m1, ServingConfig(max_batch=4, max_wait_ms=1.0, cache_entities=16,
                          max_row_nnz=32, breaker_failures=2,
                          breaker_cooldown_s=60.0))
    batcher = MicroBatcher(max_batch=4, max_wait_ms=1.0)
    server = ScoringServer(registry, batcher, port=0)
    server.start()
    host, port = server.address
    try:
        status, health = _get(host, port, "/healthz")
        assert status == 200
        assert health["status"] == "ok"
        assert health["backend"] == "cpu"      # the live backend, honestly
        assert health["degraded"] == []
        assert isinstance(health["restarts"], dict)
        assert "total" in health["restarts"]
        # An OPEN kernel breaker surfaces as a degraded reason (still 200:
        # the server answers, just worse — the ladder's middle rung).
        kb = registry.current.scorer.kernel_breaker
        for _ in range(2):
            kb.record_failure()
        status, health = _get(host, port, "/healthz")
        assert status == 200
        assert health["status"] == "degraded"
        assert health["degraded"] == ["breaker_open:kernel"]
    finally:
        server.shutdown()


@pytest.mark.chaos
@pytest.mark.slow
def test_chaos_kernel_device_lost_recovers_through_breaker(trained):
    """ISSUE 10 tentpole (serving leg): a device_lost out of the scoring
    kernel re-initializes (executable-cache clear + re-warm) through the
    kernel circuit breaker and the request still answers 200 with the
    right score — one recovery, breaker closed again afterwards."""
    from photon_tpu.obs.metrics import REGISTRY

    d, (m1, _), _ = trained
    registry = ModelRegistry(
        m1, ServingConfig(max_batch=4, max_wait_ms=1.0, cache_entities=16,
                          max_row_nnz=32, breaker_failures=3))
    batcher = MicroBatcher(max_batch=4, max_wait_ms=1.0)
    server = ScoringServer(registry, batcher, port=0)
    server.start()
    host, port = server.address
    rec = read_records(str(d / "val.avro"))[0]
    plan = FaultPlan(seed=0, specs=[
        FaultSpec(site="serving.kernel", error="device_lost", count=1),
    ])
    before = REGISTRY.counter("serve_kernel_recoveries_total").value(
        cause="device_lost")
    try:
        with active_plan(plan) as inj:
            status, body = _post(host, port, "/score", _payload(rec))
        assert inj.fired("serving.kernel") == 1  # the loss really happened
        assert status == 200 and "score" in body  # ...and was absorbed
        assert REGISTRY.counter("serve_kernel_recoveries_total").value(
            cause="device_lost") == before + 1
        kb = registry.current.scorer.breaker_snapshot()["__kernel__"]
        assert kb["state"] == "closed" and kb["failures"] == 1
        # Healthy again end to end: scoring and health agree.
        status, body2 = _post(host, port, "/score", _payload(rec))
        assert status == 200
        assert body2["score"] == pytest.approx(body["score"], abs=1e-6)
        status, health = _get(host, port, "/healthz")
        assert status == 200 and health["status"] == "ok"
        assert health["restarts"]["total"] >= 1
    finally:
        server.shutdown()


@pytest.mark.chaos
@pytest.mark.slow
def test_chaos_kernel_repeated_errors_open_breaker_fast_fail(trained):
    """When the device stays dead, the kernel breaker opens and requests
    fast-fail 500 instead of burning a re-init per batch; /healthz says
    degraded."""
    d, (m1, _), _ = trained
    registry = ModelRegistry(
        m1, ServingConfig(max_batch=4, max_wait_ms=1.0, cache_entities=16,
                          max_row_nnz=32, breaker_failures=2,
                          breaker_cooldown_s=60.0))
    batcher = MicroBatcher(max_batch=4, max_wait_ms=1.0)
    server = ScoringServer(registry, batcher, port=0)
    server.start()
    host, port = server.address
    rec = read_records(str(d / "val.avro"))[0]
    plan = FaultPlan(seed=0, specs=[
        FaultSpec(site="serving.kernel", error="device_lost"),  # every call
    ])
    try:
        with active_plan(plan):
            statuses = [
                _post(host, port, "/score", _payload(rec))[0]
                for _ in range(4)
            ]
        assert all(s == 500 for s in statuses)  # failed, never hung
        kb = registry.current.scorer.breaker_snapshot()["__kernel__"]
        assert kb["state"] == "open"
        assert kb["short_circuited"] >= 1       # recovery was NOT retried
        status, health = _get(host, port, "/healthz")
        assert status == 200 and health["status"] == "degraded"
        assert "breaker_open:kernel" in health["degraded"]
    finally:
        server.shutdown()


@pytest.mark.chaos
@pytest.mark.slow
def test_chaos_kernel_oom_downshifts_max_batch_and_answers(trained):
    """ISSUE 13 serving leg: a device_oom out of the scoring kernel is
    absorbed by the bounded max-batch downshift — the request still
    answers 200 (the halved batch is an already-warmed padded shape, zero
    retraces), the cap is sticky, and the downshift is counted."""
    from photon_tpu.obs import retrace
    from photon_tpu.obs.metrics import REGISTRY
    from photon_tpu.runtime import memory_guard as mg

    mg.reset_state()
    d, (m1, m2), _ = trained
    registry = ModelRegistry(
        m1, ServingConfig(max_batch=4, max_wait_ms=1.0, cache_entities=16,
                          max_row_nnz=32, breaker_failures=3))
    batcher = MicroBatcher(max_batch=4, max_wait_ms=1.0)
    server = ScoringServer(registry, batcher, port=0)
    server.start()
    host, port = server.address
    rec = read_records(str(d / "val.avro"))[0]
    plan = FaultPlan(seed=0, specs=[
        FaultSpec(site="serving.kernel", error="device_oom", count=1),
    ])
    shifts_before = REGISTRY.counter("oom_downshifts_total").value(
        site="serving.kernel", cause="oom")
    retr_before = retrace.retraces_after_warmup(
        "additive_score_rows")
    try:
        with active_plan(plan) as inj:
            status, body = _post(host, port, "/score", _payload(rec))
        assert inj.fired("serving.kernel") == 1  # the OOM really happened
        assert status == 200 and "score" in body  # ...and was absorbed
        scorer = registry.current.scorer
        assert scorer._max_batch_cap == 2        # halved, sticky
        assert REGISTRY.counter("oom_downshifts_total").value(
            site="serving.kernel", cause="oom") == shifts_before + 1
        # Zero retraces: the downshifted shape was warmed at startup.
        assert retrace.retraces_after_warmup(
            "additive_score_rows") == retr_before
        # Closed-loop: the next request answers identically at the
        # degraded cap, and health reports no breaker trouble.
        status, body2 = _post(host, port, "/score", _payload(rec))
        assert status == 200
        assert body2["score"] == pytest.approx(body["score"], abs=1e-6)
        status, health = _get(host, port, "/healthz")
        assert status == 200
        # The cap is sticky for the RUN, not the scorer: a hot-swap's
        # fresh scorer starts at the proven cap instead of re-OOMing its
        # way back down (and re-burning the shared downshift budget).
        v2 = registry.swap(m2)
        assert v2.scorer._max_batch_cap == 2
    finally:
        server.shutdown()
        mg.reset_state()


@pytest.mark.chaos
@pytest.mark.slow
def test_chaos_memory_pressure_sheds_and_recovers(trained):
    """Pressure-aware load shedding end to end: over the critical
    watermark /score sheds 503 + Retry-After (never hangs) and /healthz
    reports degraded ["memory_pressure"]; when pressure drains, serving
    recovers closed-loop with no operator action."""
    from photon_tpu.obs.metrics import REGISTRY
    from photon_tpu.runtime import memory_guard as mg

    mg.reset_state()
    d, (m1, _), _ = trained
    registry = ModelRegistry(
        m1, ServingConfig(max_batch=4, max_wait_ms=1.0, cache_entities=16,
                          max_row_nnz=32))
    batcher = MicroBatcher(max_batch=4, max_wait_ms=1.0)
    server = ScoringServer(registry, batcher, port=0)
    server.start()
    host, port = server.address
    rec = read_records(str(d / "val.avro"))[0]
    level = {"in_use": 990.0}
    g = mg.guard()
    g.stats_fn = lambda: {"bytes_in_use": level["in_use"],
                          "bytes_limit": 1000.0,
                          "watermark": level["in_use"] / 1000.0}
    g.min_sample_interval_s = 0.0
    sheds_before = REGISTRY.counter("memory_pressure_sheds_total").value()
    try:
        status, body = _post(host, port, "/score", _payload(rec))
        assert status == 503 and body.get("shed") is True
        status, health = _get(host, port, "/healthz")
        assert status == 200 and health["status"] == "degraded"
        assert "memory_pressure" in health["degraded"]
        assert REGISTRY.counter(
            "memory_pressure_sheds_total").value() > sheds_before
        # Pressure drains -> full service resumes, health goes clean.
        level["in_use"] = 400.0
        status, body = _post(host, port, "/score", _payload(rec))
        assert status == 200 and "score" in body
        status, health = _get(host, port, "/healthz")
        assert status == 200 and health["status"] == "ok"
        assert health["degraded"] == []
    finally:
        server.shutdown()
        mg.reset_state()


@pytest.mark.chaos
@pytest.mark.slow
def test_chaos_batcher_crash_fails_fast_and_flags_healthz(trained):
    """Satellite: if the micro-batcher worker dies, queued futures fail
    immediately (not after the full request timeout) and /healthz flips to
    503 so an orchestrator can replace the process."""
    d, (m1, _), _ = trained
    registry = ModelRegistry(
        m1, ServingConfig(max_batch=8, cache_entities=16, max_row_nnz=32))
    batcher = MicroBatcher(max_batch=8, max_wait_ms=1.0)
    server = ScoringServer(registry, batcher, port=0, request_timeout_s=30.0)
    server.start()
    host, port = server.address
    rec = read_records(str(d / "val.avro"))[0]
    crash = FaultPlan(seed=0, specs=[
        FaultSpec(site="serving.batcher_batch", error="runtime", count=1),
    ])
    try:
        status, health = _get(host, port, "/healthz")
        assert status == 200
        with active_plan(crash):
            t0 = time.monotonic()
            status, body = _post(host, port, "/score", _payload(rec))
            took = time.monotonic() - t0
        assert status == 500
        assert "worker died" in body["error"]
        assert took < 10.0          # failed fast, not a 30s timeout wait
        assert not batcher.healthy
        status, health = _get(host, port, "/healthz")
        assert status == 503
        assert health["status"] == "unhealthy"
        # Later submits are refused instantly too.
        status, body = _post(host, port, "/score", _payload(rec))
        assert status == 500
    finally:
        server.shutdown()


# -------------------------------------- online deltas (PR-11 freshness)


def test_admin_patch_applies_delta_and_reports_freshness(trained):
    """ISSUE 11 satellite: ``POST /admin/patch`` applies changed-entity
    coefficient patches atomically (model version unmoved), the patched
    entity's served score changes, and /healthz + /metrics expose the
    freshness watermarks (patch_seq, last-patch ts, patched counts) — all
    without a trainer attached."""
    d, (m1, _), _ = trained
    registry = ModelRegistry(
        m1, ServingConfig(max_batch=8, cache_entities=16, max_row_nnz=32))
    batcher = MicroBatcher(max_batch=8, max_wait_ms=1.0)
    server = ScoringServer(registry, batcher, port=0)
    server.start()
    host, port = server.address
    rec = read_records(str(d / "val.avro"))[0]
    key = rec["metadataMap"]["userId"]
    store = registry.current.scorer._caches["perUser"].store
    cols, vals = store.lookup(key)
    try:
        # Baseline freshness: no patches yet, swap watermark present.
        status, health = _get(host, port, "/healthz")
        assert status == 200
        fr = health["freshness"]
        assert fr["patch_seq"] == 0 and fr["last_patch_ts"] is None
        assert fr["model_version"] == 1 and fr["last_swap_ts"] > 0

        status, before = _post(host, port, "/score", _payload(rec))
        assert status == 200

        status, body = _post(host, port, "/admin/patch", {
            "seq": 0, "event_horizon": 41,
            "patches": {"perUser": {str(key): {
                "cols": [int(c) for c in cols],
                "vals": [float(v) * 3.0 for v in vals],
            }}},
        })
        assert status == 200, body
        assert body["patch_seq"] == 1 and body["patched"] == 1
        assert body["model_version"] == 1          # patched, not swapped

        status, after = _post(host, port, "/score", _payload(rec))
        assert status == 200
        assert after["model_version"] == 1
        assert after["score"] != pytest.approx(before["score"], abs=1e-9)

        status, health = _get(host, port, "/healthz")
        fr = health["freshness"]
        assert fr["patch_seq"] == 1
        assert fr["last_patch_entities"] == 1
        assert fr["patched_entities_total"] == 1
        assert fr["last_event_horizon"] == 41
        assert fr["seconds_since_patch"] is not None
        status, m = _get(host, port, "/metrics")
        assert m["freshness"]["patch_seq"] == 1
        assert m["patches"] == 1
        assert m["coefficient_caches"]["perUser"]["store_patched"] == 1
        assert m["coefficient_caches"]["perUser"]["invalidations"] == 1

        # A malformed delta is a 400 and applies nothing. (Unsorted cols
        # normalize at the wire layer — EntityPatch sorts defensively —
        # so the invalid cases are out-of-range columns and unknown
        # coordinates.)
        status, body = _post(host, port, "/admin/patch", {
            "patches": {"perUser": {str(key): {
                "cols": [len(store.cols) + store.global_dim + 5],
                "vals": [1.0]}}},
        })
        assert status == 400 and "out of range" in body["error"]
        status, body = _post(host, port, "/admin/patch", {
            "patches": {"noSuchCoord": {"x": {"cols": [0],
                                              "vals": [1.0]}}},
        })
        assert status == 400 and "noSuchCoord" in body["error"]
        status, health = _get(host, port, "/healthz")
        assert health["freshness"]["patch_seq"] == 1   # unchanged
    finally:
        server.shutdown()


def _post_with_headers(host, port, path, payload, headers):
    conn = http.client.HTTPConnection(host, port, timeout=30)
    conn.request("POST", path, body=json.dumps(payload).encode(),
                 headers={"Content-Type": "application/json", **headers})
    resp = conn.getresponse()
    body = json.loads(resp.read())
    conn.close()
    return resp.status, body


def test_admin_patch_idempotency_key_dedupes_retries(trained):
    """ISSUE 17 satellite: the HTTP publisher is at-least-once — a retry
    that raced a success must NOT double-apply. A repeated
    X-Photon-Idempotency-Key replays the cached result (flagged
    ``duplicate``) without touching the store; a DIFFERENT key with the
    same trainer seq still applies (restarted trainer incarnations reuse
    low seqs for genuinely new deltas)."""
    d, (m1, _), _ = trained
    registry = ModelRegistry(
        m1, ServingConfig(max_batch=8, cache_entities=16, max_row_nnz=32))
    batcher = MicroBatcher(max_batch=8, max_wait_ms=1.0)
    server = ScoringServer(registry, batcher, port=0)
    server.start()
    host, port = server.address
    rec = read_records(str(d / "val.avro"))[0]
    key = rec["metadataMap"]["userId"]
    store = registry.current.scorer._caches["perUser"].store
    cols, vals = store.lookup(key)
    wire = {
        "seq": 0, "event_horizon": 7,
        "patches": {"perUser": {str(key): {
            "cols": [int(c) for c in cols],
            "vals": [float(v) * 2.0 for v in vals],
        }}},
    }
    try:
        status, first = _post_with_headers(
            host, port, "/admin/patch", wire,
            {"X-Photon-Idempotency-Key": "0:deadbeef"})
        assert status == 200 and first["patch_seq"] == 1
        assert "duplicate" not in first
        # The retry: same key, same payload — replayed, not re-applied.
        status, again = _post_with_headers(
            host, port, "/admin/patch", wire,
            {"X-Photon-Idempotency-Key": "0:deadbeef"})
        assert status == 200 and again["duplicate"] is True
        assert again["patch_seq"] == 1
        status, health = _get(host, port, "/healthz")
        assert health["freshness"]["patch_seq"] == 1        # once
        status, m = _get(host, port, "/metrics")
        assert m["patch_duplicates"] == 1
        assert m["patches"] == 1
        # Same trainer seq, different content digest: a NEW delta from a
        # restarted incarnation — must apply, not be swallowed.
        status, other = _post_with_headers(
            host, port, "/admin/patch", wire,
            {"X-Photon-Idempotency-Key": "0:0123456789abcdef"})
        assert status == 200 and "duplicate" not in other
        assert other["patch_seq"] == 2
        # No key at all keeps the legacy at-least-once behavior (the
        # canary resync path re-applies mainline deltas on purpose).
        status, nokey = _post(host, port, "/admin/patch", wire)
        assert status == 200 and nokey["patch_seq"] == 3
    finally:
        server.shutdown()


def test_admin_tune_reconfigures_batcher_live(trained):
    """ISSUE 17 satellite: the autoscaler lever — POST /admin/tune
    resizes the live micro-batcher (and its queue bound) without a
    restart; bad input is a 400 and changes nothing."""
    d, (m1, _), _ = trained
    registry = ModelRegistry(
        m1, ServingConfig(max_batch=8, cache_entities=16, max_row_nnz=32))
    batcher = MicroBatcher(max_batch=8, max_wait_ms=1.0)
    server = ScoringServer(registry, batcher, port=0)
    server.start()
    host, port = server.address
    rec = read_records(str(d / "val.avro"))[0]
    try:
        status, cfg = _post(host, port, "/admin/tune",
                            {"max_batch": 16, "max_queue": 64})
        assert status == 200
        assert cfg["max_batch"] == 16 and cfg["max_queue"] == 64
        assert batcher.max_batch == 16 and batcher.max_queue == 64
        # Scoring still works through the resized batcher.
        status, out = _post(host, port, "/score", _payload(rec))
        assert status == 200 and "score" in out
        status, m = _get(host, port, "/metrics")
        assert m["batcher"]["max_batch"] == 16
        assert m["tunes"] == 1
        for bad in ({}, {"max_batch": 0}, {"max_queue": -1}):
            status, body = _post(host, port, "/admin/tune", bad)
            assert status == 400, body
        assert batcher.max_batch == 16 and batcher.max_queue == 64
    finally:
        server.shutdown()


def test_admin_memory_shed_frees_pinned_cache(trained):
    """ISSUE 17 satellite lever: POST /admin/memory/shed runs the memory
    guard's pinned-cache sweep proactively (the controller fires it on a
    watermark ramp, BEFORE the OOM ladder would)."""
    d, (m1, _), _ = trained
    registry = ModelRegistry(
        m1, ServingConfig(max_batch=8, cache_entities=16, max_row_nnz=32))
    batcher = MicroBatcher(max_batch=8, max_wait_ms=1.0)
    server = ScoringServer(registry, batcher, port=0)
    server.start()
    host, port = server.address
    rec = read_records(str(d / "val.avro"))[0]
    try:
        # Warm the device cache so there is something sheddable.
        status, _ = _post(host, port, "/score", _payload(rec))
        assert status == 200
        status, out = _post(host, port, "/admin/memory/shed", {})
        assert status == 200
        assert out["freed_bytes"] >= 0
        status, m = _get(host, port, "/metrics")
        assert m["memory_sheds"] == 1
        # Scoring survives the shed (cold caches refill, scores unchanged).
        status, after = _post(host, port, "/score", _payload(rec))
        assert status == 200 and "score" in after
    finally:
        server.shutdown()


def test_registry_apply_delta_rejects_overwide_patch(trained):
    """A patch wider than the device-cache row width must refuse the WHOLE
    delta (atomicity) with actionable guidance, applying nothing."""
    d, (m1, _), _ = trained
    registry = ModelRegistry(
        m1, ServingConfig(max_batch=8, cache_entities=16, max_row_nnz=32))
    cache = registry.current.scorer._caches["perUser"]
    key = list(cache.store.keys)[0]
    wide = np.arange(cache.width + 1, dtype=np.int32)
    with pytest.raises(ValueError, match="cache width"):
        registry.apply_delta({"perUser": {
            key: (wide, np.ones(len(wide), np.float32)),
        }})
    assert cache.store.n_patched == 0
    assert registry.freshness_snapshot()["patch_seq"] == 0


def test_apply_delta_swap_standby_interleave(trained):
    """Concurrent apply_delta / swap / prepare_standby on ONE registry
    (the replica tailer's world: deltas stream in while a snapshot
    catch-up swaps underneath). The swap lock must serialize them — no
    torn version, no half-applied delta, and the registry must still
    score afterwards."""
    d, (m1, m2), _ = trained
    registry = ModelRegistry(
        m1, ServingConfig(max_batch=8, cache_entities=16, max_row_nnz=32))
    key = list(registry.current.scorer._caches["perUser"].store.keys)[0]
    errors = []
    applied = []
    barrier = threading.Barrier(3)

    def deltas():
        barrier.wait()
        for i in range(12):
            try:
                r = registry.apply_delta(
                    {"perUser": {str(key): (
                        np.array([0], np.int32),
                        np.array([0.01 * i], np.float32))}},
                    seq=i,
                )
                applied.append(r["patch_seq"])
            except Exception as e:  # noqa: BLE001 - collected for assert
                errors.append(f"apply: {e}")

    def swapper():
        barrier.wait()
        for target in (m2, m1, m2):
            try:
                registry.swap(target)
            except Exception as e:  # noqa: BLE001
                errors.append(f"swap: {e}")

    def standby():
        barrier.wait()
        for target in (m1, m2, m1):
            try:
                registry.prepare_standby(target)
            except Exception as e:  # noqa: BLE001
                errors.append(f"standby: {e}")

    threads = [threading.Thread(target=f)
               for f in (deltas, swapper, standby)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors
    assert len(applied) == 12
    # Patch seqs are strictly monotone: the swap lock serialized every
    # apply against every swap — no delta landed on a half-built version.
    assert applied == sorted(applied)
    v = registry.current
    assert v.model_dir == m2
    assert registry.freshness_snapshot()["model_version"] == v.version
    # The registry still scores: one more delta goes through cleanly.
    r = registry.apply_delta({"perUser": {str(key): (
        np.array([0], np.int32), np.array([0.5], np.float32))}})
    assert r["patched"] == 1


def test_sigterm_drain_finishes_inflight_and_flushes(trained, tmp_path):
    """The SIGTERM drain contract (docs/serving.md): in-flight requests
    finish with 200, post-drain arrivals shed with 503, and the final
    metrics snapshot lands in the JSONL history before the process would
    exit."""
    d, (m1, _), n_val = trained
    registry = ModelRegistry(
        m1, ServingConfig(max_batch=8, cache_entities=16, max_row_nnz=32))
    # A wide coalescing window keeps requests in flight long enough for
    # shutdown to overlap them deterministically.
    batcher = MicroBatcher(max_batch=8, max_wait_ms=400.0)
    metrics_path = tmp_path / "serving-metrics.jsonl"
    server = ScoringServer(
        registry, batcher, port=0,
        metrics_path=str(metrics_path), metrics_interval_s=3600,
    )
    server.start()
    host, port = server.address
    rec = next(iter(read_records(str(d / "val.avro"))))
    results = []

    def one():
        results.append(_post(host, port, "/score", _payload(rec)))

    threads = [threading.Thread(target=one) for _ in range(4)]
    for t in threads:
        t.start()
    deadline = time.monotonic() + 5
    while server._inflight < 4 and time.monotonic() < deadline:
        time.sleep(0.005)
    assert server._inflight == 4          # all admitted, none answered
    server.shutdown(drain_timeout_s=10.0)
    for t in threads:
        t.join(timeout=30)
    assert len(results) == 4
    assert all(status == 200 for status, _ in results), results
    assert server._inflight == 0
    # A straggler on a kept-alive connection after the drain began gets
    # the shed contract, not a hang against the closed batcher.
    server._draining = True
    handler = server.httpd.RequestHandlerClass
    class _Fake:
        headers = {"Content-Length": "0"}
        closed = False
        def _reply(self, code, payload, headers=()):
            self.code, self.payload, self.hdrs = code, payload, headers
    fake = _Fake()
    handler._score(fake)
    assert fake.code == 503 and fake.payload["shed"] is True
    assert ("Retry-After", "1") in tuple(fake.hdrs)
    # Step 4 of the contract: the final flush wrote the JSONL snapshot.
    with open(metrics_path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    assert rows, "shutdown must flush a final metrics snapshot"
    assert rows[-1]["requests"] >= 4


# --------------------------------------------- latency waterfall (ISSUE 18)


def _post_raw(host, port, path, payload, headers=()):
    """Like _post but returns the response headers too — the timing
    breakdown rides a header, not the JSON body."""
    conn = http.client.HTTPConnection(host, port, timeout=30)
    conn.request("POST", path, body=json.dumps(payload).encode(),
                 headers={"Content-Type": "application/json",
                          **dict(headers)})
    resp = conn.getresponse()
    body = json.loads(resp.read())
    hdrs = dict(resp.getheaders())
    conn.close()
    return resp.status, body, hdrs


def test_timing_header_returns_stage_waterfall(trained):
    """ISSUE 18: opt-in X-Photon-Timing returns a Server-Timing-style
    per-stage breakdown, the per-stage labeled histogram fills on every
    success, and the stages sum to (at most) the measured total."""
    d, (m1, _), _ = trained
    registry = ModelRegistry(
        m1, ServingConfig(max_batch=8, cache_entities=16, max_row_nnz=32))
    batcher = MicroBatcher(max_batch=8, max_wait_ms=2.0)
    server = ScoringServer(registry, batcher, port=0)
    server.start()
    host, port = server.address
    rec = next(iter(read_records(str(d / "val.avro"))))
    try:
        # Without the opt-in header, no timing header comes back.
        status, _, hdrs = _post_raw(host, port, "/score", _payload(rec))
        assert status == 200
        assert "X-Photon-Timing" not in hdrs
        status, _, hdrs = _post_raw(host, port, "/score", _payload(rec),
                                    headers={"X-Photon-Timing": "1"})
        assert status == 200
        breakdown = hdrs["X-Photon-Timing"]
        parts = {}
        for item in breakdown.split(","):
            name, _, dur = item.strip().partition(";dur=")
            parts[name] = float(dur)
        for stage in ("admission", "queue_wait", "batch_assembly",
                      "store_resolve", "kernel", "response", "total"):
            assert stage in parts, (stage, breakdown)
            assert parts[stage] >= 0.0
        staged = sum(v for k, v in parts.items() if k != "total")
        assert staged == pytest.approx(parts["total"], abs=0.5)
        # The same stages land in the registry's labeled histogram —
        # p95 queue-wait vs p95 kernel is one scrape.
        hist = server.metrics.histogram("serve_stage_latency_seconds")
        for stage in ("admission", "queue_wait", "batch_assembly",
                      "store_resolve", "kernel", "response"):
            assert hist.child(stage=stage).snapshot()["count"] >= 2, stage
        prom = server.metrics.to_prometheus()
        assert 'stage="queue_wait"' in prom and 'stage="kernel"' in prom
    finally:
        server.shutdown()


def test_tail_sampler_promotes_through_real_request_path(trained):
    """ISSUE 18 satellite: no promoted-span loss across the batcher
    thread boundary on the REAL server path — a promoted request's span
    set must include both the server-side request span and the
    queue-wait span completed on the batcher worker thread."""
    from photon_tpu.obs import (
        TailSampler,
        install_tail_sampler,
        tracing,
        uninstall_tail_sampler,
    )

    d, (m1, _), _ = trained
    registry = ModelRegistry(
        m1, ServingConfig(max_batch=8, cache_entities=16, max_row_nnz=32))
    batcher = MicroBatcher(max_batch=8, max_wait_ms=1.0)
    server = ScoringServer(registry, batcher, port=0)
    server.start()
    host, port = server.address
    recs = list(read_records(str(d / "val.avro")))[:8]
    sampler = TailSampler(min_history=4, quantile=0.5)
    install_tail_sampler(sampler)
    try:
        with tracing() as col:
            for i in range(30):
                status, _ = _post(host, port, "/score",
                                  _payload(recs[i % len(recs)]))
                assert status == 200
        # The handler closes its span after the response is on the wire:
        # the last request may still be in flight when the client has it.
        deadline = time.monotonic() + 10.0
        while (sampler.snapshot()["inflight"]
               and time.monotonic() < deadline):
            time.sleep(0.01)
        snap = sampler.snapshot()
        assert snap["inflight"] == 0
        assert snap["promoted"] >= 1
        # Not everything promotes: the boring half was discarded.
        assert snap["discarded"] >= 1
        marks = [e for e in col.events
                 if e["name"] == "photon.trace.tail_promoted"]
        assert len(marks) == snap["promoted"]
        tid = marks[-1]["args"]["trace_id"]

        def spans_of(tid):
            out = []
            for e in col.events:
                if e["ph"] != "X":
                    continue
                a = e.get("args", {})
                if a.get("trace_id") == tid or tid in (
                        a.get("trace_ids") or ()):
                    out.append(e["name"])
            return sorted(set(out))

        names = spans_of(tid)
        assert "serve.request" in names            # server thread
        assert "serve.queue_wait" in names         # batcher thread
        assert "serve.score" in names or "serve.batch" in names
    finally:
        uninstall_tail_sampler()
        server.shutdown()
