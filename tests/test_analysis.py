"""Observability analysis layer (photon_tpu/obs/analysis/ — ISSUE 6).

Coverage: timeline-analyzer edge cases (unclosed spans from crashed runs,
cross-thread spans, zero-length traces, negative durations, synthetic
fully-serialized vs fully-overlapped ingest/compute pairs), the
backend-aware bench regression gate (same-backend deltas scored,
cross-backend and unknown-backend pairs refused, wrapper-tail salvage,
schema errors), the declarative SLO watchdog (violations → counter +
trace instants, missing-metric semantics, dict-leaf summing, config
schema errors, heartbeat integration), and metrics-JSONL rotation.
"""
import json
import os
import threading
import time

import pytest

from photon_tpu.obs import MetricsRegistry, trace_span, tracing
from photon_tpu.obs.analysis import (
    ArtifactError,
    SloConfig,
    SloConfigError,
    SloWatchdog,
    analyze_events,
    analyze_trace,
    compare_artifacts,
    load_bench_details,
    metric_backend,
    normalize_backend,
    roofline_attribution,
)
from photon_tpu.utils import write_metrics_jsonl

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _x(name, cat, ts_us, dur_us, tid=1, pid=1, **args):
    return {"name": name, "cat": cat, "ph": "X", "ts": ts_us,
            "dur": dur_us, "pid": pid, "tid": tid, "args": args}


# ------------------------------------------------------------- timeline


def test_fully_serialized_ingest_compute_overlap_is_zero():
    report = analyze_events([
        _x("ingest.block", "ingest", 0, 1_000_000),
        _x("optim.fixed_solve", "optim", 1_000_000, 1_000_000),
    ])
    ov = report.overlap
    assert ov["compute_overlapped_fraction"] == 0.0
    assert ov["verdict"] == "serialized"
    # the two spans partition the wall exactly: shares sum to 1, no idle
    assert sum(report.owned_shares.values()) == pytest.approx(1.0)
    assert report.idle_seconds == pytest.approx(0.0)


def test_fully_overlapped_ingest_compute_overlap_is_one():
    # ingest on thread 1, compute on thread 2, same interval — pipelined
    report = analyze_events([
        _x("ingest.block", "ingest", 0, 1_000_000, tid=1),
        _x("optim.fixed_solve", "optim", 0, 1_000_000, tid=2),
    ])
    ov = report.overlap
    assert ov["compute_overlapped_fraction"] == pytest.approx(1.0)
    assert ov["ingest_hidden_fraction"] == pytest.approx(1.0)
    assert ov["verdict"] == "overlapped"
    # concurrent spans: attribution still partitions (one owner/instant)
    assert sum(report.owned_shares.values()) <= 1.0 + 1e-9


def test_partial_overlap_fraction():
    # compute [0, 2s]; ingest [1s, 3s] -> 1s of 2s compute overlapped
    report = analyze_events([
        _x("optim.re_bucket", "optim", 0, 2_000_000, tid=1),
        _x("ingest.chunk", "ingest", 1_000_000, 2_000_000, tid=2),
    ])
    assert report.overlap["compute_overlapped_fraction"] == pytest.approx(
        0.5)
    assert report.overlap["verdict"] == "partially-overlapped"


def test_unclosed_span_from_crashed_run_clamped_not_negative():
    report = analyze_events([
        {"name": "descent.sweep", "cat": "descent", "ph": "B",
         "ts": 100, "pid": 1, "tid": 1},
        _x("optim.fixed_solve", "optim", 200, 500),
        # no E event: the run crashed mid-sweep
    ])
    assert report.unclosed_spans == 1
    assert any("unclosed" in w for w in report.warnings)
    assert all(s >= 0 for s in report.owned.values())
    assert report.idle_seconds >= 0


def test_negative_duration_clamped_and_warned():
    report = analyze_events([_x("bad", "optim", 100, -50)])
    assert any("negative dur" in w for w in report.warnings)
    assert report.wall_seconds == 0.0


def test_zero_length_trace_is_empty_report_not_crash():
    report = analyze_events([])
    assert report.wall_seconds == 0.0
    assert report.n_spans == 0
    assert report.critical_path() == []
    assert report.overlap["verdict"] == "empty"
    assert "0.00 ms" in report.format_text()


def test_cross_thread_queue_wait_breakdown():
    # queue-wait spans start on the handler thread's clock but are emitted
    # with the worker's tid (the micro-batcher boundary): the analyzer
    # must aggregate them and attribute wall like any other interval.
    report = analyze_events([
        _x("serve.request", "serving", 0, 2_000, tid=1, trace_id="t1"),
        _x("serve.queue_wait", "serving", 500, 800, tid=9, trace_id="t1"),
        _x("serve.batch", "serving", 1_300, 600, tid=9),
    ])
    qw = report.queue_wait["serve.queue_wait"]
    assert qw["count"] == 1
    assert qw["mean_ms"] == pytest.approx(0.8)
    # innermost-owner attribution: queue_wait (deeper by start order on
    # the sweep) owns its interval even while serve.request is open
    assert ("serving", "serve.queue_wait") in report.owned


def test_critical_path_names_the_biggest_owner():
    report = analyze_events([
        _x("descent.sweep", "descent", 0, 10_000, tid=1),
        _x("optim.fixed_solve", "optim", 1_000, 8_000, tid=1),
    ])
    top = report.bottleneck()
    # the nested solve owns 8ms of the 10ms wall; the sweep only its
    # exclusive 2ms
    assert (top["cat"], top["name"]) == ("optim", "optim.fixed_solve")
    assert top["share"] == pytest.approx(0.8)


def test_analyze_trace_roundtrip_from_real_collector(tmp_path):
    path = str(tmp_path / "trace.json")
    with tracing(path):
        with trace_span("ingest.block", cat="ingest"):
            time.sleep(0.01)
        with trace_span("optim.fixed_solve", cat="optim"):
            time.sleep(0.01)
    report = analyze_trace(path)
    assert report.n_spans == 2
    assert report.overlap["compute_overlapped_fraction"] is not None
    doc = report.to_dict()
    assert doc["schema"] == "photon-timeline/1"
    json.dumps(doc)  # must be JSON-serializable


def test_analyze_trace_schema_error(tmp_path):
    from photon_tpu.obs.analysis import TraceParseError

    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    with pytest.raises(TraceParseError):
        analyze_trace(str(bad))


def test_roofline_attribution_joins_bench_details():
    report = analyze_events([
        _x("ingest.block", "ingest", 0, 3_000_000, tid=1),
        _x("optim.fixed_solve", "optim", 3_000_000, 1_000_000, tid=1),
    ])
    attr = roofline_attribution(report, {
        "roofline": {"fraction_of_roofline": 0.151, "backend": "cpu"},
    })
    assert attr["fraction_of_roofline"] == 0.151
    assert attr["bottleneck"] == "ingest:ingest.block"
    assert "serialized" in attr["note"] or "overlap" in attr["note"]


# --------------------------------------------------------- bench compare


def _details(backend=None, stage_backends=None, **metrics):
    d = dict(metrics)
    if backend:
        d["backend"] = backend
    if stage_backends:
        d["stage_backends"] = stage_backends
    return d


def _write(tmp_path, name, details):
    p = tmp_path / name
    p.write_text(json.dumps(details))
    return str(p)


def test_same_backend_regression_and_noise_threshold(tmp_path):
    old = _write(tmp_path, "a.json", _details(
        backend="cpu", ingest_rows_per_sec=1000.0, serve_p50_ms=10.0))
    new = _write(tmp_path, "b.json", _details(
        backend="cpu", ingest_rows_per_sec=500.0, serve_p50_ms=10.5))
    doc = compare_artifacts([old, new])
    m = doc["pairs"][0]["metrics"]
    assert m["ingest_rows_per_sec"]["verdict"] == "regressed"  # -50%
    assert m["serve_p50_ms"]["verdict"] == "unchanged"  # +5% < threshold
    assert doc["overall"] == "regressed"


def test_zero_old_value_scores_without_infinite_delta(tmp_path):
    # old serve_shed == 0, new > 0: scored on the raw difference, with a
    # null delta_pct — float('inf') would make the --json verdict invalid
    # JSON for strict parsers.
    old = _write(tmp_path, "a.json", _details(backend="cpu", serve_shed=0))
    new = _write(tmp_path, "b.json", _details(backend="cpu", serve_shed=5))
    doc = compare_artifacts([old, new])
    d = doc["pairs"][0]["metrics"]["serve_shed"]
    assert d["verdict"] == "regressed"
    assert d.get("delta_pct") is None
    json.loads(json.dumps(doc))  # strictly round-trippable
    from photon_tpu.obs.analysis import format_verdict

    assert "serve_shed" in format_verdict(doc)
    # both zero: unchanged
    doc0 = compare_artifacts([old, old])
    assert doc0["pairs"][0]["metrics"]["serve_shed"]["verdict"] == "unchanged"


def test_newest_artifacts_orders_by_content_not_mtime(tmp_path):
    # a fresh git clone gives every artifact the same mtime: recency must
    # come from written_at / round number, deterministically
    a = _write(tmp_path, "BENCH_r01.json", _details(
        backend="cpu", x_per_sec=1.0, written_at="2026-01-01T00:00:00Z"))
    b = _write(tmp_path, "BENCH_r02.json", _details(
        backend="cpu", x_per_sec=2.0, written_at="2026-02-01T00:00:00Z"))
    c = _write(tmp_path, "BENCH_r03.json", _details(
        backend="cpu", x_per_sec=3.0))  # predates written_at: round key
    now = time.time()
    for p in (a, b, c):
        os.utime(p, (now, now))  # identical mtimes, like a checkout
    from photon_tpu.obs.analysis import newest_artifacts

    got = newest_artifacts(str(tmp_path), k=2)
    assert [os.path.basename(p) for p in got] == [
        "BENCH_r01.json", "BENCH_r02.json"]
    assert newest_artifacts(str(tmp_path), k=2) == got  # deterministic


def test_same_backend_improvement(tmp_path):
    old = _write(tmp_path, "a.json", _details(
        backend="cpu", game_samples_per_sec=100.0))
    new = _write(tmp_path, "b.json", _details(
        backend="cpu", game_samples_per_sec=200.0))
    doc = compare_artifacts([old, new])
    assert doc["pairs"][0]["metrics"]["game_samples_per_sec"][
        "verdict"] == "improved"
    assert doc["overall"] == "ok"


def test_cross_backend_pair_marked_incomparable_not_regressed(tmp_path):
    old = _write(tmp_path, "a.json", _details(
        backend="tpu", game_samples_per_sec=10_000.0))
    new = _write(tmp_path, "b.json", _details(
        backend="cpu-fallback", game_samples_per_sec=100.0))
    doc = compare_artifacts([old, new])
    delta = doc["pairs"][0]["metrics"]["game_samples_per_sec"]
    assert delta["verdict"] == "incomparable"
    assert (delta["backend_old"], delta["backend_new"]) == ("tpu", "cpu")
    assert doc["overall"] == "incomparable"


def test_unknown_backend_never_compares_even_to_itself(tmp_path):
    old = _write(tmp_path, "a.json", _details(game_samples_per_sec=1.0))
    new = _write(tmp_path, "b.json", _details(game_samples_per_sec=2.0))
    doc = compare_artifacts([old, new])
    assert doc["pairs"][0]["metrics"]["game_samples_per_sec"][
        "verdict"] == "incomparable"


def test_stage_backends_partition_one_artifact(tmp_path):
    # one artifact, two stages on different backends: each metric carries
    # its own stage's backend
    details = _details(
        backend="tpu",
        stage_backends={"ingest": "cpu", "game": "tpu"},
        ingest_rows_per_sec=1.0, game_samples_per_sec=2.0)
    assert metric_backend(details, "ingest_rows_per_sec") == "cpu"
    assert metric_backend(details, "game_samples_per_sec") == "tpu"


def test_checked_in_artifacts_match_roadmap_caveat(tmp_path):
    """The acceptance demo on the repo's own history: r03 vs r05 were both
    CPU rounds (deltas score); an artifact in the driver's wrapper shape
    that carries no backend stamp — as the early accelerator rounds wrote
    them — refuses every pair."""
    r02 = _write(tmp_path, "BENCH_r02.json", {
        "n": 2, "cmd": "python bench.py", "rc": 0, "tail": "",
        "parsed": {
            "metric": "fixed_effect_logistic_lbfgs_samples_per_sec",
            "value": 3671869.6, "unit": "samples/sec",
            "extra_metrics": {
                "fixed_effect_lbfgs": {"seconds": 0.857, "iterations": 6,
                                       "samples_per_sec": 3671869.587},
                "owlqn_linear_l1_samples_per_sec": 3471765.4,
                "tron_poisson_l2_samples_per_sec": 1195977.8,
                "game_sweep_seconds": 0.19,
                "game_samples_per_sec": 172821.8,
            }}})
    r05 = os.path.join(REPO, "BENCH_r05.json")
    r03 = os.path.join(REPO, "BENCH_r03.json")
    same = compare_artifacts([r03, r05])
    scored = [d for d in same["pairs"][0]["metrics"].values()
              if d["verdict"] in ("improved", "regressed", "unchanged")]
    assert scored, "same-backend pair must score some deltas"
    cross = compare_artifacts([r02, r05])
    assert cross["overall"] == "incomparable"
    assert all(
        d["verdict"] in ("incomparable", "missing")
        for d in cross["pairs"][0]["metrics"].values())


def test_wrapper_tail_salvage(tmp_path):
    inner = {"metric": "m", "value": 1.0, "extra_metrics": {
        "backend": "cpu", "game_samples_per_sec": 5.0}}
    wrapper = {"n": 9, "cmd": "x", "rc": 0, "parsed": None,
               # tail truncated mid-line: only the back half survives
               "tail": json.dumps(inner)[20:]}
    # unsalvageable fragment -> ArtifactError
    p = tmp_path / "BENCH_r09.json"
    p.write_text(json.dumps(wrapper))
    if not wrapper["tail"].endswith("}}"):
        with pytest.raises(ArtifactError):
            load_bench_details(str(p))
    # the repo's own truncated r05 wrapper salvages into real metrics
    d = load_bench_details(os.path.join(REPO, "BENCH_r05.json"))
    assert d.get("stage_backends", {}).get("game_scale") == "cpu"
    assert "game_scale_total_seconds" in d


def test_schema_error_on_unreadable_artifact(tmp_path):
    bad = tmp_path / "BENCH_bad.json"
    bad.write_text("not json at all")
    with pytest.raises(ArtifactError):
        load_bench_details(str(bad))


def test_normalize_backend_variants():
    assert normalize_backend("cpu-fallback") == "cpu"
    assert normalize_backend("host-cpu (by design: this IS the baseline)") \
        == "cpu"
    assert normalize_backend("tpu") == "tpu"
    assert normalize_backend(None) == "unknown"
    assert normalize_backend("") == "unknown"


def test_provenance_mismatch_noted_not_fatal(tmp_path):
    old = _write(tmp_path, "a.json", _details(
        backend="cpu", game_samples_per_sec=1.0,
        provenance={"jax_version": "0.4.1", "hostname": "a"}))
    new = _write(tmp_path, "b.json", _details(
        backend="cpu", game_samples_per_sec=1.01,
        provenance={"jax_version": "0.5.0", "hostname": "b"}))
    doc = compare_artifacts([old, new])
    notes = doc["pairs"][0]["notes"]
    assert any("jax version" in n for n in notes)
    assert any("host" in n for n in notes)
    assert doc["pairs"][0]["metrics"]["game_samples_per_sec"][
        "verdict"] == "unchanged"


# ----------------------------------------------------------------- SLO


def _slo(rules):
    return SloConfig.from_dict({"slos": rules})


def test_slo_violation_bumps_counter_and_emits_instant(tmp_path):
    reg = MetricsRegistry()
    cfg = _slo([
        {"name": "p99", "metric": "latency.p99_ms", "op": "<=",
         "threshold": 5.0},
        {"name": "floor", "metric": "rows_per_sec", "op": ">=",
         "threshold": 100.0},
    ])
    path = str(tmp_path / "t.json")
    with tracing(path):
        report = cfg.evaluate(
            {"latency": {"p99_ms": 50.0}, "rows_per_sec": 500.0},
            where="test", registry=reg)
    assert not report.ok
    assert [r.name for r in report.violations] == ["p99"]
    assert reg.counter("slo_violations_total").value(slo="p99") == 1
    assert reg.counter("slo_violations_total").value(slo="floor") == 0
    events = json.load(open(path))["traceEvents"]
    viol = [e for e in events if e["name"] == "slo.violation"]
    passed = [e for e in events if e["name"] == "slo.pass"]
    assert len(viol) == 1 and viol[0]["args"]["slo"] == "p99"
    assert viol[0]["args"]["where"] == "test"
    assert len(passed) == 1 and passed[0]["args"]["slo"] == "floor"


def test_slo_missing_metric_skip_vs_violate():
    reg = MetricsRegistry()
    cfg = _slo([
        {"name": "absent_skip", "metric": "no.such", "op": "<=",
         "threshold": 1},
        {"name": "absent_hard", "metric": "no.such", "op": "<=",
         "threshold": 1, "on_missing": "violate"},
    ])
    report = cfg.evaluate({}, registry=reg)
    by_name = {r.name: r.status for r in report.results}
    assert by_name == {"absent_skip": "skipped", "absent_hard": "violation"}
    assert report.checked == 1


def test_slo_dict_leaf_sums_labeled_counters():
    # retraces-after-warmup == 0 across kernels: the per-kernel dict sums
    cfg = _slo([{"name": "no_retraces",
                 "metric": "kernel_retraces_after_warmup_total",
                 "op": "==", "threshold": 0}])
    reg = MetricsRegistry()
    ok = cfg.evaluate(
        {"kernel_retraces_after_warmup_total": {"a": 0, "b": 0}},
        registry=reg)
    assert ok.ok
    bad = cfg.evaluate(
        {"kernel_retraces_after_warmup_total": {"a": 0, "b": 2}},
        registry=reg)
    assert [r.name for r in bad.violations] == ["no_retraces"]
    assert bad.violations[0].value == 2.0


def test_slo_config_schema_errors(tmp_path):
    with pytest.raises(SloConfigError):
        SloConfig.from_dict({"rules": []})  # wrong top-level key
    with pytest.raises(SloConfigError):
        _slo([{"name": "x", "metric": "m", "op": "~", "threshold": 1}])
    with pytest.raises(SloConfigError):
        _slo([{"name": "x", "metric": "m", "op": "<="}])  # no threshold
    with pytest.raises(SloConfigError):
        _slo([{"name": "x", "metric": "m", "op": "<=", "threshold": "NaNo"}])
    with pytest.raises(SloConfigError):
        _slo([{"name": "d", "metric": "m", "op": "<=", "threshold": 1},
              {"name": "d", "metric": "m", "op": "<=", "threshold": 2}])
    bad = tmp_path / "slo.json"
    bad.write_text("{")
    with pytest.raises(SloConfigError):
        SloConfig.from_file(str(bad))
    good = tmp_path / "good.json"
    good.write_text(json.dumps({"slos": [
        {"name": "x", "metric": "m", "op": "<=", "threshold": 1}]}))
    assert len(SloConfig.from_file(str(good)).rules) == 1


def test_slo_watchdog_rides_heartbeat(tmp_path):
    from photon_tpu.supervisor import Heartbeat

    reg = MetricsRegistry()
    beats = {"n": 0}

    def snapshot():
        beats["n"] += 1
        return {"depth": 7.0}

    wd = SloWatchdog(
        _slo([{"name": "depth", "metric": "depth", "op": "<=",
               "threshold": 1}]),
        snapshot_fn=snapshot, registry=reg, min_interval_s=0.0)
    hb = Heartbeat(str(tmp_path), process_id=0, interval_seconds=0.05,
                   slo_watchdog=wd)
    with hb:
        deadline = time.monotonic() + 5.0
        while beats["n"] == 0 and time.monotonic() < deadline:
            time.sleep(0.02)
    assert beats["n"] >= 1
    assert reg.counter("slo_violations_total").value(slo="depth") >= 1
    assert wd.last_report is not None and not wd.last_report.ok


def test_slo_watchdog_rate_limited_and_probe_safe():
    calls = {"n": 0}

    def snapshot():
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("sick probe")
        return {"x": 0.0}

    wd = SloWatchdog(
        _slo([{"name": "x", "metric": "x", "op": "<=", "threshold": 1}]),
        snapshot_fn=snapshot, registry=MetricsRegistry(),
        min_interval_s=3600.0)
    assert wd.check() is None          # probe raised; swallowed
    assert wd.check() is None          # rate limited after the attempt
    assert calls["n"] == 1


def test_serving_server_evaluates_slos_on_flush():
    """check_slos() on a real ScoringServer snapshot: a deliberately
    failing threshold shows up in the snapshot and the global counter."""
    pytest.importorskip("jax")
    from photon_tpu.obs.metrics import REGISTRY

    class _Srv:  # only what check_slos touches
        logger = None
        slo_config = _slo([
            {"name": "impossible_uptime", "metric": "uptime", "op": "<=",
             "threshold": -1.0}])
        _slo_last = None

        def metrics_snapshot(self):
            return {"uptime": 5.0}

    from photon_tpu.serving.server import ScoringServer

    before = REGISTRY.counter("slo_violations_total").value(
        slo="impossible_uptime")
    out = ScoringServer.check_slos(_Srv())
    assert out is not None and not out["ok"]
    assert out["violations"] == ["impossible_uptime"]
    assert REGISTRY.counter("slo_violations_total").value(
        slo="impossible_uptime") == before + 1


def test_slo_only_server_starts_periodic_flush_loop():
    """A server with slo_config but NO metrics_path must still judge SLOs
    on a cadence: the flush thread starts for either consumer."""
    pytest.importorskip("jax")
    from photon_tpu.serving.server import ScoringServer

    class _Scorer:
        def cache_snapshot(self):
            return {}

        def breaker_snapshot(self):
            return {}

    class _Version:
        version = 1
        model_dir = "x"
        scorer = _Scorer()

    class _Registry:
        current = _Version()

    class _Batcher:
        healthy = True

        def snapshot(self):
            return {"queued": 0, "mean_batch_rows": 0.0}

        def close(self):
            pass

    cfg = _slo([{"name": "slo_only_impossible", "metric": "uptime_fake",
                 "op": "<=", "threshold": -1, "on_missing": "violate"}])
    srv = ScoringServer(_Registry(), _Batcher(), port=0, slo_config=cfg,
                        metrics_interval_s=0.05)
    try:
        assert srv._metrics_thread is not None, (
            "slo_config alone must start the flush loop")
        from photon_tpu.obs.metrics import REGISTRY

        deadline = time.monotonic() + 5.0
        while (REGISTRY.counter("slo_violations_total").value(
                slo="slo_only_impossible") < 1
               and time.monotonic() < deadline):
            time.sleep(0.02)
        assert REGISTRY.counter("slo_violations_total").value(
            slo="slo_only_impossible") >= 1, "no periodic SLO judgment"
    finally:
        srv.shutdown()
    # without either consumer, no thread is spent
    srv2 = ScoringServer(_Registry(), _Batcher(), port=0)
    try:
        assert srv2._metrics_thread is None
    finally:
        srv2.shutdown()


# ------------------------------------------------------- JSONL rotation


def test_write_metrics_jsonl_rotates_at_size(tmp_path):
    path = str(tmp_path / "m.jsonl")
    rec = {"k": "x" * 100}
    line_len = len(json.dumps(rec)) + 1
    # 10 records per file before rotation kicks in
    for _ in range(35):
        write_metrics_jsonl(path, [rec], max_bytes=10 * line_len,
                            max_rotated=2)
    assert os.path.exists(path)
    assert os.path.exists(path + ".1")
    assert os.path.exists(path + ".2")
    assert not os.path.exists(path + ".3")  # bounded at max_rotated
    # every surviving file holds only whole, valid JSON lines
    total = 0
    for p in (path, path + ".1", path + ".2"):
        with open(p) as f:
            for line in f:
                assert json.loads(line)["k"] == rec["k"]
                total += 1
    assert total <= 33  # growth is bounded: at most 11 lines x 3 files
    assert total >= 20


def test_write_metrics_jsonl_rotation_disabled(tmp_path):
    path = str(tmp_path / "m.jsonl")
    for _ in range(50):
        write_metrics_jsonl(path, [{"a": 1}], max_bytes=0)
    assert not os.path.exists(path + ".1")
    with open(path) as f:
        assert sum(1 for _ in f) == 50


def test_write_metrics_jsonl_concurrent_with_rotation(tmp_path):
    """The whole-line-atomic contract holds across rotation: concurrent
    writers + size-triggered rotation never tear or corrupt a line."""
    path = str(tmp_path / "m.jsonl")
    n_threads, per_thread = 4, 40
    rec = {"pad": "y" * 64}
    line_len = len(json.dumps({"t": 0, "i": 0, **rec})) + 1

    def worker(t):
        for i in range(per_thread):
            write_metrics_jsonl(path, [{"t": t, "i": i, **rec}],
                                max_bytes=8 * line_len, max_rotated=5)

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    seen = 0
    for suffix in ("", ".1", ".2", ".3", ".4", ".5"):
        p = path + suffix
        if not os.path.exists(p):
            continue
        with open(p) as f:
            for line in f:
                obj = json.loads(line)  # no torn lines, ever
                assert obj["pad"] == rec["pad"]
                seen += 1
    assert seen >= 8  # bounded retention may drop old lines, never tear
