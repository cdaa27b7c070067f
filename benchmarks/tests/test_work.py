"""``work.py`` against counts made by hand at a tiny shape."""
import pytest

from benchmarks import work
from benchmarks.layer_metrics import _tracker


def test_one_pass_by_hand():
    # 4 rows x 3 entries, 5 features: 12 entries x (4 + 4 + 4) B, plus the
    # 4-row vector and the 5-feature vector in float32.
    assert work.pass_bytes(4, 3, 5) == 12 * 12 + (4 + 5) * 4 == 180
    assert work.pass_flops(4, 3) == 24


def test_fixed_and_random_work():
    assert work.fixed_work(4, 3, 5, data_passes=7) == {
        "bytes": 7 * 180, "flops": 7 * 24}
    assert work.random_effect_work(2, 3, 4, entity_passes=10) == {
        "bytes": 10 * (6 * 12 + 6 * 4), "flops": 10 * 12}


def test_least_seconds_names_the_binding_peak():
    peak = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_seconds({"bytes": 50, "flops": 100}, peak) == (5.0, "bytes")
    assert work.least_seconds({"bytes": 5, "flops": 100}, peak) == (1.0, "flops")


def test_the_real_cells_are_bytes_bound():
    import json
    import os

    from benchmarks.tests.conftest import ROOT

    with open(os.path.join(ROOT, "benchmarks", "peaks.json")) as f:
        peak = json.load(f)["TPU v5 lite"]
    w = work.fixed_work(1 << 18, 32, 1 << 18, 1)
    assert w["bytes"] == (1 << 23) * 12 + (1 << 19) * 4
    seconds, bound = work.least_seconds(w, peak)
    assert bound == "bytes"
    assert seconds == pytest.approx(w["bytes"] / 819e9)


def test_tracker_work_reads_the_solvers_own_counts():
    state = {
        "config": {"coordinates": [{"id": "f", "kind": "fixed"},
                                   {"id": "u", "kind": "random"}]},
        "shapes": {"rows": 4, "global_nnz": 3, "global_dim": 5,
                   "rows_per_user": 2, "user_nnz": 3, "user_dim": 4},
        "trackers": [[{"coordinate": "f", "data_passes": 3},
                      {"coordinate": "u", "data_passes": 10}],
                     [{"coordinate": "f", "data_passes": 4},
                      {"coordinate": "u", "data_passes": 0}]],
    }
    assert _tracker.fixed_work(state) == work.fixed_work(4, 3, 5, 7)
    assert _tracker.random_work(state) == work.random_effect_work(2, 3, 4, 10)
