"""The readers of the fits' own span trees, on trees built by hand."""
import importlib

import pytest

from benchmarks.layer_metrics import _spans

READERS = ("table_build_s", "table_builds_per_fit", "validate_s",
           "descent_host_s")


def tree(scale: float = 1.0, tables: bool = True, first_id: int = 0) -> list:
    """One fit's tree as the program keeps it: the spans in the order they
    ended, the root last; seconds times ``scale``."""
    spans = [                          # name, id, parent, start, end
        ("data.accel_tables", 3, 2, 0.1, 1.1),
        ("estimator.build_coordinates", 2, 1, 0.05, 1.15),
        ("optim.glm_fit", 8, 7, 1.3, 1.8),
        ("optim.fixed_solve", 7, 6, 1.3, 1.85),
        ("descent.step", 6, 5, 1.25, 1.9),
        ("descent.validate", 9, 5, 1.9, 2.0),
        ("optim.re_bucket", 11, 10, 2.05, 2.1),
        ("descent.step", 10, 5, 2.0, 2.4),
        ("descent.validate", 12, 5, 2.4, 2.55),
        ("descent.sweep", 5, 4, 1.22, 2.6),
        ("descent.run", 4, 1, 1.2, 2.65),
        ("estimator.evaluate", 13, 1, 2.7, 2.75),
        ("estimator.fit", 1, None, 0.0, 2.8),
    ]
    if not tables:
        spans = spans[1:]
    return [(name, first_id + sid, None if parent is None else first_id + parent,
             scale * a, scale * b, {"trace_id": f"t{first_id}"})
            for name, sid, parent, a, b in spans]


def read(name: str, state: dict):
    return importlib.import_module(f"benchmarks.layer_metrics.{name}").read(state)


@pytest.fixture
def kept(monkeypatch):
    """What the program keeps, oldest first, behind its own function."""
    from photon_tpu.obs import trace as program

    held: list = []

    def recent_trees(root_name, last=None):
        assert root_name == "estimator.fit"
        if last is None:
            return list(held)
        return held[-last:] if last > 0 else []

    monkeypatch.setattr(program, "recent_trees", recent_trees)
    return held


def test_self_seconds_of_a_hand_built_tree():
    own = _spans.self_seconds(tree())
    assert own["estimator.fit"] == pytest.approx(0.05 + 0.05 + 0.05 + 0.05)
    assert own["estimator.build_coordinates"] == pytest.approx(0.1)
    assert own["descent.run"] == pytest.approx(0.02 + 0.05)
    assert own["descent.sweep"] == pytest.approx(0.03 + 0.05)
    assert own["descent.step"] == pytest.approx(0.1 + 0.35)
    assert own["optim.fixed_solve"] == pytest.approx(0.05)
    assert sum(own.values()) == pytest.approx(2.8)     # the root's duration


def test_self_seconds_count_overlapping_children_once():
    spans = [("a", 2, 1, 0.0, 6.0, {}), ("b", 3, 1, 4.0, 12.0, {}),
             ("root", 1, None, 0.0, 10.0, {})]
    assert _spans.self_seconds(spans)["root"] == pytest.approx(0.0)


# What each reader reads in ``tree()``.
WANT = {"table_build_s": 1.0, "table_builds_per_fit": 1.0,
        "validate_s": 0.1 + 0.15 + 0.05,
        "descent_host_s": 0.2 + 0.1 + 0.07 + 0.08}


@pytest.mark.parametrize("name", READERS)
def test_reader_on_one_tree(kept, name):
    kept.append(tree())
    assert read(name, {"trackers": [[]]}) == pytest.approx(WANT[name])


def test_the_five_parts_add_up_to_the_fit(kept):
    kept.append(tree())
    state = {"trackers": [[]]}
    steps = _spans.seconds(tree(), ("descent.step",))
    parts = sum(read(n, state) for n in
                ("table_build_s", "validate_s", "descent_host_s"))
    assert parts + steps == pytest.approx(2.8)


@pytest.mark.parametrize("name", READERS)
def test_reader_is_the_mean_over_the_windows_fits(kept, name):
    kept.extend([tree(1.0), tree(3.0, first_id=100)])
    scale = 1.0 if name == "table_builds_per_fit" else 2.0
    assert read(name, {"trackers": [[], []]}) == pytest.approx(
        scale * WANT[name])


@pytest.mark.parametrize("name", READERS)
def test_reader_never_reaches_past_the_windows_fits(kept, name):
    """The warm-up fit's tree (ten times as long) lies before the
    window's two and is not read."""
    kept.extend([tree(10.0), tree(1.0, first_id=100), tree(1.0, first_id=200)])
    with_warmup = read(name, {"trackers": [[], []]})
    kept.pop(0)
    assert with_warmup == pytest.approx(read(name, {"trackers": [[], []]}))


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_what_is_kept_when_the_ring_dropped_fits(kept, name):
    kept.append(tree())
    assert read(name, {"trackers": [[], [], []]}) == pytest.approx(
        read(name, {"trackers": [[]]}))


@pytest.mark.parametrize("name", READERS)
def test_reader_without_a_tree_returns_nothing(kept, name):
    assert read(name, {"trackers": [[]]}) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_on_a_program_that_keeps_no_trees(monkeypatch, name):
    """The parent of PR 26: ``obs.trace`` has no ``recent_trees``."""
    from photon_tpu.obs import trace as program

    monkeypatch.delattr(program, "recent_trees")
    assert read(name, {"trackers": [[]]}) is None


def test_a_fit_that_builds_no_table_reads_nought(kept):
    kept.append(tree(tables=False))
    state = {"trackers": [[]]}
    assert read("table_build_s", state) == 0.0
    assert read("table_builds_per_fit", state) == 0.0


def test_the_new_entries_name_the_issues_layers_and_cells(bench):
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        m = entries[name]
        assert m["moves"] == "fit_s"
        assert m["workloads"] == ["glm_fit", "game_fit"]
    assert {entries[n]["layer"] for n in READERS[:2]} == {"fit preparation"}
    assert {entries[n]["layer"] for n in READERS[2:]} == {"coordinate descent"}
