"""The readers that split a layer's seconds into the host's and the wait
for the device (PR 38), on a tree built by hand."""
import pytest

# ``kept``: what the program keeps, behind its own function (a fixture).
from benchmarks.tests.test_span_metrics import kept, read  # noqa: F401

READERS = ("device_wait_s", "fixed_host_s", "re_host_s", "re_inputs_s",
           "re_dispatch_s", "validate_host_s", "validate_score_s")
GAME_ONLY = ("re_host_s", "re_inputs_s", "re_dispatch_s")
CONFIG = {"coordinates": [{"id": "global", "kind": "fixed"},
                          {"id": "per-user", "kind": "random"}]}


def tree(scale: float = 1.0, waits: bool = True, first_id: int = 0) -> list:
    """One fit's tree as the program keeps it since PR 38 (one sweep, a
    fixed and a per-entity step, validation after each and of the returned
    model), the spans in the order they ended; without the waits, as a
    program from before would keep the spans it had."""
    spans = [                    # name, id, parent, start, end, arguments
        ("optim.fixed_solve", 5, 4, 1.0, 1.1, {}),
        ("descent.score", 6, 4, 1.1, 1.15, {"coordinate": "global"}),
        ("device.wait", 7, 4, 1.15, 1.9, {"site": "step"}),
        ("descent.step", 4, 3, 1.0, 1.9, {"coordinate": "global"}),
        ("device.wait", 8, 3, 1.9, 1.92, {"site": "solver_outcome"}),
        ("validate.score", 10, 9, 1.95, 2.0, {"coordinate": "global"}),
        ("device.wait", 12, 11, 2.0, 2.08, {"site": "evaluator"}),
        ("validate.evaluate", 11, 9, 2.0, 2.1, {"evaluator": "AUC"}),
        ("descent.validate", 9, 3, 1.95, 2.15, {"coordinate": "global"}),
        ("optim.re_inputs", 14, 13, 2.2, 2.3, {"bucket": 0}),
        ("optim.re_bucket", 15, 13, 2.3, 2.35, {"bucket": 0}),
        ("optim.re_inputs", 16, 13, 2.35, 2.5, {"bucket": 1}),
        ("optim.re_bucket", 17, 13, 2.5, 2.52, {"bucket": 1}),
        ("descent.score", 18, 13, 2.55, 2.6, {"coordinate": "per-user"}),
        ("device.wait", 19, 13, 2.6, 3.0, {"site": "step"}),
        ("descent.step", 13, 3, 2.2, 3.0, {"coordinate": "per-user"}),
        ("device.wait", 22, 21, 3.05, 3.25, {"site": "project_stacks"}),
        ("validate.score", 21, 20, 3.0, 3.4, {"coordinate": "per-user"}),
        ("device.wait", 24, 23, 3.4, 3.45, {"site": "evaluator"}),
        ("validate.evaluate", 23, 20, 3.4, 3.5, {"evaluator": "AUC"}),
        ("descent.validate", 20, 3, 3.0, 3.5, {"coordinate": "per-user"}),
        ("descent.sweep", 3, 2, 0.95, 3.55, {}),
        ("descent.run", 2, 1, 0.9, 3.6, {}),
        ("validate.score", 26, 25, 3.6, 3.65, {"coordinate": "global"}),
        ("device.wait", 28, 27, 3.7, 3.8, {"site": "project_stacks"}),
        ("validate.score", 27, 25, 3.65, 3.9, {"coordinate": "per-user"}),
        ("device.wait", 30, 29, 3.9, 3.97, {"site": "evaluator"}),
        ("validate.evaluate", 29, 25, 3.9, 4.0, {"evaluator": "AUC"}),
        ("estimator.evaluate", 25, 1, 3.6, 4.0, {}),
        ("estimator.fit", 1, None, 0.0, 4.1, {}),
    ]
    if not waits:
        spans = [s for s in spans if s[0] in (
            "optim.fixed_solve", "optim.re_bucket", "descent.step",
            "descent.validate", "descent.sweep", "descent.run",
            "estimator.evaluate", "estimator.fit")]
    return [(name, first_id + sid,
             None if parent is None else first_id + parent,
             scale * a, scale * b, args)
            for name, sid, parent, a, b, args in spans]


# What each reader reads in ``tree()``.
WANT = {
    "device_wait_s": (0.75 + 0.02 + 0.08 + 0.4 + 0.2 + 0.05 + 0.1 + 0.07),
    "fixed_host_s": 0.9 - 0.75,
    "re_host_s": 0.8 - 0.4,
    "re_inputs_s": 0.1 + 0.15,
    "re_dispatch_s": 0.05 + 0.02,
    # validate_s 0.2 + 0.5 + 0.4, less the waits under it, however deep
    "validate_host_s": 1.1 - (0.08 + 0.2 + 0.05 + 0.1 + 0.07),
    "validate_score_s": 0.05 + 0.4 + 0.05 + 0.25,
}


def state(fits: int = 1) -> dict:
    return {"trackers": [[]] * fits, "config": CONFIG}


@pytest.mark.parametrize("name", READERS)
def test_reader_on_one_tree(kept, name):
    kept.append(tree())
    assert read(name, state()) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", READERS)
def test_reader_is_the_mean_over_the_windows_fits(kept, name):
    """The warm-up fit's tree (ten times as long) lies before the window's
    two and is not read."""
    kept.extend([tree(10.0), tree(1.0, first_id=100), tree(3.0, first_id=200)])
    assert read(name, state(2)) == pytest.approx(2.0 * WANT[name])


@pytest.mark.parametrize("name", READERS)
def test_reader_on_a_program_without_the_wait_span_returns_nothing(kept, name):
    """The parent of PR 38 keeps the spans it had and no ``device.wait``:
    a line then holds none of the seven."""
    kept.append(tree(waits=False))
    assert read(name, state()) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_without_a_tree_returns_nothing(kept, name):
    assert read(name, state()) is None


def test_the_host_seconds_and_the_wait_add_up_to_the_fit(kept):
    """With the descent's own host work (the framing spans' self time,
    ``descent_host_s``, which the wait under the sweep is no part of) the
    three layers' host seconds and the wait are the whole fit."""
    kept.append(tree())
    parts = sum(read(n, state()) for n in (
        "device_wait_s", "fixed_host_s", "re_host_s", "validate_host_s",
        "descent_host_s"))
    assert parts == pytest.approx(4.1)


def test_a_fit_with_no_per_entity_coordinate_reads_nought_there(kept):
    kept.append([s for s in tree() if s[5].get("coordinate") != "per-user"
                 and not s[0].startswith("optim.re_")])
    glm = {"trackers": [[]], "config": {"coordinates": CONFIG["coordinates"][:1]}}
    assert read("re_host_s", glm) == 0.0
    assert read("fixed_host_s", glm) == pytest.approx(WANT["fixed_host_s"])


def test_the_entries_name_the_issues_layers_and_cells(bench):
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]][-7:] == list(READERS)
    cells = [w["name"] for w in bench["workloads"]]
    game = [c for c in cells if c.startswith("game_")]
    layers = {"device_wait_s": "device", "fixed_host_s": "fixed-effect solve",
              "validate_host_s": "coordinate descent",
              "validate_score_s": "coordinate descent"}
    for name in READERS:
        m = entries[name]
        assert (m["moves"], m["unit"], m["better"], m["source"]) == (
            "fit_s", "s", "lower", "program_span")
        assert m["workloads"] == (game if name in GAME_ONLY else cells)
        assert m["layer"] == layers.get(name, "random-effect solve")
