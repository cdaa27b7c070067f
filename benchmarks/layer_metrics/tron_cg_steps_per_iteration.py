"""CG steps a trust-region iteration takes: the ``cg_steps`` arguments of
the window's ``descent.step`` spans over the iterations those steps'
trackers report (the cap is ``max_cg_iterations``, 20)."""
from benchmarks.layer_metrics import _tracker, _tron


def read(state: dict):
    steps = _tron.per_fit(state, "cg_steps")
    iterations = sum(s["iterations"] for s in _tracker.steps(state, "fixed"))
    if steps is None or not iterations:
        return None
    return steps * len(state["trackers"]) / iterations
