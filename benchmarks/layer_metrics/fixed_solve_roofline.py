"""The fixed-effect solve's share of its roofline: the least time the chip
could take for the passes the solves made (bytes-bound: entries x 12 B a
pass, ``benchmarks/work.py``) over the device time of the solve's program
in the traced window. Counted from the algorithm, so it reads the same work
whatever sparse formulation the program holds."""
from benchmarks import work
from benchmarks.layer_metrics import _tracker

# The program of ``functions/problem.py:_fit_jitted`` on the trace's
# XLA Modules line.
MODULES = ("jit__fit_jitted",)


def read(state: dict):
    seconds = sum(state["trace"]["module_s"].get(m, 0.0) for m in MODULES)
    if seconds <= 0 or not _tracker.steps(state, "fixed"):
        return None
    least, _ = work.least_seconds(_tracker.fixed_work(state), state["peak"])
    return 100.0 * least / seconds
