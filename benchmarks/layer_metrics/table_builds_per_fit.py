"""How often a fit builds fast-path tables (the number of the program's
``data.accel_tables`` spans), per fit: the data does not change between the
window's fits, so every build after the first repeats one."""
from benchmarks.layer_metrics import _spans

SPANS = ("data.accel_tables",)


def read(state: dict):
    return _spans.per_fit(state, lambda tree: _spans.count(tree, SPANS))
