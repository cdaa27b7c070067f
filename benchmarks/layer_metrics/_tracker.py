"""Shared arithmetic of the readers that read the fits' trackers."""
from benchmarks import work


def coordinate_ids(state: dict, kind: str) -> set:
    return {c["id"] for c in state["config"]["coordinates"] if c["kind"] == kind}


def steps(state: dict, kind: str) -> list:
    """Every tracker step of the window's fits that belongs to a coordinate
    of ``kind`` (``fixed`` or ``random``)."""
    ids = coordinate_ids(state, kind)
    return [s for fit in state["trackers"] for s in fit if s["coordinate"] in ids]


def fixed_work(state: dict) -> dict:
    sh = state["shapes"]
    passes = sum(s["data_passes"] for s in steps(state, "fixed"))
    return work.fixed_work(sh["rows"], sh["global_nnz"], sh["global_dim"], passes)


def random_work(state: dict) -> dict:
    sh = state["shapes"]
    passes = sum(s["data_passes"] for s in steps(state, "random"))
    if not passes:
        return {"bytes": 0, "flops": 0}
    return work.random_effect_work(
        sh["rows_per_user"], sh["user_nnz"], sh["user_dim"], passes)
