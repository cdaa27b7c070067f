"""Peak device memory in use on the fullest chip, after the window."""


def read(state: dict):
    peak = state["memory_peak_bytes"]
    return peak / 1e9 if peak else None
