"""Seconds of set-up spent compiling programs or loading them from the
persistent cache."""


def read(state: dict):
    c = state["setup_counters"]
    return c["compile_s"] + c["cache_load_s"]
