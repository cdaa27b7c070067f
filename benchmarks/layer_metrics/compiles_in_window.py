"""Compile requests (persistent-cache hits and misses alike, from
``jax.monitoring`` through ``runtime/compile_store``) made inside the
window. Expected 0: everything compiles or loads in set-up."""


def read(state: dict):
    return (state["window_counters"]["compile_requests"]
            - state["setup_counters"]["compile_requests"])
