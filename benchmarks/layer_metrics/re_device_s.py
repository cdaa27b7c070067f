"""Device seconds a fit spends in the programs that solve random-effect
buckets, from the traced window. Beside ``re_step_s`` (the steps' seconds
on the host's clock) it says how much of a step is the chip and how much
the host between buckets."""

# ``game/newton_re.py`` ``fit_bucket_newton`` and ``fit_bucket_newton_dual``
# and ``game/random_effect.py`` ``_fit_bucket_jitted`` on the trace's
# XLA Modules line.
MODULES = ("jit_fit_bucket_newton", "jit_fit_bucket_newton_dual",
           "jit__fit_bucket_jitted")


def read(state: dict):
    seconds = sum(state["trace"]["module_s"].get(m, 0.0) for m in MODULES)
    if seconds <= 0 or not state["trackers"]:
        return None
    return seconds / len(state["trackers"])
