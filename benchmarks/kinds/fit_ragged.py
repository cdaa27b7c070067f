"""Job kind ``fit_ragged``: the jobs of kind ``fit`` (whole
``GameEstimator.fit`` calls from zero on resident data, back to back) on a
configuration whose users hold **unequal numbers of rows**.

What it shares with ``fit`` it imports from there: the estimator as the
training driver would build it (``build``), the wrappers around the
coordinates (``Probe``), one timed fit (``one_fit``), the comparison of a
fixed-effect step with the stated optimizer and the judgement (``judge``).
The rows are ``datagen``'s own (``generate`` and through it ``_rows``). Its
own are:

* **the users' column**: ``user_counts`` gives every user its number of
  rows from a fixed sequence, the same for every seed, so that every seed
  gives the same bucket shapes and one set of programs (``PERF.md`` §6,
  PR 25). The seed decides which user gets which count and the order of
  the rows, nothing else. README_fit_ragged.md has the parameters.
* **the comparison of the per-user steps**, against
  ``references/entities_ragged.py`` (segments of rows sorted by user; the
  dense block of ``reference.PerUserLogistic`` cannot exist at a longest
  user of thousands of rows), with ``re_resid`` the worst over the size
  classes of users and not over all users pooled.
"""
from __future__ import annotations

import contextlib
import gc
import statistics
import time

import numpy as np

from benchmarks import datagen, reference
from benchmarks.kinds import fit
from benchmarks.references import entities_ragged


# ------------------------------------------------------------ the data


def user_counts(data: dict) -> np.ndarray:
    """Rows a user, ascending: the quantiles ``(i + 0.5) / of_users`` of a
    log-normal with the stated ``median`` and ``sigma``, rounded and
    clipped to ``[min, max]``. A cut keeps whole users and the skew: of
    that sorted sequence every ``every``-th count, from the middle of the
    first stride (``every`` 1: all of them; 2: positions 1, 3, 5, ...)."""
    r = data["rows_per_user"]
    normal = statistics.NormalDist()
    q = np.array([normal.inv_cdf((i + 0.5) / r["of_users"])
                  for i in range(r["of_users"])])
    counts = np.rint(np.exp(np.log(r["median"]) + r["sigma"] * q))
    counts = np.clip(counts, r["min"], r["max"]).astype(np.int64)
    return counts[r["every"] // 2::r["every"]]


def generate(data: dict, seed: int) -> datagen.Dataset:
    """``datagen.generate``'s data with ``user_counts`` rows a user: the
    seed shuffles which user gets which count, and the generator the order
    of the rows. (``datagen.generate`` repeats each user ``rows_per_user``
    times, and takes a count a user as it takes one for all.)"""
    counts = user_counts(data)
    if len(counts) != data["users"]:
        raise ValueError(f"data.users is {data['users']}, and "
                         f"data.rows_per_user gives {len(counts)} users")
    np.random.default_rng([seed, 3]).shuffle(counts)
    return datagen.generate(dict(data, rows_per_user=counts), seed)


def shapes(config: dict, ds: datagen.Dataset) -> dict:
    """What the per-layer readers count work from. ``rows_per_user`` is a
    number, rows over users: ``fit_mfu``'s reader counts the random
    effect's work as entity passes x these mean rows (PERF.md §5 says how
    far that is from the sum over users)."""
    d = config["data"]
    return {"rows": ds.train.n_rows, "validation_rows": ds.validation.n_rows,
            "global_dim": ds.global_dim, "global_nnz": d["named_nnz"] + 1,
            "users": ds.n_users, "rows_per_user": ds.train.n_rows / ds.n_users,
            "user_dim": ds.user_dim, "user_nnz": d["user_nnz"] + 1}


# ---------------------------------------------------------- the comparison


def check(config: dict, ds: datagen.Dataset, steps: list, tracker: list,
          paths: list = None) -> dict:
    """The numbers ``fit.check`` compares, by the same names and of the
    same steps (``PERF.md`` §2), with the per-user problems stated over
    ragged segments and ``re_resid`` the worst residual of any size class
    of users after any random-effect step."""
    coords = config["coordinates"]
    by_id = {c["id"]: c for c in coords}
    order = [c["id"] for c in coords] * config["sweeps"]
    if [s["kind"] for s in steps] != [by_id[c]["kind"] for c in order]:
        return {"steps_missing": float(max(1, abs(len(order) - len(steps))))}
    tr, va = ds.train, ds.validation

    problems = {}
    for c in coords:
        if c["kind"] == "fixed":
            problems[c["id"]] = reference.objective(config["task"])(
                idx=tr.gi, val=tr.gv, y=tr.y, offsets=np.zeros(tr.n_rows),
                dim=ds.global_dim, l2=c["reg_weight"],
                intercept=ds.global_dim - 1)
        else:
            problems[c["id"]] = entities_ragged.RaggedUserLogistic.build(
                tr.users, tr.ui, tr.uv, tr.y, ds.n_users, ds.user_dim,
                c["reg_weight"], ds.user_dim - 1)

    latest: dict = {}
    train_scores: dict = {}
    gaps: dict = {}

    def hold(name: str, value: float) -> None:
        value = float(value) if np.isfinite(value) else float("inf")
        gaps[name] = max(gaps.get(name, 0.0), value)

    for cid, s, t in zip(order, steps, tracker):
        c, problem = by_id[cid], problems[cid]
        expected = sum((v for k, v in train_scores.items() if k != cid),
                       np.zeros(tr.n_rows))
        hold("offsets", fit._max_gap(s["offsets"], expected))
        if s["kind"] == "fixed":
            problem.offsets = s["offsets"]
            fit._fixed_step(c, problem, s, hold, paths)
            train_scores[cid] = reference.sparse_scores(tr.gi, tr.gv, s["w"])
        else:
            by_class = problem.residual_by_class(s["w"], s["offsets"])
            hold("re_resid", max(by_class.values()))
            if paths is not None:
                paths.append({"re_resid_by_class": by_class})
            train_scores[cid] = problem.scores(s["w"])
        hold("scores", fit._max_gap(s["scores"], train_scores[cid]))
        latest[cid] = s
        scores = fit._validation_scores(latest, va)
        for name in config["evaluators"]:
            hold("val_" + name.lower(), reference.evaluator_gap(
                name, t["validation"][name], scores, va.y))
    return gaps


# ------------------------------------------------------------------ a run


def run(cell: dict, config: dict, mix: dict, limits: dict, seed: int,
        seconds: float, trace_dir, t_start: float, say) -> dict:
    """Set-up, window and comparison of one run, as ``fit.run`` makes them
    (the same earlier lines, the same state for the readers)."""
    import jax

    from photon_tpu.runtime import compile_store

    if mix.get("start") != "zero" or mix.get("checkpointing"):
        raise ValueError(f"job mix not understood by kind 'fit_ragged': {mix}")
    compile_store.install_accounting()
    cache_dir = compile_store.enable_compilation_cache(min_compile_secs=0.0)
    marks = {"imports_s": time.perf_counter() - t_start}

    t = time.perf_counter()
    ds = generate(config["data"], seed)
    marks["datagen_s"] = time.perf_counter() - t
    t = time.perf_counter()
    estimator, train, validation, opt_configs = fit.build(config, ds)
    marks["bundle_s"] = time.perf_counter() - t

    probe = fit.Probe()
    probe.install()
    try:
        result, warm_s = fit.one_fit(estimator, train, validation,
                                     opt_configs, probe)
        marks["warmup_fit_s"] = warm_s
        setup_counters = fit._counters()
        setup_s = time.perf_counter() - t_start
        say({"setup": marks, "setup_s": setup_s, "cache_dir": cache_dir,
             "counters": setup_counters, "shapes": shapes(config, ds),
             "warmup_tracker": fit._tracker(result)})

        trackers, fit_seconds = [], []
        tracing = contextlib.nullcontext()
        if trace_dir:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            tracing = jax.profiler.trace(trace_dir, profiler_options=options)
        with tracing:
            w0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.window"):
                while time.perf_counter() - w0 < seconds:
                    result, s = fit.one_fit(estimator, train, validation,
                                            opt_configs, probe)
                    trackers.append(fit._tracker(result))
                    fit_seconds.append(s)
            window_s = time.perf_counter() - w0
        window_counters = fit._counters()
        steps = fit._plain_steps(probe.steps, ds)
    finally:
        probe.remove()

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices())
    del estimator, train, validation, result, probe
    gc.collect()

    t = time.perf_counter()
    correct, compared = fit.judge(check(config, ds, steps, trackers[-1]),
                                  limits)
    check_s = time.perf_counter() - t
    say({"window_s": window_s, "fits": len(trackers),
         "fit_seconds": fit_seconds, "check_s": check_s,
         "trackers": trackers})
    return {
        "correct": correct, "compared": compared,
        "attempted": len(trackers), "failed": 0,
        "end_to_end": {"fit_s": window_s / len(trackers), "setup_s": setup_s},
        "state": {
            "cell": cell, "config": config, "shapes": shapes(config, ds),
            "window_s": window_s, "trackers": trackers,
            "fit_seconds": fit_seconds, "memory_peak_bytes": peak,
            "setup_counters": setup_counters,
            "window_counters": window_counters,
        },
    }
