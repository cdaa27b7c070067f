#!/usr/bin/env python3
"""The control and the faults of a factored random effect, planted under
the timed path as ``faults.py`` plants the others (``PERF.md`` §2), for
the tests and the chip readings that have to see ``correct`` come out
false in a cell of kind ``fit_factored``:

* ``projection_skipped``: every projection step returns the projection it
  was given (its solve made, its answer dropped);
* ``wrong_user_latent``: a row is scored with the latent vector of the
  entity after its own (training rows and validation rows alike);
* ``init_at_zero``: the initial projection is left at zero;
* ``latent_step_skipped``: the latent step between a factored step's two
  projection steps returns the latent vectors it was given (its solve
  made, its answer dropped; the cell's two alternations make three latent
  steps a factored step).

And, read by the fixed steps' numbers alone (``fit_factored.fixed_check``),
``faults.py``'s faults of the fixed effect (``_fixed_faults``).

The control, ``products_bfloat16``, is the precision below the
configuration's: the operands of every product with the projection rounded
to bfloat16, the projected rows the latent steps solve on, the projection
step's products (``XP . b`` and its transpose) and the latent-space scores
alike.

Run as a script it makes the readings the limits of
``limits/game_fit_factored.json`` are set from, on the chip, many seeds in
one process: the sound program compared (``--program``), the control
(``--control``), the faults of the factored effect (``--faults``) and of
the fixed effect (``--fixed-faults``), each compared; ``--fits-only``
seeds fitted once, the fit that prepares, and not compared (the
iterations and passes every step reads). A seed may be named under
several; one prepared estimator a seed serves all of its fits, and each
fit's comparison runs in a worker process while the chip goes on. One
JSON line a reading on standard output and in
``chiprun_out/readings_<cell>.jsonl``::

    python3 benchmarks/tests/faults_factored.py game_fit_factored \\
        --program 300 --control 300 --faults 300 --fits-only 310-321
"""
from __future__ import annotations

import contextlib
import os
import sys

if __name__ == "__main__":
    sys.path[0] = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


@contextlib.contextmanager
def _module_patch(name: str, make):
    """The program's ``factored_random_effect.<name>`` replaced by
    ``make(original)`` (the functions a factored step calls by name)."""
    from photon_tpu.game import factored_random_effect as f

    original = getattr(f, name)
    setattr(f, name, make(original))
    try:
        yield
    finally:
        setattr(f, name, original)


def projection_skipped():
    def make(original):
        def projection_step(problem, rows, P, offsets, lats):
            _, result = original(problem, rows, P, offsets, lats)
            return P, result
        return projection_step
    return _module_patch("projection_step", make)


def init_at_zero():
    def make(original):
        def subspace_init(*args):
            import jax.numpy as jnp

            return jnp.zeros_like(original(*args))
        return subspace_init
    return _module_patch("subspace_init", make)


@contextlib.contextmanager
def wrong_user_latent():
    import jax.numpy as jnp

    from photon_tpu.game.factored_random_effect import (
        FactoredRandomEffectModel,
    )

    original = FactoredRandomEffectModel.latent_plan

    def latent_plan(self, dataset):
        plan = original(self, dataset)
        n = sum(int(b.shape[0]) for b in self.bucket_latent)
        return jnp.where(plan < n, (plan + 1) % n, plan)

    FactoredRandomEffectModel.latent_plan = latent_plan
    try:
        yield
    finally:
        FactoredRandomEffectModel.latent_plan = original


def latent_step_skipped():
    def make(original):
        made = [0]

        def latent_step(problem, dataset, rows, P, offsets, lats):
            out, results = original(problem, dataset, rows, P, offsets, lats)
            made[0] += 1
            return (list(lats) if made[0] % 3 == 2 else out), results
        return latent_step
    return _module_patch("latent_step", make)


def _low(x):
    import jax.numpy as jnp

    return x.astype(jnp.bfloat16).astype(x.dtype)


@contextlib.contextmanager
def products_bfloat16():
    import jax

    from photon_tpu.game import factored_random_effect as f

    times_dot, transpose_dot = f._times_dot, f._transpose_dot
    step, project_slots = f._projection_step, f.project_slots
    scores = f.latent_scores

    def low_times_dot(table, beta, idx, val, slot):
        return times_dot(_low(table), _low(beta), idx, val, slot)

    def low_transpose_dot(r, beta, idx, val, slot, dim):
        return transpose_dot(_low(r), _low(beta), idx, val, slot, dim)

    def low_project_slots(P, idx, val, row_ids):
        return _low(project_slots(_low(P), idx, val, row_ids))

    f._times_dot, f._transpose_dot = low_times_dot, low_transpose_dot
    # Traced anew, so that the products they make are the rounded ones: a
    # function of its own, as jit keeps the traces of a function it has
    # seen.
    def low_step(problem, *args):
        return step.__wrapped__(problem, *args)

    def low_scores(*args, n_rows):
        return scores.__wrapped__(*args, n_rows=n_rows)

    f._projection_step = jax.jit(low_step, static_argnums=(0,))
    f.latent_scores = jax.jit(low_scores, static_argnames="n_rows")
    f.project_slots = low_project_slots
    try:
        yield
    finally:
        f._times_dot, f._transpose_dot = times_dot, transpose_dot
        f._projection_step, f.project_slots = step, project_slots
        f.latent_scores = scores


def _fixed_faults() -> dict:
    from benchmarks.tests import faults

    return {name: faults.FAULTS[name] for name in (
        "unchanged_fixed", "half_batch", "short_memory", "stops_early")}


CONTROL = products_bfloat16
FAULTS = {
    "projection_skipped": projection_skipped,
    "wrong_user_latent": wrong_user_latent,
    "init_at_zero": init_at_zero,
    "latent_step_skipped": latent_step_skipped,
}


# ------------------------------------------------- the readings on the chip


_held: dict = {}


def compare(config: dict, seed: int, path: str, limits: dict,
            fixed_only: bool = False) -> dict:
    """In a worker: the comparison of the fit whose steps ``path`` holds
    (of its fixed steps alone where ``fixed_only``), judged by the cell's
    ``limits``. The worker keeps the last seed's data."""
    import pickle

    from benchmarks.kinds import fit_factored

    if _held.get("seed") != seed:
        _held.clear()
        _held.update(seed=seed,
                     ds=fit_factored.generate(config["data"], seed))
    with open(path, "rb") as f:
        steps, tracker = pickle.load(f)
    os.remove(path)
    from benchmarks.kinds import fit

    if fixed_only:
        numbers = fit_factored.fixed_check(config, _held["ds"], steps)
        return {"numbers": numbers,
                "correct": fit.judge(numbers, limits)[0]}
    paths: list = []
    numbers = fit_factored.check(config, _held["ds"], steps, tracker, paths)
    return {"numbers": numbers, "correct": fit.judge(numbers, limits)[0],
            "paths": paths}


def _fit_once(built, ds, planted):
    """One fit of a prepared estimator with ``planted`` under the kind's
    probe: (steps, tracker, seconds, sub-steps)."""
    from benchmarks.kinds import fit, fit_factored
    from benchmarks.layer_metrics import _spans

    probe = fit_factored.Probe()
    with planted:
        probe.install()
        try:
            result, seconds = fit_factored.one_fit(*built, probe)
            steps = fit_factored._plain_steps(probe.steps, ds)
        finally:
            probe.remove()
    kept = _spans.trees({"trackers": [None]}) or [[]]
    return (steps, fit._tracker(result), seconds,
            fit_factored.sub_steps(kept[-1]))


def main() -> int:
    import argparse
    import concurrent.futures
    import contextlib as cl
    import gc
    import json
    import multiprocessing
    import pickle
    import tempfile
    import threading
    import time

    from benchmarks.tests import chip_readings

    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--program", default="",
                    help="seeds of the sound program, compared, as 100-104")
    ap.add_argument("--control", default="",
                    help="seeds of the control, compared")
    ap.add_argument("--faults", default="",
                    help="seeds of the factored effect's faults, compared")
    ap.add_argument("--fixed-faults", default="",
                    help="seeds of the fixed effect's faults, compared by "
                         "the fixed steps' numbers")
    ap.add_argument("--fits-only", default="",
                    help="seeds fitted once (the fit that prepares) and "
                         "not compared")
    # At the cell's size two comparisons at once, each on the reference's
    # threads, have run a one-chip host's 40 GiB out beside the chip's
    # process.
    ap.add_argument("--workers", type=int, default=1)
    ap.add_argument("--tiny", action="store_true",
                    help="rehearse on the CPU at the tests' size")
    args = ap.parse_args()

    with open(os.path.join(chip_readings.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == args.cell)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(chip_readings.ROOT, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(chip_readings.ROOT, "benchmarks", "limits",
                           f"{args.cell}.json")) as f:
        limits = json.load(f)
    if args.tiny:
        from benchmarks.tests.test_fit_kind_factored import tiny_factored

        config = tiny_factored(config)

    t_start = time.perf_counter()
    pool = concurrent.futures.ProcessPoolExecutor(
        args.workers, mp_context=multiprocessing.get_context("spawn"))

    import jax

    from benchmarks.kinds import fit_factored
    from photon_tpu.runtime import compile_store

    compile_store.enable_compilation_cache(min_compile_secs=0.0)
    out_dir = os.path.join(chip_readings.ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    log = open(os.path.join(out_dir, f"readings_{args.cell}.jsonl"), "a")
    held = tempfile.mkdtemp(prefix="readings_")
    lock = threading.Lock()
    pending: list = []
    device = jax.devices()[0].device_kind

    def emit(line: dict) -> None:
        line["at_s"] = time.perf_counter() - t_start
        with lock:
            text = json.dumps(line)
            print(text, flush=True)
            log.write(text + "\n")
            log.flush()

    def reading(what, seed, ds, built, planted, compared=True,
                fixed_only=False) -> None:
        steps, tracker, seconds, subs = _fit_once(built, ds, planted)
        line = {"cell": args.cell, "what": what, "seed": seed,
                "device": device, "fit_seconds": [seconds],
                "steps": [{k: t.get(k) for k in (
                    "coordinate", "seconds", "iterations", "data_passes",
                    "validation")} for t in tracker],
                "sub_steps": subs}
        if not compared:
            emit(line)
            return
        path = os.path.join(held, f"{what}.{seed}.pkl")
        with open(path, "wb") as f:
            pickle.dump((steps, tracker), f, protocol=pickle.HIGHEST_PROTOCOL)

        def done(future) -> None:
            try:
                line.update(future.result())
            except Exception as e:       # noqa: BLE001 - reported, not lost
                line["error"] = repr(e)
            emit(line)

        future = pool.submit(compare, config, seed, path, limits, fixed_only)
        future.add_done_callback(done)
        pending.append(future)

    asked = {name: chip_readings.seeds(getattr(args, name)) for name in (
        "program", "control", "fixed_faults", "faults", "fits_only")}
    order = list(dict.fromkeys(sum(asked.values(), [])))
    for seed in order:
        ds = fit_factored.generate(config["data"], seed)
        built = fit_factored.build(config, ds)
        if seed in asked["fits_only"]:
            reading("program", seed, ds, built, cl.nullcontext(),
                    compared=False)
        else:
            _fit_once(built, ds, cl.nullcontext())    # prepares, compiles
        if seed in asked["program"]:
            reading("program", seed, ds, built, cl.nullcontext())
        if seed in asked["control"]:
            reading("control_products_bfloat16", seed, ds, built, CONTROL())
        if seed in asked["fixed_faults"]:
            for name, plant in _fixed_faults().items():
                reading(f"fault_{name}", seed, ds, built, plant(),
                        fixed_only=True)
        if seed in asked["faults"]:
            for name, plant in FAULTS.items():
                reading(f"fault_{name}", seed, ds, built, plant())
        built = ds = None
        gc.collect()
    concurrent.futures.wait(pending)
    pool.shutdown(wait=True)
    log.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
