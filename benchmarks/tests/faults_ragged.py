#!/usr/bin/env python3
"""The faults only unequal buckets can show, planted under the timed path
as ``faults.py`` plants the others (``PERF.md`` §2):

* ``smallest_class_unchanged``: the bucket with the fewest users returns
  its start unchanged (zero coefficients on the first step). A handful of
  users among tens of thousands: a residual pooled over all users hides
  them, the worst residual over the size classes reads 1;
* ``padded_rows_weighted``: every row slot of a bucket, the padding too,
  is trained on with weight 1.

Run as a script it makes ``chip_readings.py``'s readings for a cell of
kind ``fit_ragged`` (that kind's generator and comparison), with these two
added to the faults it plants:

    python3 benchmarks/tests/faults_ragged.py game_fit_ragged \\
        --faults 300-301 --faults-some 302 --only half_batch,stops_early \\
        --program 100-108
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import os
import sys

if __name__ == "__main__":
    sys.path[0] = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmarks.tests import faults


def smallest_class_unchanged():
    import jax.numpy as jnp

    from photon_tpu.game import coordinates as co

    def wrapper(original):
        def train(self, offsets, init=None):
            model, result = original(self, offsets, init)
            coefs = list(model.bucket_coefs)
            b = min(range(len(coefs)), key=lambda i: coefs[i].shape[0])
            coefs[b] = (jnp.zeros_like(coefs[b]) if init is None
                        else init.bucket_coefs[b])
            return dataclasses.replace(model, bucket_coefs=coefs), result
        return train

    return faults._patched(co.RandomEffectCoordinate, wrapper)


@contextlib.contextmanager
def padded_rows_weighted():
    import jax.numpy as jnp

    from photon_tpu.data.random_effect import EntityBucket

    original = EntityBucket.local_batches

    def local_batches(self, global_offsets):
        batches = original(self, global_offsets)
        return dataclasses.replace(
            batches, weights=jnp.ones_like(batches.weights))

    EntityBucket.local_batches = local_batches
    try:
        yield
    finally:
        EntityBucket.local_batches = original


FAULTS = {
    "smallest_class_unchanged": smallest_class_unchanged,
    "padded_rows_weighted": padded_rows_weighted,
}


# ------------------------------------------------- the readings on the chip
#
# ``chip_readings.py`` makes one reading after another: data, estimator,
# fits, comparison. At this cell's size the comparison is 165 s of NumPy on
# the host and the data and the estimator's preparation 80 s, against 15 s
# of fit, so here the chip is not left waiting for either: a seed's data
# and prepared estimator serve the sound fit and every planted fault (the
# faults patch what a fit calls, not what it prepared; the control changes
# what is prepared, and gets its own), and each fit's comparison runs in a
# worker process that touches NumPy alone while the chip goes on.


def _dataset(config: dict, seed: int):
    from benchmarks.kinds import fit_ragged

    return fit_ragged.generate(config["data"], seed)


_held: dict = {}


def _cold_steps_once() -> None:
    """A seed's sound fit, its faults and its control all start their
    first fixed-effect step from zero coefficients and zero offsets: the
    reference's run of that step (float64, the same every time, a third
    of a comparison) is made once a seed in this worker."""
    import numpy as np

    from benchmarks import reference

    stated = reference.optimizer

    def optimizer(name):
        def run(problem, cap, init):
            if np.any(init) or np.any(problem.offsets):
                return stated(name)(problem, cap, init)
            key = (name, cap)
            if key not in _held["cold"]:
                _held["cold"][key] = stated(name)(problem, cap, init)
            return _held["cold"][key]
        return run

    reference.optimizer = optimizer


def compare(config: dict, seed: int, path: str) -> dict:
    """In a worker: the comparison of the fit whose steps ``path`` holds.
    The worker keeps the last seed's data."""
    import pickle

    import numpy as np

    from benchmarks.kinds import fit_ragged

    if not _held:
        _cold_steps_once()
    if _held.get("seed") != seed:
        _held.clear()
        _held.update(seed=seed, ds=_dataset(config, seed), cold={})
    with open(path, "rb") as f:
        steps, tracker = pickle.load(f)
    os.remove(path)
    for s in steps:
        for name in ("offsets", "scores"):
            s[name] = s[name].astype(np.float64)
    paths: list = []
    numbers = fit_ragged.check(config, _held["ds"], steps, tracker, paths)
    return {"numbers": numbers, "paths": paths}


def _last_step_buckets() -> list:
    """What the last fit's last per-user step says of each bucket it
    solved (the arguments of its ``optim.re_bucket`` spans)."""
    from benchmarks.layer_metrics import _re_buckets, _spans

    kept = _spans.trees({"trackers": [None]})
    spans = [s for s in (kept[0] if kept else ())
             if s[_spans.NAME] == _re_buckets.BUCKET]
    keys = ("padded_rows", "entities", "rows", "row_slots", "solver", "chunk")
    return [{k: s[_spans.ARGS].get(k) for k in keys} for s in spans
            if s[_spans.PARENT_ID] == spans[-1][_spans.PARENT_ID]]


def _fit_once(built, ds, planted):
    """One fit of a prepared estimator with ``planted`` under the
    harness's wrappers: (steps, tracker, seconds, buckets)."""
    from benchmarks.kinds import fit

    probe = fit.Probe()
    with planted:
        probe.install()
        try:
            result, seconds = fit.one_fit(*built, probe)
            steps = fit._plain_steps(probe.steps, ds)
        finally:
            probe.remove()
    return (steps, fit._tracker(result), seconds,
            _last_step_buckets())


def main() -> int:
    import argparse
    import concurrent.futures
    import json
    import multiprocessing
    import pickle
    import tempfile
    import threading
    import time

    import numpy as np

    from benchmarks.tests import chip_readings

    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--program", default="",
                    help="seeds of the sound program alone, as 100-104")
    ap.add_argument("--faults", default="",
                    help="seeds given the sound program, every fault and "
                         "the control, on one set of data a seed")
    ap.add_argument("--faults-some", default="",
                    help="seeds given the sound program, the faults that "
                         "--only names and the control")
    ap.add_argument("--only", default="", help="comma-separated fault names")
    ap.add_argument("--workers", type=int, default=5)
    ap.add_argument("--until-s", type=float, default=float("inf"),
                    help="start no fit after this many seconds")
    ap.add_argument("--stop-s", type=float, default=float("inf"),
                    help="start no comparison after this many seconds")
    ap.add_argument("--tiny", action="store_true",
                    help="rehearse on the CPU at the tests' size")
    args = ap.parse_args()

    with open(os.path.join(chip_readings.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == args.cell)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(chip_readings.ROOT, entry["file"])) as f:
        config = json.load(f)
    if args.tiny:
        from benchmarks.tests.test_fit_kind_ragged import tiny_ragged

        config = tiny_ragged(config)

    t_start = time.perf_counter()
    pool = concurrent.futures.ProcessPoolExecutor(
        args.workers, mp_context=multiprocessing.get_context("spawn"))

    import jax

    from benchmarks.kinds import fit
    from photon_tpu.runtime import compile_store

    compile_store.enable_compilation_cache(min_compile_secs=0.0)
    out_dir = os.path.join(chip_readings.ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    log = open(os.path.join(out_dir, f"readings_{args.cell}.jsonl"), "a")
    held = tempfile.mkdtemp(prefix="readings_")
    lock = threading.Lock()
    pending: list = []
    device = jax.devices()[0].device_kind

    def submit(what, seed, ds, built, planted) -> None:
        t0 = time.perf_counter()
        steps, tracker, seconds, buckets = _fit_once(built, ds, planted)
        for s in steps:                  # float32 as the program gave them
            for name in ("offsets", "scores"):
                s[name] = s[name].astype(np.float32)
        path = os.path.join(held, f"{what}.{seed}.pkl")
        with open(path, "wb") as f:
            pickle.dump((steps, tracker), f, protocol=pickle.HIGHEST_PROTOCOL)
        line = {"cell": args.cell, "what": what, "seed": seed,
                "device": device, "rows": ds.train.n_rows,
                "fit_seconds": [seconds], "buckets": buckets,
                "steps": [{k: t[k] for k in ("coordinate", "seconds",
                                             "iterations", "data_passes")}
                          for t in tracker],
                "held_s": time.perf_counter() - t0}

        def done(future) -> None:
            try:
                line.update(future.result())
            except Exception as e:       # noqa: BLE001 - reported, not lost
                line["error"] = repr(e)
            line["at_s"] = time.perf_counter() - t_start
            with lock:
                text = json.dumps(line)
                print(text, flush=True)
                log.write(text + "\n")
                log.flush()

        future = pool.submit(compare, config, seed, path)
        future.add_done_callback(done)
        pending.append(future)

    def warm_up(built, ds) -> None:
        """A reading is a run's set-up and one more fit: the fit that
        groups, builds the tables and compiles is not the one compared."""
        _fit_once(built, ds, contextlib.nullcontext())

    def in_time() -> bool:
        return time.perf_counter() - t_start < args.until_s

    only = set(filter(None, args.only.split(",")))
    planted = dict(faults.FAULTS, **FAULTS)
    with_all = chip_readings.seeds(args.faults)
    with_faults = with_all + chip_readings.seeds(args.faults_some)
    for seed in with_faults + chip_readings.seeds(args.program):
        if not in_time():
            break
        ds = _dataset(config, seed)
        built = fit.build(config, ds)
        warm_up(built, ds)
        submit("program", seed, ds, built, contextlib.nullcontext())
        if seed in with_faults:
            for name, plant in planted.items():
                if in_time() and (seed in with_all or name in only):
                    submit(f"fault_{name}", seed, ds, built, plant())
            built = None
            gc.collect()
            if in_time():
                with faults.CONTROL():
                    built = fit.build(config, ds)
                    warm_up(built, ds)
                    submit("control_program_bf16", seed, ds, built,
                           contextlib.nullcontext())
        built = ds = None
        gc.collect()
    while pending and time.perf_counter() - t_start < args.stop_s:
        concurrent.futures.wait(pending, timeout=5.0)
        pending = [f for f in pending if not f.done()]
    pool.shutdown(wait=True, cancel_futures=True)
    log.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
