#!/usr/bin/env python3
"""Readings of the compared numbers on the chip, many seeds in one process:
the sound program (the lower readings), the control (the program's own
bfloat16 value storage, ``faults.CONTROL``) and the planted faults (the
upper readings). Limits in ``benchmarks/limits/`` are set from what this
prints (PERF.md §2). Not part of a benchmark run.

    python3 benchmarks/tests/chip_readings.py <cell> --program 100-111 \
        --program-bf16 200-202 --faults 300-302

One JSON line per reading on standard output and in
``chiprun_out/readings_<cell>.jsonl``.
"""
import argparse
import contextlib
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[0] = ROOT


def seeds(spec: str) -> list:
    if not spec:
        return []
    lo, _, hi = spec.partition("-")
    return list(range(int(lo), int(hi or lo) + 1))


def reading(config: dict, seed: int, fits: int) -> dict:
    """A run's set-up and window without the clock."""
    from benchmarks import datagen
    from benchmarks.kinds import fit

    out = fit.reading(config, datagen.generate(config["data"], seed), fits)
    gc.collect()
    return {"numbers": out["numbers"], "paths": out["paths"],
            "fit_seconds": out["fit_seconds"],
            "steps": [{k: t[k] for k in ("coordinate", "seconds",
                                         "iterations", "data_passes")}
                      for t in out["tracker"]]}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--program", default="")
    ap.add_argument("--program-bf16", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--fits", type=int, default=2)
    ap.add_argument("--only", default="",
                    help="comma-separated fault names; all unless given")
    ap.add_argument("--tiny", action="store_true",
                    help="rehearse on the CPU at the tests' size")
    args = ap.parse_args()

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == args.cell)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    if args.tiny:
        from benchmarks.tests.conftest import tiny

        config = tiny(config)

    import jax

    from photon_tpu.runtime import compile_store

    from benchmarks.tests import faults

    compile_store.enable_compilation_cache(min_compile_secs=0.0)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    log = open(os.path.join(ROOT, "chiprun_out",
                            f"readings_{args.cell}.jsonl"), "a")

    def emit(what: str, seed: int, planted) -> None:
        t0 = time.perf_counter()
        with planted:
            out = reading(config, seed, args.fits)
        line = json.dumps({"cell": args.cell, "what": what, "seed": seed,
                           "device": jax.devices()[0].device_kind,
                           "seconds": time.perf_counter() - t0, **out})
        print(line, flush=True)
        log.write(line + "\n")
        log.flush()

    for seed in seeds(args.program):
        emit("program", seed, contextlib.nullcontext())
    kinds = {c["kind"] for c in config["coordinates"]}
    for seed in seeds(args.program_bf16):
        emit("control_program_bf16", seed, faults.CONTROL())
    only = set(filter(None, args.only.split(",")))
    for name, plant in faults.FAULTS.items():
        if name == "unchanged_random" and "random" not in kinds:
            continue
        if only and name not in only:
            continue
        for seed in seeds(args.faults):
            emit(f"fault_{name}", seed, plant())
    log.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
