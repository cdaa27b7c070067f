#!/usr/bin/env python3
"""What the sparse formulations (``plain`` gather/segment_sum, the ``fast``
row-slice tables and the one-hot kernel over its two arrangements, the
sorted ``window`` table and, for ``X.w``, the ``planes``) give on the device
in use, op by op and end to end.

    python scripts/sparse_formulation_check.py ops       # this process owns the device
    python scripts/sparse_formulation_check.py training  # children own it, one at a time

``ops``: ``matvec``/``rmatvec``/``sq_rmatvec`` of every formulation, each
forced in turn, at the fixed-effect shapes of the benchmark's five cells
(their configurations' generator, from the seed) and of chip_smoke.py,
against float64 NumPy (largest error relative to the largest entry of the
answer), the first call's seconds and the median of five warm calls on the
host's clock (dispatch, one ``block_until_ready``; not kernel time). One JSON
line a shape, with the mean MXU passes a slot of each op's ``window`` table,
what ``build_fast_aux`` chooses by itself there, and under ``xw`` the two
arrangements of ``X.w`` beside each other: a call's milliseconds (twenty
calls in flight, over twenty), the chunks and passes it runs and what the
build's model makes of them. These readings are what ``ops/fast_sparse.py``
``WINDOW_BREAK_EVEN_PASSES``, ``WINDOW_PASS_US``, ``WINDOW_CHUNK_US``,
``PLANE_CHUNK_US`` and ``PLANES_MARGIN`` were set from. A last line says
whether either arrangement's lookup alone returned its operand's float32
bits.

``training``: two one-device ``game_training_driver`` runs of chip_smoke.py's
data that differ only in the formulation — so only in the order float32
sums are taken — compared by chip_smoke.py's own compare child, once run to
convergence (default flags, two sweeps) and once step for step (one sweep,
the fixed effect stopped after chip_smoke's FOUR_CHIP_FIXED_ITERATIONS).
It is how far apart float32 lets two correct runs stop on ONE device: the
yardstick for the four-chip comparison's limits. Nothing is held to a limit
here; every figure is printed, one JSON object per line.

``--rehearse`` runs either mode at a tiny size (on the CPU both trainings
run the plain formulation, so it only rehearses the control flow; ``ops``
runs the kernel in the Pallas interpreter).
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import chip_smoke  # noqa: E402  (jax-free)


# The benchmark's cells, by the configuration file that holds their data.
CELLS = {"glm_fit": "glm-logistic-l2", "glm_fit_tron": "glm-logistic-tron",
         "game_fit": "game-logistic-user-re",
         "game_fit_ragged": "game-logistic-ragged-re",
         "game_fit_crossed": "game-logistic-crossed-re"}
# The constants of ``ops/fast_sparse.py`` that make ``build_fast_aux`` give
# every op it can the named formulation (``X^T.r`` has no ``planes``: it
# takes ``window`` there): a script's privilege, not an option of the program.
FORCED = {
    "fast": {"WINDOW_BREAK_EVEN_PASSES": -1.0},
    "window": {"WINDOW_BREAK_EVEN_PASSES": float("inf"), "PLANES_MARGIN": 0.0},
    "planes": {"WINDOW_BREAK_EVEN_PASSES": float("inf"),
               "PLANES_MARGIN": float("inf")},
}
# ``fast`` X.w writes 512 B an entry at once: not asked of the chip over this.
FAST_XW_BYTES = 8e9


def _shapes(sizes: dict, seed: int, rehearse: bool):
    """``(name, idx, val, dim)`` of every fixed-effect shape read."""
    import numpy as np

    from benchmarks import datagen

    from benchmarks.kinds import fit_ragged

    for cell, config in CELLS.items():
        with open(os.path.join(HERE, "benchmarks", "configs",
                               config + ".json")) as f:
            data = json.load(f)["data"]
        per_user = data.get("rows_per_user", 0)
        rows = (data["rows"] if "rows" in data
                else int(fit_ragged.user_counts(data).sum())
                if isinstance(per_user, dict) else data["users"] * per_user)
        if rehearse:     # the cell's widths over a four-hundredth of its rows
            rows //= 400
        # The fixed effect's shard alone, as ``datagen.generate`` draws it
        # for rows with no user.
        wg = np.zeros(data["named_features"])
        train = datagen._rows(np.random.default_rng([seed, 1]), data, None,
                              rows, wg, None, None)
        yield (cell, train.gi.astype(np.int32), train.gv.astype(np.float32),
               data["named_features"] + 1)
    n = sizes["n_users"] * sizes["rows_per_user"]
    k, dim = sizes["k_global"] + 1, sizes["d_global"] + 1
    rng = np.random.default_rng([seed, 9])
    idx = np.concatenate([                      # half head, half anywhere
        rng.integers(0, sizes["d_head"], size=(n, k // 2)),
        rng.integers(0, dim, size=(n, k - k // 2))], axis=1).astype(np.int32)
    yield "smoke", idx, rng.normal(size=(n, k)).astype(np.float32), dim


def _select_is_bit_exact(seed: int, arrangement: str) -> bool:
    """The lookup alone (one entry a row, value 1) on float32 operands from
    1e-30 to 1e30, zeros and negatives, by the named arrangement."""
    import numpy as np

    from photon_tpu.data.batch import SparseFeatures
    from photon_tpu.ops import fast_sparse

    rng = np.random.default_rng([seed, 10])
    n, dim = 4096, 20000
    x = (rng.normal(size=dim) * 10.0 ** rng.uniform(-30, 30, size=dim)
         ).astype(np.float32)
    x[::7] = 0.0
    # Sorted, so that 128 rows read a narrow span.
    idx = np.sort(rng.integers(0, dim, size=n)).astype(np.int32)[:, None]
    with _forced(fast_sparse, arrangement):
        feats = SparseFeatures(idx=idx, val=np.ones((n, 1), np.float32),
                               dim=dim).with_fast_path()
    assert feats.fast.formulation("matvec") == arrangement
    got = np.asarray(feats.matvec(x))
    return bool((got.view(np.uint32) == x[idx[:, 0]].view(np.uint32)).all())


@contextlib.contextmanager
def _forced(fast_sparse, formulation: str):
    kept = {name: getattr(fast_sparse, name) for name in FORCED[formulation]}
    for name, value in FORCED[formulation].items():
        setattr(fast_sparse, name, value)
    try:
        yield
    finally:
        for name, value in kept.items():
            setattr(fast_sparse, name, value)


def _xw_reading(fast_sparse, feats, fn, x) -> dict:
    """One arrangement of ``X.w``: what it runs, what the build's model
    makes of that, and a call's milliseconds with twenty in flight."""
    import numpy as np

    table = feats.fast.xw
    n_pass = np.asarray(table.passes) & 255
    calls = 20
    t0 = time.monotonic()
    for _ in range(calls):
        out = fn(feats, x)
    out.block_until_ready()
    return {"chunks": int(np.count_nonzero(n_pass)),
            "passes": int(n_pass.sum()),
            "model_ms": fast_sparse._kernel_us(
                n_pass, fast_sparse.WINDOW_CHUNK_US
                if table.formulation == "window"
                else fast_sparse.PLANE_CHUNK_US) / 1e3,
            "call_ms": (time.monotonic() - t0) / calls * 1e3}


def _ops(sizes: dict, seed: int, rehearse: bool) -> None:
    import jax
    import numpy as np

    from photon_tpu.data.batch import SparseFeatures
    from photon_tpu.ops import fast_sparse

    dev = jax.devices()[0]
    for shape, idx, val, dim in _shapes(sizes, seed, rehearse):
        n, k = idx.shape
        rng = np.random.default_rng([seed, 11])
        w = rng.normal(size=dim).astype(np.float32)
        v = rng.normal(size=n).astype(np.float32)
        val64, flat = val.astype(np.float64), idx.ravel()
        want = {
            "matvec": (np.append(w.astype(np.float64), 0.0)[idx] * val64
                       ).sum(1),
            "rmatvec": np.bincount(
                flat, (v.astype(np.float64)[:, None] * val64).ravel(),
                dim + 1)[:dim],
            "sq_rmatvec": np.bincount(
                flat, (v.astype(np.float64)[:, None] * val64 ** 2).ravel(),
                dim + 1)[:dim],
        }
        plain = SparseFeatures(idx=jax.device_put(idx),
                               val=jax.device_put(val), dim=dim)
        chosen = plain.with_fast_path().fast
        out = {"mode": "ops", "device": dev.device_kind,
               "platform": dev.platform, "shape_of": shape,
               "shape": [n, k, dim],
               "chosen_by": {name: getattr(fast_sparse, name) for name in (
                   "WINDOW_BREAK_EVEN_PASSES", "WINDOW_PASS_US",
                   "WINDOW_CHUNK_US", "PLANE_CHUNK_US", "PLANES_MARGIN")},
               "chosen": {op: chosen.formulation(op)
                          for op in ("matvec", "rmatvec")}, "xw": {}}
        del chosen
        for name in ("plain", *FORCED):
            feats = plain
            if name in FORCED:
                with _forced(fast_sparse, name):
                    feats = plain.with_fast_path()
            if name == "window":
                out["passes_per_slot"] = {
                    key[len("passes_per_slot_"):]: value
                    for key, value in feats.fast.span_arguments().items()
                    if key.startswith("passes_per_slot_")}
            for op, arg in (("matvec", w), ("rmatvec", v), ("sq_rmatvec", v)):
                if feats.fast is not None and feats.fast.formulation(op) != name:
                    continue     # this op's entries have no such table
                if (name, op) == ("fast", "matvec") and (
                        512 * n * k > FAST_XW_BYTES):
                    continue
                fn = jax.jit(lambda f, x, op=op: getattr(f, op)(x))
                x = jax.device_put(arg)
                t0 = time.monotonic()
                got = fn(feats, x).block_until_ready()
                first = time.monotonic() - t0
                warm = []
                for _ in range(5):
                    t0 = time.monotonic()
                    fn(feats, x).block_until_ready()
                    warm.append(time.monotonic() - t0)
                err = np.abs(np.asarray(got, np.float64) - want[op]).max()
                out[f"{name}.{op}"] = {
                    "max_err_rel_to_largest":
                        float(err / np.abs(want[op]).max()),
                    "first_call_s": round(first, 3),
                    "median_warm_s": sorted(warm)[2]}
                if op == "matvec" and name in ("window", "planes"):
                    out["xw"][name] = _xw_reading(fast_sparse, feats, fn, x)
            del feats
        print(json.dumps(out), flush=True)
    print(json.dumps({"mode": "ops", **{
        f"{arrangement}_select_bit_exact": _select_is_bit_exact(
            seed, arrangement)
        for arrangement in ("window", "planes")}}), flush=True)


def _training(sizes: dict, seed: int, out: str, platform: str) -> None:
    def run(name, spec, on_chip=True, env=None):
        return chip_smoke._run_child(name, spec, out, platform if on_chip
                                     else "cpu", on_chip=on_chip, env=env)

    data = run("data", {"seed": seed, "sizes": sizes,
                        "dir": os.path.join(out, "data")}, on_chip=False)
    for mode, sweeps, cut in (
            ("converged", 2, None),
            ("step_for_step", 1, chip_smoke.FOUR_CHIP_FIXED_ITERATIONS)):
        dirs = {}
        for kind, env in (("default", {}),
                          ("plain", {"PHOTON_DISABLE_ACCEL_PATHS": "1"})):
            name = f"{mode}_{kind}"
            dirs[kind] = os.path.join(out, name)
            rep = run(name, {"driver": "game_training_driver",
                             "argv": chip_smoke._train_argv(
                                 data["paths"], dirs[kind], sweeps, 1, cut)},
                      env=env)
            chip_smoke._check_device(rep, platform, rep["device"]["count"])
            print(json.dumps({
                "mode": "training", "run": name, "device": rep["device"],
                "phase_seconds": rep["phase_seconds"],
                "sparse_op_traces": rep["sparse_op_traces"],
                **chip_smoke._check_training(
                    rep, dirs[kind], sweeps, fixed_cut=cut is not None)}),
                flush=True)
        cmp_ = run(f"{mode}_compare", {
            "kind": "compare", "a": dirs["default"], "b": dirs["plain"],
            "coef_tol": float("inf"), "objective_tol": float("inf")},
            on_chip=False)
        print(json.dumps({"mode": "training", "compare": mode, **{
            k: v for k, v in cmp_.items() if not k.endswith("_limit")}}),
            flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=["ops", "training"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=os.path.join(
        HERE, "chip_smoke_out", "formulations"))
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    sizes = chip_smoke.TINY if args.rehearse else chip_smoke.REAL
    platform = "cpu" if args.rehearse else "tpu"
    if args.mode == "ops":
        if args.rehearse:
            os.environ["JAX_PLATFORMS"] = "cpu"
        _ops(sizes, args.seed, args.rehearse)
        return 0
    os.makedirs(args.out, exist_ok=True)
    try:
        _training(sizes, args.seed, os.path.abspath(args.out), platform)
    except chip_smoke.SmokeFailure as e:
        print(f"sparse_formulation_check: FAILED: {e}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
