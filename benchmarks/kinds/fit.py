"""Job kind ``fit``: repeated whole ``GameEstimator.fit`` calls on resident
data, each from zero coefficients, each ending in a device-to-host read of
its coefficients.

Set-up makes the data from the seed, builds the estimator the training
driver would build from the configuration's coordinates, and runs one whole
fit (which prepares the device-resident data and compiles or loads every
program). The window then drives that same estimator. What decides
``correct`` is read from the window's last fit, once the window has closed
and the program's state is freed: see ``check`` and ``PERF.md``.

From the program this file takes the estimator and, through thin wrappers
around ``Coordinate.train`` that it installs itself, what each coordinate
step was given and what it returned (for the comparison) and host spans
around the layers (for the traced run).
"""
from __future__ import annotations

import contextlib
import gc
import time

import numpy as np

from benchmarks import datagen, reference

FIRST_STEPS = 3


# ------------------------------------------------------------- the program


def _spec(c: dict) -> str:
    """The program's ``--coordinate`` string for one coordinate."""
    s = (f"{c['id']}:type={c['kind']},shard={c['shard']},"
         f"optimizer={c['optimizer']},reg={c['regularization']},"
         f"reg_weights={c['reg_weight']:g},max_iter={c['max_iterations']},"
         f"tol={c['tolerance']:g}")
    if c["kind"] == "random":
        s += f",re_type={c['entity']}"
    return s


def _bundle(split: datagen.Split, ds: datagen.Dataset, dtype, entity: str):
    import jax.numpy as jnp

    from photon_tpu.data.batch import SparseFeatures
    from photon_tpu.io.data_reader import GameDataBundle

    feats = {"global": SparseFeatures(
        idx=jnp.asarray(split.gi, jnp.int32),
        val=jnp.asarray(split.gv, dtype), dim=ds.global_dim)}
    tags = {}
    if split.users is not None:
        feats["user"] = SparseFeatures(
            idx=jnp.asarray(split.ui, jnp.int32),
            val=jnp.asarray(split.uv, dtype), dim=ds.user_dim)
        tags[entity] = datagen.user_keys(split.users)
    n = split.n_rows
    return GameDataBundle(
        features=feats, labels=split.y.astype(dtype),
        offsets=np.zeros(n, dtype), weights=np.ones(n, dtype),
        uids=np.arange(n), id_tags=tags)


def build(config: dict, ds: datagen.Dataset):
    """(estimator, training bundle, validation bundle, optimization
    configurations): what ``game_training_driver`` assembles, from arrays."""
    from photon_tpu.cli.params import configs_from_specs, parse_coordinates
    from photon_tpu.estimators.game_estimator import GameEstimator
    from photon_tpu.types import TaskType

    coords = config["coordinates"]
    specs = parse_coordinates([_spec(c) for c in coords])
    data_configs, opt_configs = configs_from_specs(specs)
    dtype = np.dtype(config["dtype"])
    entity = next((c["entity"] for c in coords if c["kind"] == "random"), "")
    intercepts = {"global": ds.global_dim - 1}
    if ds.n_users:
        intercepts["user"] = ds.user_dim - 1
    estimator = GameEstimator(
        task=TaskType[config["task"]],
        coordinate_data_configs=data_configs,
        update_sequence=tuple(c["id"] for c in coords),
        n_sweeps=config["sweeps"],
        evaluator_specs=tuple(config["evaluators"]),
        intercept_indices=intercepts,
    )
    return (estimator, _bundle(ds.train, ds, dtype, entity),
            _bundle(ds.validation, ds, dtype, entity), opt_configs)


class Probe:
    """Wrappers the benchmark puts around the program's layer boundaries:
    each coordinate step's inputs and outputs are kept (the last fit's
    only), and every wrapped call is a ``bench.*`` span in a trace."""

    def __init__(self):
        self.steps: list = []
        self._undo: list = []

    def _wrap(self, owner, attr: str, span: str, keep: str = ""):
        import jax

        original = getattr(owner, attr)
        steps = self.steps

        def wrapped(*args, **kwargs):
            with jax.profiler.TraceAnnotation(span):
                out = original(*args, **kwargs)
            if keep == "train":          # train(self, offsets, init=None)
                init = args[2] if len(args) > 2 else kwargs.get("init")
                steps.append({"kind": span.split(".")[1], "offsets": args[1],
                              "init": init, "out": out})
            elif keep == "score" and steps:
                steps[-1]["scores"] = out      # descent scores right after
            return out

        setattr(owner, attr, wrapped)
        self._undo.append((owner, attr, original))

    def install(self) -> None:
        from photon_tpu.evaluation import EvaluationSuite
        from photon_tpu.game import coordinates as co
        from photon_tpu.ops import fast_sparse

        self._wrap(co.FixedEffectCoordinate, "train", "bench.fixed.train", "train")
        self._wrap(co.RandomEffectCoordinate, "train", "bench.random.train", "train")
        self._wrap(co.FixedEffectCoordinate, "score", "bench.fixed.score", "score")
        self._wrap(co.RandomEffectCoordinate, "score", "bench.random.score", "score")
        self._wrap(fast_sparse, "build_fast_aux", "bench.build_fast_aux")
        self._wrap(EvaluationSuite, "evaluate", "bench.validate")

    def remove(self) -> None:
        for owner, attr, original in reversed(self._undo):
            setattr(owner, attr, original)
        self._undo.clear()


def _fixed_coefficients(model) -> np.ndarray:
    return np.asarray(model.model.coefficients.means, np.float64)


def _user_coefficients(model, n_users: int, dim: int) -> np.ndarray:
    """The per-user coefficients ``[U, P]`` of a RandomEffectModel, in the
    user shard's own columns, rows by the user the generator's key names."""
    out = np.zeros((n_users, dim))
    users = np.array([datagen.user_of_key(k) for k in model.entity_keys])
    for coefs, proj, ids in zip(model.bucket_coefs, model.bucket_proj,
                                model.bucket_entity_ids):
        coefs, proj, ids = (np.asarray(a) for a in (coefs, proj, ids))
        live = ids >= 0
        u = np.broadcast_to(users[np.where(live, ids, 0)][:, None], proj.shape)
        ok = live[:, None] & (proj < dim)
        out[u[ok], proj[ok]] = coefs[ok]
    return out


def _plain_steps(steps: list, ds: datagen.Dataset) -> list:
    """The kept steps as NumPy: offsets and starting coefficients in,
    coefficients out, and for a fixed effect the solver's own record of its
    iterations (``values`` and ``grad_norms``: entry 0 the start, entry i
    after iteration i, infinite past the last iteration it ran)."""
    out = []
    for s in steps:
        model, result = s["out"]
        rec = {"kind": s["kind"],
               "offsets": np.asarray(s["offsets"], np.float64),
               "scores": np.asarray(s["scores"], np.float64)}
        if s["kind"] == "fixed":
            rec["w"] = _fixed_coefficients(model)
            rec["init"] = (np.zeros_like(rec["w"]) if s["init"] is None
                           else _fixed_coefficients(s["init"]))
            rec["value"] = float(result.value)
            rec["values"] = np.asarray(result.values, np.float64)
            rec["grad_norms"] = np.asarray(result.grad_norms, np.float64)
        else:
            rec["w"] = _user_coefficients(model, ds.n_users, ds.user_dim)
        out.append(rec)
    return out


def _tracker(result) -> list:
    out = []
    for r in result.tracker:
        conv = r.convergence or {}
        out.append({
            "sweep": r.sweep, "coordinate": r.coordinate_id,
            "seconds": r.seconds,
            "iterations": conv.get("iterations"),
            "data_passes": conv.get("data_passes"),
            "reasons": conv.get("reasons"),
            "validation": dict(r.validation.values) if r.validation else None,
        })
    return out


# ---------------------------------------------------------- the comparison


def check(config: dict, ds: datagen.Dataset, steps: list, tracker: list,
          paths: list = None) -> dict:
    """The numbers compared, by short name (``PERF.md`` §2 says what each
    is for and where its limit comes from). ``steps`` are the last fit's
    coordinate steps as ``_plain_steps`` gives them. Every coordinate step
    is held against the stated problem it was given: the offsets and the
    starting coefficients it was handed. The reference runs each fixed
    step's stated optimizer for the stated number of iterations, and the
    two are compared iteration by iteration (``paths``, if given, receives
    each fixed step's gaps for ``tests/chip_readings.py`` to print)."""
    coords = config["coordinates"]
    by_id = {c["id"]: c for c in coords}
    order = [c["id"] for c in coords] * config["sweeps"]
    if [s["kind"] for s in steps] != [by_id[c]["kind"] for c in order]:
        # Not the steps the configuration states: held to 0 by ``judge``.
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
            problems[c["id"]] = reference.PerUserLogistic.build(
                tr.users, tr.ui, tr.uv, tr.y, ds.n_users, ds.user_dim,
                c["reg_weight"], ds.user_dim - 1)

    latest: dict = {}
    train_scores: dict = {}
    gaps: dict = {}

    def hold(name: str, value: float) -> None:
        """The worst reading of ``name`` over the steps; a reading that is
        no number counts as infinitely bad."""
        value = float(value) if np.isfinite(value) else float("inf")
        gaps[name] = max(gaps.get(name, 0.0), value)

    for cid, s, t in zip(order, steps, tracker):
        c, problem = by_id[cid], problems[cid]
        if len(coords) > 1:
            expected = sum((v for k, v in train_scores.items() if k != cid),
                           np.zeros(tr.n_rows))
            hold("offsets", _max_gap(s["offsets"], expected))
        if s["kind"] == "fixed":
            problem.offsets = s["offsets"]
            _fixed_step(c, problem, s, hold, paths)
            train_scores[cid] = reference.sparse_scores(tr.gi, tr.gv, s["w"])
        else:
            hold("re_resid", problem.residual(s["w"], s["offsets"]))
            train_scores[cid] = problem.scores(s["w"])
        hold("scores", _max_gap(s["scores"], train_scores[cid]))
        latest[cid] = s
        scores = _validation_scores(latest, va)
        for name in config["evaluators"]:
            hold("val_" + name.lower(), reference.evaluator_gap(
                name, t["validation"][name], scores, va.y))
    return gaps


def _fixed_step(c: dict, problem, s: dict, hold, paths) -> None:
    """One fixed-effect step against the reference's run of the stated
    optimizer from the same start, for the stated number of iterations.

    The two objectives are compared after every iteration the program ran.
    Two paths that agree to rounding part for an iteration or more where
    rounding decides a line search or the iteration at which a plateau is
    left, and a step that starts from an earlier model does so from its
    second iteration on (PERF.md §2). So what is held of a step from zero
    is the first iterations' worst gap (``loss3``, ``grad3``) and the
    median gap over its iterations (``loss_mid``), as far as the reference
    finds every line search decided beyond rounding (``sure``, all of them
    on most seeds; a step with fewer than three such iterations has no
    path to hold), and of every step
    the first gradient (``grad0``) and the objective at the coefficients
    it returned (``final_loss``). A step that stops before its cap has to
    have reached a plateau by the reference's reckoning: ``early_stop`` is
    the reference's decrease in the program's last iteration, in units of
    the stated tolerance."""
    cap = c["max_iterations"]
    ref = reference.optimizer(c["optimizer"])(problem, cap, s["init"])
    want, want_g = np.asarray(ref["values"]), np.asarray(ref["grad_norms"])
    ran = int(np.isfinite(s["values"]).sum()) - 1
    if ran < 1 or ran > len(want) - 1:
        hold("early_stop", float("inf"))
        return
    # What the reference's path can vouch for: the iterations up to the first
    # line search that float32 rounding could have decided otherwise.
    sure = min(ran, ref["sure"])
    if ran == cap:
        hold("early_stop", 0.0)
    elif ran == sure:
        hold("early_stop", (want[ran - 1] - want[ran])
             / (c["tolerance"] * abs(want[ran])))
    got, got_g = s["values"][:ran + 1], s["grad_norms"][:ran + 1]
    gap = np.abs(got - want[:ran + 1]) / np.abs(want[:ran + 1])
    gap_g = np.abs(got_g - want_g[:ran + 1]) / want_g[:ran + 1]
    cold = not np.any(s["init"])
    hold("grad0", gap_g[0])
    if cold and sure >= FIRST_STEPS:
        first = FIRST_STEPS + 1
        hold("loss3", gap[:first].max())
        hold("grad3", gap_g[1:first].max())
        hold("loss_mid", np.median(gap[:sure + 1]))
    value = problem.value_from_margins(problem.margins(s["w"]), s["w"])
    hold("final_loss", abs(s["value"] - value) / value)
    if paths is not None:
        moved = np.linalg.norm(ref["w"] - s["init"])
        paths.append({
            "cold": bool(cold), "ran": ran, "sure": int(ref["sure"]),
            "loss_gap": gap.tolist(),
            "grad_gap": gap_g.tolist(),
            "loss_end_signed": float((got[-1] - want[ran]) / want[ran]),
            "w_change": float(abs(np.linalg.norm(s["w"] - s["init"]) - moved)
                              / moved)})


def _max_gap(got: np.ndarray, want: np.ndarray) -> float:
    """Largest |got - want| over the rows, against the largest |want| (1 at
    the least)."""
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.max(np.abs(got - want))) / max(1.0, float(np.max(np.abs(want))))


def _validation_scores(latest: dict, va: datagen.Split) -> np.ndarray:
    scores = np.zeros(va.n_rows)
    for s in latest.values():
        if s["kind"] == "fixed":
            scores += reference.sparse_scores(va.gi, va.gv, s["w"])
        else:
            scores += reference.user_scores(va.users, va.ui, va.uv, s["w"])
    return scores


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """``compared``: each number beside its limit; correct when every one
    is finite and within it. A number the cell's limits do not name is held
    to 0, as an exact comparison."""
    compared = {name: [value, limits.get(name, 0.0)]
                for name, value in numbers.items()}
    ok = all(np.isfinite(v) and v <= limit for v, limit in compared.values())
    return bool(ok), compared


# ------------------------------------------------------------------ a run


def one_fit(estimator, train, validation, opt_configs, probe: Probe):
    """One whole fit as the window drives it, ending in a device-to-host
    read of every coefficient. Returns (result, seconds)."""
    import jax

    probe.steps.clear()
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.fit"):
        result = estimator.fit(train, validation, opt_configs)[0]
        for model in result.model.models.values():
            if hasattr(model, "bucket_coefs"):
                for c in model.bucket_coefs:
                    np.asarray(c)
            else:
                np.asarray(model.model.coefficients.means)
    return result, time.perf_counter() - t0


def reading(config: dict, ds: datagen.Dataset, fits: int = 1) -> dict:
    """Outside a run (the tests, ``tests/chip_readings.py``): build the
    estimator, fit ``fits`` times and compare the last fit with the
    reference."""
    estimator, train, validation, opt = build(config, ds)
    probe = Probe()
    probe.install()
    try:
        seconds = []
        for _ in range(fits):
            result, s = one_fit(estimator, train, validation, opt, probe)
            seconds.append(s)
        steps = _plain_steps(probe.steps, ds)
    finally:
        probe.remove()
    tracker = _tracker(result)
    paths: list = []
    return {"numbers": check(config, ds, steps, tracker, paths),
            "paths": paths, "fit_seconds": seconds, "tracker": tracker}


def _counter(snapshot: dict, name: str) -> float:
    v = snapshot.get(name, 0)
    return float(sum(v.values()) if isinstance(v, dict) else v)


def _counters() -> dict:
    from photon_tpu.obs.metrics import REGISTRY

    snap = REGISTRY.snapshot()
    return {
        "compile_requests": (_counter(snap, "xla_cache_hits_total")
                             + _counter(snap, "xla_cache_misses_total")),
        "cache_hits": _counter(snap, "xla_cache_hits_total"),
        "cache_misses": _counter(snap, "xla_cache_misses_total"),
        "compile_s": _counter(snap, "xla_compile_seconds_total"),
        "cache_load_s": _counter(snap, "xla_cache_load_seconds_total"),
        "sparse_op_traces": snap.get("sparse_op_traces_total", {}),
    }


def shapes(config: dict, ds: datagen.Dataset) -> dict:
    d = config["data"]
    out = {"rows": ds.train.n_rows, "validation_rows": ds.validation.n_rows,
           "global_dim": ds.global_dim, "global_nnz": d["named_nnz"] + 1}
    if ds.n_users:
        out.update(users=ds.n_users, rows_per_user=d["rows_per_user"],
                   user_dim=ds.user_dim, user_nnz=d["user_nnz"] + 1)
    return out


def run(cell: dict, config: dict, mix: dict, limits: dict, seed: int,
        seconds: float, trace_dir, t_start: float, say) -> dict:
    """Set-up, window and comparison of one run. ``say`` prints one JSON
    object on an earlier line of standard output. ``trace_dir`` is where a
    traced run writes its profile, else None."""
    import jax

    from photon_tpu.runtime import compile_store

    if mix.get("start") != "zero" or mix.get("checkpointing"):
        raise ValueError(f"job mix not understood by kind 'fit': {mix}")
    compile_store.install_accounting()
    cache_dir = compile_store.enable_compilation_cache(min_compile_secs=0.0)
    marks = {"imports_s": time.perf_counter() - t_start}

    t = time.perf_counter()
    ds = datagen.generate(config["data"], seed)
    marks["datagen_s"] = time.perf_counter() - t
    t = time.perf_counter()
    estimator, train, validation, opt_configs = build(config, ds)
    marks["bundle_s"] = time.perf_counter() - t

    probe = Probe()
    probe.install()
    try:
        result, warm_s = one_fit(estimator, train, validation, opt_configs,
                                 probe)
        marks["warmup_fit_s"] = warm_s
        setup_counters = _counters()
        setup_s = time.perf_counter() - t_start
        say({"setup": marks, "setup_s": setup_s, "cache_dir": cache_dir,
             "counters": setup_counters, "shapes": shapes(config, ds),
             "warmup_tracker": _tracker(result)})

        trackers, fit_seconds = [], []
        tracing = contextlib.nullcontext()
        if trace_dir:
            # Device planes and the benchmark's own host spans; no Python
            # call tracing, which would swamp the trace and slow the host.
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            tracing = jax.profiler.trace(trace_dir, profiler_options=options)
        with tracing:
            w0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.window"):
                while time.perf_counter() - w0 < seconds:
                    result, s = one_fit(estimator, train, validation,
                                        opt_configs, probe)
                    trackers.append(_tracker(result))
                    fit_seconds.append(s)
            window_s = time.perf_counter() - w0
        window_counters = _counters()
        steps = _plain_steps(probe.steps, ds)
    finally:
        probe.remove()

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices())
    del estimator, train, validation, result, probe
    gc.collect()

    t = time.perf_counter()
    numbers = check(config, ds, steps, trackers[-1])
    correct, compared = judge(numbers, limits)
    check_s = time.perf_counter() - t

    fixed_ids = {c["id"] for c in config["coordinates"] if c["kind"] == "fixed"}
    fixed_passes = sum(s["data_passes"] for tr in trackers for s in tr
                       if s["coordinate"] in fixed_ids)
    say({"window_s": window_s, "fits": len(trackers),
         "fit_seconds": fit_seconds, "check_s": check_s,
         "trackers": trackers})
    return {
        "correct": correct, "compared": compared,
        "attempted": len(trackers), "failed": 0,
        "end_to_end": {
            "fit_s": window_s / len(trackers),
            "row_passes_per_s": ds.train.n_rows * fixed_passes / window_s,
            "setup_s": setup_s,
        },
        # What the per-layer readers read (benchmarks/README.md).
        "state": {
            "cell": cell, "config": config, "shapes": shapes(config, ds),
            "window_s": window_s, "trackers": trackers,
            "fit_seconds": fit_seconds, "memory_peak_bytes": peak,
            "setup_counters": setup_counters,
            "window_counters": window_counters,
        },
    }
