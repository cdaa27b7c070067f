"""CLI parameter parsing shared by the GAME drivers.

Parity: reference ⟦photon-client/.../cli/game/GameDriver.scala,
ScoptGameTrainingParametersParser, ScoptGameScoringParametersParser⟧
(SURVEY.md §2.3 "Param parsing"): declarative flag → config bridging with
cross-validation, including the reference's per-coordinate configuration
mini-DSL.

Coordinate spec mini-DSL (one ``--coordinate`` flag per coordinate):

    <cid>:<k>=<v>,<k>=<v>,...

keys: ``type`` fixed|random|factored (required); ``shard`` feature shard id;
``re_type`` entity id column (random/factored, required); ``active_bound``
int; ``min_rows`` int; ``optimizer`` LBFGS|OWLQN|TRON; ``max_iter`` int;
``tol`` float; ``reg`` NONE|L1|L2|ELASTIC_NET; ``alpha`` elastic-net α;
``reg_weights`` '|'-separated floats (sweep, default 0); ``downsample`` rate;
``variance`` NONE|SIMPLE|FULL; ``incremental`` prior weight for incremental
training from --model-input-dir (requires it); ``latent``/``alternations``
(factored only) latent dimension and alternation count.

Example:
    --coordinate "fixed:type=fixed,shard=global,optimizer=LBFGS,reg=L2,reg_weights=0.1|1|10"
    --coordinate "perUser:type=random,re_type=userId,shard=user,reg=L2,reg_weights=1"
"""
from __future__ import annotations

import dataclasses
from typing import TYPE_CHECKING, Optional, Sequence

# Where the persistent XLA cache goes is the runtime's decision; the drivers
# reach it through this module, next to the flag (jax-free at import).
from photon_tpu.runtime.compile_store import (  # noqa: F401
    compilation_cache_dir,
    enable_compilation_cache,
)

# The estimator/optimizer config types reach jax-backed kernels on import.
# They are needed only by the coordinate mini-DSL parsers, so they load
# lazily inside those functions — the accelerator-free drivers (router,
# control) import this module for flag helpers and must stay jax-free.
if TYPE_CHECKING:  # pragma: no cover - typing only
    from photon_tpu.estimators.config import (
        CoordinateDataConfig,
        GLMOptimizationConfiguration,
    )


@dataclasses.dataclass(frozen=True)
class CoordinateSpec:
    """One parsed ``--coordinate`` flag."""

    cid: str
    data: CoordinateDataConfig
    optimization: GLMOptimizationConfiguration
    reg_weights: tuple[float, ...]


_BOOL = {"true": True, "false": False}


def _parse_bool(cid: str, key: str, raw: str) -> bool:
    """Strict DSL booleans: silent False on a typo would quietly disable the
    scale knob and OOM at exactly the scale it exists for."""
    low = raw.strip().lower()
    if low in ("1", "true", "yes"):
        return True
    if low in ("0", "false", "no"):
        return False
    raise ValueError(
        f"coordinate {cid!r}: {key} must be one of 1/0/true/false/yes/no, "
        f"got {raw!r}"
    )


def parse_coordinate_spec(spec: str) -> CoordinateSpec:
    from photon_tpu.estimators.config import (
        FactoredRandomEffectDataConfig,
        FixedEffectDataConfig,
        GLMOptimizationConfiguration,
        RandomEffectDataConfig,
    )
    from photon_tpu.functions.problem import VarianceComputationType
    from photon_tpu.optim import OptimizerType
    from photon_tpu.optim.regularization import (
        RegularizationContext,
        RegularizationType,
        elastic_net_context,
    )

    cid, sep, body = spec.partition(":")
    cid = cid.strip()
    if not sep or not cid:
        raise ValueError(
            f"coordinate spec must be '<cid>:k=v,...', got {spec!r}"
        )
    kv: dict[str, str] = {}
    for item in body.split(","):
        item = item.strip()
        if not item:
            continue
        k, sep, v = item.partition("=")
        if not sep:
            raise ValueError(f"coordinate {cid!r}: bad item {item!r} (need k=v)")
        kv[k.strip()] = v.strip()

    known = {
        "type", "shard", "re_type", "active_bound", "min_rows", "max_features", "optimizer",
        "max_iter", "tol", "reg", "alpha", "reg_weights", "downsample",
        "variance", "incremental", "latent", "alternations",
        "max_bucket_entities", "host_resident",
    }
    unknown = set(kv) - known
    if unknown:
        raise ValueError(f"coordinate {cid!r}: unknown keys {sorted(unknown)}")

    ctype = kv.get("type")
    if ctype not in ("fixed", "random", "factored"):
        raise ValueError(
            f"coordinate {cid!r}: type must be 'fixed', 'random' or "
            f"'factored', got {ctype!r}"
        )
    shard = kv.get("shard", "global")
    if ctype == "fixed":
        for k in ("re_type", "active_bound", "min_rows", "max_features",
                  "latent", "alternations", "max_bucket_entities",
                  "host_resident"):
            if k in kv:
                raise ValueError(f"coordinate {cid!r}: {k} is random-effect only")
        data: CoordinateDataConfig = FixedEffectDataConfig(feature_shard=shard)
    else:
        if "re_type" not in kv:
            raise ValueError(f"coordinate {cid!r}: random effects need re_type")
        re_kwargs = dict(
            re_type=kv["re_type"],
            feature_shard=shard,
            active_bound=int(kv["active_bound"]) if "active_bound" in kv else None,
            min_entity_rows=int(kv.get("min_rows", 1)),
            max_features_per_entity=(
                int(kv["max_features"]) if "max_features" in kv else None
            ),
            max_bucket_entities=(
                int(kv["max_bucket_entities"])
                if "max_bucket_entities" in kv else None
            ),
            host_resident=_parse_bool(cid, "host_resident",
                                      kv.get("host_resident", "0")),
        )
        if ctype == "factored":
            data = FactoredRandomEffectDataConfig(
                latent_dim=int(kv.get("latent", 8)),
                n_alternations=int(kv.get("alternations", 2)),
                **re_kwargs,
            )
        else:
            if "latent" in kv or "alternations" in kv:
                raise ValueError(
                    f"coordinate {cid!r}: latent/alternations need type=factored"
                )
            data = RandomEffectDataConfig(**re_kwargs)

    reg_type = RegularizationType(kv.get("reg", "NONE").upper())
    if reg_type == RegularizationType.ELASTIC_NET:
        reg_ctx = elastic_net_context(float(kv.get("alpha", 0.5)))
    else:
        reg_ctx = RegularizationContext(reg_type)

    opt = GLMOptimizationConfiguration(
        optimizer_type=OptimizerType(kv.get("optimizer", "LBFGS").upper()),
        max_iterations=int(kv.get("max_iter", 80)),
        tolerance=float(kv.get("tol", 1e-7)),
        regularization=reg_ctx,
        down_sampling_rate=float(kv.get("downsample", 1.0)),
        variance_type=VarianceComputationType(kv.get("variance", "NONE").upper()),
        incremental_weight=float(kv.get("incremental", 0.0)),
    )
    weights = tuple(
        float(w) for w in kv.get("reg_weights", "0").split("|") if w != ""
    )
    if not weights:
        weights = (0.0,)
    return CoordinateSpec(cid=cid, data=data, optimization=opt, reg_weights=weights)


def parse_coordinates(specs: Sequence[str]) -> list[CoordinateSpec]:
    out = [parse_coordinate_spec(s) for s in specs]
    seen = set()
    for c in out:
        if c.cid in seen:
            raise ValueError(f"duplicate coordinate id {c.cid!r}")
        seen.add(c.cid)
    return out


def configs_from_specs(specs: Sequence[CoordinateSpec]):
    """(data configs by cid, optimization-config sweep) from parsed specs —
    the reference's Seq[GameOptimizationConfiguration] expansion."""
    from photon_tpu.estimators.config import reg_weight_sweep

    data_configs = {c.cid: c.data for c in specs}
    base = {c.cid: c.optimization.with_reg_weight(c.reg_weights[0]) for c in specs}
    sweep_axes = {
        c.cid: list(c.reg_weights) for c in specs if len(c.reg_weights) > 1
    }
    configs = reg_weight_sweep(base, sweep_axes) if sweep_axes else [base]
    return data_configs, configs


@dataclasses.dataclass(frozen=True)
class FeatureShardSpec:
    """One parsed ``--feature-shard`` flag: ``<shard>:<bag>[+<bag>...][:no-intercept]``."""

    shard: str
    feature_bags: tuple[str, ...]
    add_intercept: bool


def parse_feature_shard(spec: str) -> FeatureShardSpec:
    parts = spec.split(":")
    if not (1 <= len(parts) <= 3) or not parts[0]:
        raise ValueError(
            f"feature shard spec must be '<shard>[:<bag>+<bag>][:no-intercept]', got {spec!r}"
        )
    shard = parts[0]
    bags = tuple((parts[1] if len(parts) > 1 and parts[1] else "features").split("+"))
    add_intercept = True
    if len(parts) == 3:
        if parts[2] != "no-intercept":
            raise ValueError(f"feature shard {shard!r}: expected 'no-intercept', got {parts[2]!r}")
        add_intercept = False
    return FeatureShardSpec(shard, bags, add_intercept)


def mesh_from_flags(n_devices: int, mesh_spec=None):
    """Shared --devices/--mesh handling for the drivers: 0 = all visible
    devices, 1 = no mesh (None), N = data-axis mesh over the first N;
    ``mesh_spec`` ("data=4,model=2") builds an explicit multi-axis mesh.
    Negative counts and over-subscription fail loud."""
    import jax

    from photon_tpu.parallel.mesh import DATA_AXIS, make_mesh

    avail = len(jax.devices())
    if mesh_spec:
        axes = {}
        for item in mesh_spec.split(","):
            name, sep, size = item.partition("=")
            if not sep:
                raise ValueError(f"--mesh items must be axis=size, got {item!r}")
            axes[name.strip()] = int(size)
        if DATA_AXIS not in axes:
            raise ValueError(
                f"--mesh must include the '{DATA_AXIS}' axis (got {sorted(axes)})"
            )
        total = 1
        for s in axes.values():
            total *= s
        if total > avail:
            raise ValueError(f"--mesh needs {total} devices, have {avail}")
        return make_mesh(axes, devices=jax.devices()[:total])
    if n_devices < 0:
        raise ValueError(f"--devices must be >= 0, got {n_devices}")
    n = avail if n_devices == 0 else n_devices
    if n > avail:
        raise ValueError(f"--devices {n} > {avail} visible devices")
    if n <= 1:
        return None
    return make_mesh({DATA_AXIS: n}, devices=jax.devices()[:n])


def add_compilation_cache_flag(parser) -> None:
    """Shared --compilation-cache-dir flag (see
    :func:`~photon_tpu.runtime.compile_store.compilation_cache_dir` for
    where the cache goes without it)."""
    parser.add_argument(
        "--compilation-cache-dir", default=None,
        help="persistent XLA compilation cache directory: compiled programs "
             "survive process restarts (supervisor relaunches, repeated "
             "driver runs), so an accelerator compile is paid once per "
             "program shape, not once per process (default: "
             "$JAX_COMPILATION_CACHE_DIR if set — naming another directory "
             "here is then an error — else <checkout>/.jax_cache)")


def add_compile_store_flag(parser) -> None:
    """Shared --compile-store flag (default: $PHOTON_COMPILE_STORE, else
    <output-dir>/compile-store): the AOT compile-artifact store that makes
    restarts and device-loss recoveries zero-recompile
    (runtime/compile_store.py; docs/robustness.md §"Recovery time")."""
    import os

    parser.add_argument(
        "--compile-store",
        default=os.environ.get("PHOTON_COMPILE_STORE") or None,
        help="AOT compile-artifact store directory: compiled-kernel "
             "signatures are recorded into a manifest and the supervisor / "
             "device-loss recovery pre-warms them from the persistent "
             "compilation cache instead of re-paying XLA "
             "(default: $PHOTON_COMPILE_STORE, else "
             "<output-dir>/compile-store; 'off' disables)")


def enable_compile_store(args, output_dir=None):
    """Activate the AOT compile store process-wide (``--compile-store off``
    disables). Defaults to ``<output-dir>/compile-store`` so supervised
    restarts and checkpoint resumes get zero-recompile behavior out of the
    box. The artifact bytes live in the one persistent cache
    (:func:`compilation_cache_dir`), never under the store. Returns the
    store or None."""
    import logging

    from photon_tpu.runtime import compile_store

    path = getattr(args, "compile_store", None)
    if path in ("off", "0", "none"):
        # Pin the opt-out: a fleet-wide $PHOTON_COMPILE_STORE must not
        # lazily re-activate behind the operator's explicit 'off'.
        compile_store.disable()
        return None
    if path is None and output_dir:
        import os

        path = os.path.join(output_dir, "compile-store")
    if not path:
        return None
    store = compile_store.configure(path)
    logging.getLogger("photon_tpu.cli").info(
        "AOT compile store: %s (%d recorded signature(s))",
        store.root, len(store.entries()))
    return store


def add_trace_flag(parser) -> None:
    """Shared --trace-out flag (default: $PHOTON_TRACE_OUT): write the
    run's spans — ingest blocks, coordinate steps, optimizer solves, the
    serving path, injected faults — as Chrome trace-event JSON, loadable
    in Perfetto (docs/observability.md)."""
    import os

    parser.add_argument(
        "--trace-out",
        default=os.environ.get("PHOTON_TRACE_OUT") or None,
        help="write an end-to-end Chrome trace-event JSON timeline of this "
             "run to this file (open in https://ui.perfetto.dev; "
             "docs/observability.md; default: $PHOTON_TRACE_OUT)")


def enable_trace(path) -> None:
    """Install the process-wide trace collector (no-op if falsy); pair
    with :func:`finish_trace` in a ``finally``."""
    if not path:
        return
    from photon_tpu.obs import start_tracing

    start_tracing()


def finish_trace(path) -> None:
    """Write and uninstall the collector installed by :func:`enable_trace`
    (no-op if falsy). Runs in the driver's ``finally`` so a failed run
    still leaves a timeline — failures are when the trace matters most."""
    if not path:
        return
    import logging

    from photon_tpu.obs import stop_tracing

    col = stop_tracing(path)
    if col is not None:
        logging.getLogger("photon_tpu.obs").info(
            "trace written: %s (%d events%s)", path, len(col.events),
            f", {col.dropped} dropped" if col.dropped else "",
        )


def add_telemetry_flag(parser) -> None:
    """Shared --telemetry-dir flag (default: $PHOTON_TELEMETRY_DIR): the
    fleet-observability convention (docs/observability.md §"Fleet view").
    Every cooperating process of one run points here; each writes its
    trace shard (``trace.<role>.<pid>.json``) and metrics-registry shard
    (``registry.<role>.<pid>.json``) into the shared directory, and
    ``python -m photon_tpu.obs.analysis report <dir>`` fuses them into
    one merged timeline + run report."""
    import os

    parser.add_argument(
        "--telemetry-dir",
        default=os.environ.get("PHOTON_TELEMETRY_DIR") or None,
        help="shared fleet-telemetry directory: this process writes its "
             "trace shard and metrics-registry shard here under the "
             "fleet naming convention, mergeable across processes by "
             "`python -m photon_tpu.obs.analysis report` "
             "(docs/observability.md §'Fleet view'; default: "
             "$PHOTON_TELEMETRY_DIR)")


def enable_telemetry(args, role: str):
    """Install the fleet-telemetry convention for this process: stamp its
    ROLE (carried by every trace anchor, whether or not a telemetry dir
    is set), and under ``--telemetry-dir`` default ``--trace-out`` into
    the shard layout so the trace lands where the aggregator looks.
    Returns the telemetry dir (or None). Call BEFORE enable_trace — the
    anchor is stamped at collector install."""
    import os

    from photon_tpu.obs import trace

    trace.set_process_role(role)
    d = getattr(args, "telemetry_dir", None)
    if not d:
        return None
    os.makedirs(d, exist_ok=True)
    if getattr(args, "trace_out", None) is None:
        args.trace_out = os.path.join(
            d, f"trace.{role}.{os.getpid()}.json")
    return d


def finish_telemetry(args, registries=()) -> None:
    """Export this process's metrics-registry shard into the telemetry
    dir (no-op without ``--telemetry-dir``). Runs in the driver's
    ``finally`` — a failed run's counters are exactly the ones the run
    report needs. Best-effort by contract: telemetry is evidence, never
    a new failure mode."""
    d = getattr(args, "telemetry_dir", None)
    if not d:
        return
    import logging
    import os

    from photon_tpu.obs import fleet, trace

    path = os.path.join(
        d, f"registry.{trace.process_role()}.{os.getpid()}.json")
    try:
        fleet.write_registry_shard(path, registries=list(registries))
    except Exception as e:  # noqa: BLE001 - evidence, never a failure mode
        logging.getLogger("photon_tpu.obs").warning(
            "registry shard export failed (%s): %s", path, e)


def add_re_routing_flags(parser) -> None:
    """Shared random-effect solver-routing flags (docs/scaling.md §"Solver
    routing"): ``--re-routing`` picks between the deterministic static gate
    ladder and the measured cost-model router; ``--re-cost-table`` persists
    the calibration results alongside the model so a warm restart skips
    the race AND reproduces the original routing decisions (a re-raced
    timing winner could differ and break bit-identical resume)."""
    import os

    parser.add_argument(
        "--re-routing", choices=["static", "measured"],
        default=os.environ.get("PHOTON_RE_ROUTING") or "static",
        help="random-effect bucket solver routing: 'static' = deterministic "
             "eligibility gates (primal/dual Newton, chunked tiers, vmapped "
             "fallback); 'measured' = per-bucket-shape cost table seeded by "
             "a one-time calibration race on the first sweep "
             "(game/solver_routing.py; default: $PHOTON_RE_ROUTING or "
             "static)")
    parser.add_argument(
        "--re-cost-table",
        default=os.environ.get("PHOTON_RE_COST_TABLE") or None,
        help="JSON file for the measured-routing cost table (loaded at "
             "startup if present, saved after every calibration race); "
             "defaults to <output-dir>/solver_costs.json under "
             "--re-routing measured (default: $PHOTON_RE_COST_TABLE)")
    parser.add_argument(
        "--clear-caches-per-config", action="store_true",
        default=os.environ.get("PHOTON_CLEAR_CACHES_PER_CONFIG") == "1",
        help="drop jax's compiled-executable caches at every optimization-"
             "config (λ) boundary: bounds the mmap'd JIT code-page growth "
             "that otherwise creeps toward vm.max_map_count and segfaults "
             "multi-day runs (supervisor.MapCountWatchdog warns; this flag "
             "acts). Off by default — in-core sweeps reuse executables "
             "across λ values when shapes repeat")


def enable_re_routing(args, output_dir=None) -> None:
    """Install the routing flags process-wide (env is the contract the
    bucket solver reads — see game/solver_routing.py). Under measured
    routing with no explicit table path, the table persists alongside the
    model in ``output_dir``."""
    import logging
    import os

    os.environ["PHOTON_RE_ROUTING"] = args.re_routing
    table = args.re_cost_table
    if table is None and args.re_routing == "measured" and output_dir:
        table = os.path.join(output_dir, "solver_costs.json")
    if table:
        os.environ["PHOTON_RE_COST_TABLE"] = table
        logging.getLogger("photon_tpu.cli").info(
            "RE solver routing: %s (cost table: %s%s)", args.re_routing,
            table, ", resuming" if os.path.exists(table) else "",
        )
    if getattr(args, "clear_caches_per_config", False):
        os.environ["PHOTON_CLEAR_CACHES_PER_CONFIG"] = "1"


def add_backend_policy_flag(parser) -> None:
    """Shared --backend-policy flag (default: $PHOTON_BACKEND_POLICY or
    'strict'): what to do when the accelerator backend fails its health
    probe (docs/robustness.md §"Backend-failure resilience"). The probe
    runs subprocess-isolated under the PHOTON_BACKEND_INIT_TIMEOUT_S hard
    deadline (default 120 s), so no entrypoint can hang ~25 minutes inside
    a wedged backend init."""
    import os

    parser.add_argument(
        "--backend-policy", choices=["strict", "failover", "cpu-only"],
        default=os.environ.get("PHOTON_BACKEND_POLICY") or "strict",
        help="on a failed backend health probe: 'strict' = classified "
             "error + nonzero exit (never silently train on the wrong "
             "hardware); 'failover' = re-enter on CPU with the swap "
             "stamped into provenance (artifacts resolve to backend=cpu); "
             "'cpu-only' = pin the CPU backend, never touch the "
             "accelerator (default: $PHOTON_BACKEND_POLICY or strict)")


def add_distributed_flags(parser) -> None:
    """Shared --distributed-policy flag (default: $PHOTON_DISTRIBUTED_POLICY
    or 'strict'): what to do when multi-host bring-up
    (``jax.distributed.initialize``) fails — coordinator unreachable, rank
    mismatch, preempted peer (docs/scaling.md §"Multi-host mesh"). Either
    way the failure is classified, counted, and journaled
    (``distributed_init_failed``); the policy only decides whether the
    process dies or degrades to single-host."""
    import os

    parser.add_argument(
        "--distributed-policy", choices=["strict", "degrade"],
        default=os.environ.get("PHOTON_DISTRIBUTED_POLICY") or "strict",
        help="on failed multi-host bring-up: 'strict' = classified error + "
             "exit 2 (a silent 1/N-sized mesh must never masquerade as the "
             "pod); 'degrade' = journal the failure and continue "
             "single-host (default: $PHOTON_DISTRIBUTED_POLICY or strict)")


def enable_backend_guard(args, logger=None) -> dict:
    """Enforce --backend-policy before any in-process backend init. A
    probe that already passed in this process is not repeated (driver
    re-entries and test suites stay fast); a failed probe under 'strict'
    raises BackendUnusable, which the console entry surfaces as a
    classified one-line error and a nonzero exit."""
    import logging

    from photon_tpu.runtime.backend_guard import ensure_backend

    return ensure_backend(
        policy=getattr(args, "backend_policy", "strict"),
        logger=logger or logging.getLogger("photon_tpu.runtime"),
    )


def console_main(run_fn) -> None:
    """Console-entry wrapper shared by the drivers: a failed backend
    health probe under --backend-policy strict exits with ONE classified
    line and status 2 — the operator (and the scheduler's log scraper)
    gets `fatal [init_unavailable]: ...`, not a 40-frame traceback ending
    in a jaxlib internal."""
    import sys

    from photon_tpu.runtime.backend_guard import BackendUnusable

    try:
        run_fn()
    except BackendUnusable as e:
        print(f"fatal [{e.cause}]: {e.reason}", file=sys.stderr)
        raise SystemExit(2) from None


def add_fault_plan_flag(parser) -> None:
    """Shared --fault-plan flag (default: $PHOTON_FAULT_PLAN): run the
    driver under a deterministic fault-injection plan for chaos drills
    (docs/robustness.md). Never set in production."""
    import os

    parser.add_argument(
        "--fault-plan",
        default=os.environ.get("PHOTON_FAULT_PLAN") or None,
        help="JSON FaultPlan file (photon_tpu.faults): inject seeded "
             "faults — IO errors, preemptions, store latency — at the "
             "framework's hook points to rehearse recovery paths "
             "(default: $PHOTON_FAULT_PLAN)")


def enable_fault_plan(path) -> None:
    """Install the plan file process-wide (no-op if falsy)."""
    if not path:
        return
    import logging

    from photon_tpu.faults import install_from_file

    install_from_file(path)
    logging.getLogger("photon_tpu.faults").warning(
        "FAULT INJECTION ACTIVE: plan %s (chaos drill — not production)",
        path,
    )
