#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which alone touches JAX. It finds the cell in
``BENCHMARK.json``, the cell's configuration in ``benchmarks/configs/``, its
job mix in ``benchmarks/traffic/`` and the limits of its comparison in
``benchmarks/limits/``, hands them to the mix's kind
(``benchmarks/kinds/<kind>.py``) and prints what that returns as the last
line of standard output. With ``--trace 1`` the window runs under the JAX
profiler and the line holds the cell's per-layer metrics, each read by
``benchmarks/layer_metrics/<name>.py``.

No accelerator, or fewer chips than the cell asks for: one line on standard
error, exit 1, no result line. There is no CPU path here; the tests drive
``kinds/<kind>.py`` directly.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
ACCELERATORS = ("tpu",)


def load_json(*parts: str) -> dict:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json")


def applies(metric: dict, cell: dict, bench: dict) -> bool:
    """Whether ``cell`` reports ``metric``: it is listed under the metric's
    ``workloads``, or the metric lists none and (per-layer) the cell reports
    the end-to-end metric it moves."""
    if "workloads" in metric:
        return cell["name"] in metric["workloads"]
    if "moves" not in metric:
        return True
    moved = next(m for m in bench["end_to_end"] if m["name"] == metric["moves"])
    return applies(moved, cell, bench)


def device_info(memory_peak_bytes: int) -> dict:
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": int(memory_peak_bytes)}


def peak_for(kind: str) -> dict:
    peaks = load_json("benchmarks", "peaks.json")
    if kind not in peaks:
        raise SystemExit(f"run.py: no peaks for device kind {kind!r} in "
                         "benchmarks/peaks.json")
    return peaks[kind]


def per_layer(bench: dict, cell: dict, state: dict) -> dict:
    out = {}
    for metric in bench["per_layer"]:
        if not applies(metric, cell, bench):
            continue
        reader = importlib.import_module(
            f"benchmarks.layer_metrics.{metric['name']}")
        value = reader.read(state)
        if value is not None:
            out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return out


def run_cell(bench: dict, cell: dict, config: dict, mix: dict, limits: dict,
             seed: int, seconds: float, traced: bool, say) -> dict:
    """Everything after the look for a chip: the kind's run, the metrics
    the cell reports, and the result line (returned, not printed)."""
    trace_dir = None
    if traced:
        trace_dir = os.path.join(HERE, "_trace", cell["name"])
        shutil.rmtree(trace_dir, ignore_errors=True)
    kind = importlib.import_module(f"benchmarks.kinds.{mix['kind']}")
    out = kind.run(cell, config, mix, limits, seed, seconds, trace_dir,
                   T_START, say)

    line = {"correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"]}
    device = device_info(out["state"]["memory_peak_bytes"])
    if traced:
        from benchmarks import trace

        reduced = trace.reduce(trace.load(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        state = dict(out["state"], trace=reduced,
                     peak=peak_for(device["kind"]))
        line["metrics"] = per_layer(bench, cell, state)
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
        say({"module_s": reduced["module_s"]})
    else:
        line["metrics"] = {
            m["name"]: {"value": out["end_to_end"][m["name"]], "unit": m["unit"]}
            for m in bench["end_to_end"] if applies(m, cell, bench)}
    line["device"] = device
    line["compared"] = out["compared"]
    return line


def say(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # The checkout, not benchmarks/ (whose trace.py would shadow the
    # standard library's).
    sys.path[0] = ROOT
    bench = load_json("BENCHMARK.json")
    cell = find_cell(bench, args.workload)
    config_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(config_entry["file"])
    mix = load_json("benchmarks", "traffic", cell["traffic"] + ".json")
    limits = load_json("benchmarks", "limits", cell["name"] + ".json")

    import jax

    devices = jax.devices()
    if devices[0].platform not in ACCELERATORS or len(devices) < cell["chips"]:
        print(f"run.py: {cell['name']} needs {cell['chips']} accelerator "
              f"chip(s); JAX found {len(devices)} x {devices[0].platform}",
              file=sys.stderr)
        return 1
    peak_for(devices[0].device_kind)       # an unknown device is an error

    line = run_cell(bench, cell, config, mix, limits, args.seed,
                    args.seconds, bool(args.trace), say)
    for name, (value, limit) in line["compared"].items():
        print(f"compared {name}: {value:.6g} (limit {limit:g})",
              file=sys.stderr)
    sys.stderr.flush()
    say(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
