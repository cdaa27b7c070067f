"""The device's idle seconds a fit, put down to the program's own spans:
what ``benchmarks/trace.py`` does by the harness's ``bench.*`` wrappers,
done by the spans of a fit's tree (``docs/observability.md`` "A fit's span
tree"; ``PERF.md`` §5 holds the tables this prints).

    python scripts/idle_by_span.py <cell> [--fits n] [--seed s]
    python scripts/idle_by_span.py <cell> --reads [--seed s]

It builds the cell's estimator and data as the cell's kind does
(``benchmarks/kinds/<kind>.py`` ``generate`` and ``build``; no ``Probe``,
so the harness's wrappers make no span), runs a warm-up fit, then ``n``
fits under ``jax.profiler.trace`` with the options ``kinds/fit.py`` uses.
Every idle gap of the device is split over the innermost program span open
on the fit's thread while it lasts (the names of the fits' kept trees, as
their annotations carry them: ``device.wait:<site>``,
``validate.evaluate:<evaluator>``). Beside a span's idle seconds stand the
seconds the host spent with that span innermost, and of the idle seconds
those that lie in gaps under 20 us (between the operations of a running
program). The arithmetic of intervals is ``benchmarks/trace.py``'s. The
last line is one JSON object; the table goes to ``chiprun_out/`` too.

``--reads`` makes the check of ISSUE 38 instead: one fit under
``jax.transfer_guard_device_to_host("log")`` with standard error kept, in
which every span of the fit writes a marker, so that each transfer the
guard logs falls to the span open around it; then one fit in which a read
outside a ``device.wait`` is disallowed, so that its traceback names the
call site. Exit 1 if a read lies outside a ``device.wait``.

Off a chip the profile has no device plane and the guard sees no transfer:
exit 1, one line on standard error.
"""
import argparse
import importlib
import json
import os
import sys
import tempfile
import traceback

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import datagen, run as bench_run  # noqa: E402
from benchmarks import trace as bench_trace  # noqa: E402
from benchmarks.kinds import fit as fit_kind  # noqa: E402

WINDOW = "bench.window"          # the name ``benchmarks.trace.window`` finds
OUTSIDE = "(outside a fit)"
WAIT = "device.wait"
READERS = ("device_wait_s", "fixed_host_s", "re_host_s", "re_inputs_s",
           "re_dispatch_s", "validate_host_s", "validate_score_s",
           "descent_host_s", "validate_s")


def cell_files(name: str) -> tuple[dict, dict]:
    """(configuration, job mix) of the cell ``name``, found as
    ``benchmarks/run.py`` finds them."""
    bench = bench_run.load_json("BENCHMARK.json")
    cell = bench_run.find_cell(bench, name)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return (bench_run.load_json(entry["file"]),
            bench_run.load_json("benchmarks", "traffic",
                                cell["traffic"] + ".json"))


def build(config: dict, mix: dict, seed: int):
    """(estimator, training bundle, validation bundle, optimization
    configurations), from the kind's own generator and builder where it
    has one and ``kinds/fit.py``'s where it has not."""
    from photon_tpu.runtime import compile_store

    compile_store.enable_compilation_cache(min_compile_secs=0.0)
    kind = importlib.import_module(f"benchmarks.kinds.{mix['kind']}")
    ds = getattr(kind, "generate", datagen.generate)(config["data"], seed)
    return getattr(kind, "build", fit_kind.build)(config, ds)


def innermost_segments(spans: list) -> list:
    """``[(start, end, name)]`` in time order: the stretches over which one
    span of ``spans`` (``(name, start ns, duration ns)``, nested as one
    thread enters them) is the innermost one open."""
    segments, stack, at = [], [], 0.0

    def close(upto: float) -> None:
        nonlocal at
        while stack and stack[-1][1] <= upto:
            name, end = stack.pop()
            if end > at:
                segments.append((at, end, name))
                at = end

    for name, start, dur in sorted(spans, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack and start > at:
            segments.append((at, start, stack[-1][0]))
        at = max(at, start)
        stack.append((name, start + dur))
    close(float("inf"))
    return segments


def attribute(trace: dict, names: set) -> dict:
    """Busy, window and idle seconds of the traced window, the idle ones by
    the innermost span open (``by_span``: name -> ``{"idle_s", "short_s",
    "host_s"}``), averaged over the device planes. ``names`` are the span
    names of a fit's tree; an annotation ``<name>:<label>`` counts under
    its whole name."""
    planes = bench_trace.device_planes(trace)
    if not planes:
        raise ValueError("the trace has no /device:TPU:<n> plane")
    t0, t1 = bench_trace.window(trace, WINDOW)
    spans = bench_trace.clip(
        (e for e in bench_trace.annotations(trace, prefix="")
         if e[0].split(":")[0] in names), t0, t1)
    segments = innermost_segments(spans)
    by_span: dict = {}

    def row(name: str) -> dict:
        return by_span.setdefault(
            name, {"idle_s": 0.0, "short_s": 0.0, "host_s": 0.0})

    covered = 0.0
    for a, b, name in segments:
        row(name)["host_s"] += (b - a) / 1e9
        covered += b - a
    row(OUTSIDE)["host_s"] += ((t1 - t0) - covered) / 1e9
    busy = 0.0
    for p in planes:
        merged = bench_trace.union_intervals(
            bench_trace.clip(trace[p].get(bench_trace.OPS_LINE, []), t0, t1))
        busy += sum(b - a for a, b in merged) / 1e9 / len(planes)
        i = 0
        for a, b in bench_trace.gaps(merged, t0, t1):
            short = b - a < bench_trace.SHORT_GAP_NS
            while i < len(segments) and segments[i][1] <= a:
                i += 1
            at, j = a, i
            while at < b:
                if j < len(segments) and segments[j][0] <= at:
                    upto, name = min(segments[j][1], b), segments[j][2]
                    j += 1
                else:
                    upto = min(segments[j][0], b) if j < len(segments) else b
                    name = OUTSIDE
                r = row(name)
                r["idle_s"] += (upto - at) / 1e9 / len(planes)
                if short:
                    r["short_s"] += (upto - at) / 1e9 / len(planes)
                at = upto
    return {"busy_s": busy, "window_s": (t1 - t0) / 1e9, "by_span": by_span}


def profile(config: dict, mix: dict, seed: int, fits: int) -> tuple:
    """(the profile of ``fits`` fits as plain data, their kept trees)."""
    import jax

    from photon_tpu.obs.trace import recent_trees

    estimator, train, validation, opt_configs = build(config, mix, seed)
    estimator.fit(train, validation, opt_configs)            # warm-up
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir, profiler_options=options):
            with jax.profiler.TraceAnnotation(WINDOW):
                for _ in range(fits):
                    estimator.fit(train, validation, opt_configs)
        return (bench_trace.load(trace_dir),
                recent_trees("estimator.fit", fits))


def report(cell: str, config: dict, seed: int, reduced: dict, trees: list
           ) -> dict:
    """The table, printed, and the same as one object."""
    fits = len(trees)
    idle = sum(r["idle_s"] for r in reduced["by_span"].values())
    rows = sorted(reduced["by_span"].items(), key=lambda kv: -kv[1]["idle_s"])
    print(f"{cell}: {fits} fits, window {reduced['window_s']:.3f} s, busy "
          f"{reduced['busy_s'] / fits:.4f} s a fit, idle {idle / fits:.4f} "
          f"s a fit ({100 * idle / reduced['window_s']:.1f}%)")
    print(f"{'innermost span open':44} {'idle s a fit':>12} {'share':>7} "
          f"{'running':>8} {'under 20 us':>12} {'host s a fit':>12}")
    running = 0.0
    for name, r in rows:
        running += r["idle_s"]
        print(f"{name:44} {r['idle_s'] / fits:12.5f} "
              f"{100 * r['idle_s'] / idle:6.1f}% {100 * running / idle:7.1f}% "
              f"{r['short_s'] / fits:12.5f} {r['host_s'] / fits:12.5f}")
    state = {"trackers": [[]] * fits, "config": config}
    readings = {
        name: importlib.import_module(
            f"benchmarks.layer_metrics.{name}").read(state)
        for name in READERS}
    waits: dict = {}
    for tree in trees:
        for name, _, _, start, end, args in tree:
            if name == WAIT:
                site = args.get("site")
                waits[site] = waits.get(site, 0.0) + (end - start) / fits
    out = {"cell": cell, "seed": seed, "fits": fits,
           "window_s": reduced["window_s"],
           "busy_s_a_fit": reduced["busy_s"] / fits,
           "idle_s_a_fit": idle / fits, "readings": readings,
           "wait_s_a_fit_by_site": waits,
           "spans_a_fit": sum(len(t) for t in trees) / fits,
           "by_span": {name: {k: v / fits for k, v in r.items()}
                       for name, r in rows}}
    print(json.dumps(out), flush=True)
    return out


# ----------------------------------------------------------------- --reads


class _Marked:
    """While installed, every span writes a line to standard error when it
    is entered and when it ends, and a ``device.wait`` holds ``inside``
    (a context manager factory) for its length."""

    def __init__(self, inside=None, mark: bool = True):
        from photon_tpu.obs.trace import trace_span

        self.cls, self.inside, self.mark = trace_span, inside, mark
        self.enter, self.exit = trace_span.__enter__, trace_span.__exit__
        self.held: dict = {}

    def __enter__(self):
        marked = self

        def enter(span):
            if marked.inside is not None and span.name == WAIT:
                guard = marked.inside()
                guard.__enter__()
                marked.held[id(span)] = guard
            if marked.mark:
                os.write(2, f"@@ > {span._label}\n".encode())
            return marked.enter(span)

        def leave(span, *exc):
            out = marked.exit(span, *exc)
            if marked.mark:
                os.write(2, f"@@ < {span._label}\n".encode())
            guard = marked.held.pop(id(span), None)
            if guard is not None:
                guard.__exit__(*exc)
            return out

        self.cls.__enter__, self.cls.__exit__ = enter, leave
        return self

    def __exit__(self, *exc):
        self.cls.__enter__, self.cls.__exit__ = self.enter, self.exit


def logged_reads(lines: list) -> tuple[dict, dict]:
    """From the kept standard error of a fit under the guard's ``log``
    (markers ``@@ > name`` / ``@@ < name`` and the guard's own lines):
    transfers logged inside a ``device.wait``, by its site, and outside
    one, by the innermost span open."""
    inside, outside, stack = {}, {}, []
    for line in lines:
        if line.startswith("@@ > "):
            stack.append(line[5:].strip())
        elif line.startswith("@@ < "):
            if stack:
                stack.pop()
        elif "transfer" in line and "host" in line:
            top = stack[-1] if stack else OUTSIDE
            into = inside if top.startswith(WAIT) else outside
            into[top] = into.get(top, 0) + 1
    return inside, outside


def reads(config: dict, mix: dict, seed: int) -> int:
    import jax

    estimator, train, validation, opt_configs = build(config, mix, seed)
    estimator.fit(train, validation, opt_configs)            # warm-up
    sys.stderr.flush()
    kept_fd = os.dup(2)
    with tempfile.TemporaryFile() as err:
        os.dup2(err.fileno(), 2)
        try:
            with _Marked(), jax.transfer_guard_device_to_host("log"):
                estimator.fit(train, validation, opt_configs)
        finally:
            os.dup2(kept_fd, 2)
            os.close(kept_fd)
        err.seek(0)
        lines = err.read().decode(errors="replace").splitlines()
    inside, outside = logged_reads(lines)
    guard_lines = [line for line in lines if not line.startswith("@@ ")]
    print("the guard's first lines:", *guard_lines[:3], sep="\n  ")
    failed = None
    try:
        allow = lambda: jax.transfer_guard_device_to_host("allow")  # noqa: E731
        with _Marked(inside=allow, mark=False), \
                jax.transfer_guard_device_to_host("disallow"):
            estimator.fit(train, validation, opt_configs)
    except Exception:  # noqa: BLE001 - the traceback is the finding
        failed = traceback.format_exc()
        print(failed)
    print(json.dumps({"logged_inside_a_wait_by_site": inside,
                      "logged_outside_a_wait_by_span": outside,
                      "disallowed_outside_a_wait": failed is not None}),
          flush=True)
    if not inside:
        print("idle_by_span.py: the guard logged no transfer (no chip?)",
              file=sys.stderr)
        return 1
    return 1 if outside or failed else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell")
    ap.add_argument("--fits", type=int, default=3)
    ap.add_argument("--seed", type=int, default=2147483647)
    ap.add_argument("--reads", action="store_true")
    args = ap.parse_args(argv)
    config, mix = cell_files(args.cell)
    if args.reads:
        return reads(config, mix, args.seed)
    trace, trees = profile(config, mix, args.seed, args.fits)
    if not bench_trace.device_planes(trace):
        print("idle_by_span.py: the profile has no device plane; this runs "
              "on the chip", file=sys.stderr)
        return 1
    names = {s[0] for tree in trees for s in tree}
    out = report(args.cell, config, args.seed, attribute(trace, names), trees)
    os.makedirs(os.path.join(ROOT, "chiprun_out", "idle_by_span"),
                exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "idle_by_span",
                           f"{args.cell}.{args.seed}.json"), "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
