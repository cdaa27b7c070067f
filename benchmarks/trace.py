"""From a profiler trace to numbers: busy and idle time of the device, time
per program, the operations that took most time, and what the host was
doing in the longest idle gaps.

The reduction works on plain data, ``{plane name: {line name: [(event
name, start ns, duration ns), ...]}}``, so that a test can hand it a trace
built by hand; ``load`` makes that from the ``.xplane.pb`` the JAX profiler
writes, with nothing but JAX. Names of planes and lines are the TPU
profiler's (looked at by hand on a v5e trace, PR 25):

* a device plane is ``/device:TPU:<n>``; its ``XLA Ops`` line holds one
  event per executed HLO operation, nested where an operation (a ``while``)
  contains others; its ``XLA Modules`` line one event per executed program,
  named ``<jit name>(<fingerprint>)``;
* the host plane is ``/host:CPU``; the benchmark's own annotations
  (``bench.*``, from ``jax.profiler.TraceAnnotation``) are events on the
  lines of its threads, on the same clock.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Iterable

Event = tuple          # (name, start_ns, duration_ns)
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SHORT_GAP_NS = 20_000.0
_DEVICE = re.compile(r"^/device:TPU:\d+$")


def load(trace_dir: str) -> dict:
    """The newest ``.xplane.pb`` under ``trace_dir`` as plain data."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    out: dict = {}
    for plane in data.planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (e.name, float(e.start_ns), float(e.duration_ns))
                for e in line.events)
    return out


def device_planes(trace: dict) -> list[str]:
    return sorted(p for p in trace if _DEVICE.match(p))


def clip(events: Iterable[Event], t0: float, t1: float) -> list[Event]:
    """Events cut to the window ``[t0, t1]``; those outside it dropped."""
    out = []
    for name, start, dur in events:
        a, b = max(start, t0), min(start + dur, t1)
        if b > a:
            out.append((name, a, b - a))
    return out


def union_intervals(events: Iterable[Event]) -> list[tuple[float, float]]:
    """Merged ``(start, end)`` intervals covered by any event."""
    merged: list[list[float]] = []
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], start + dur)
        else:
            merged.append([start, start + dur])
    return [(a, b) for a, b in merged]


def busy_ns(events: Iterable[Event]) -> float:
    return sum(b - a for a, b in union_intervals(events))


def gaps(intervals: list[tuple[float, float]], t0: float, t1: float
         ) -> list[tuple[float, float]]:
    """The parts of ``[t0, t1]`` that no interval covers."""
    out, at = [], t0
    for a, b in intervals:
        if a > at:
            out.append((at, min(a, t1)))
        at = max(at, b)
        if at >= t1:
            break
    if at < t1:
        out.append((at, t1))
    return [(a, b) for a, b in out if b > a]


def self_times(events: Iterable[Event]) -> dict:
    """Seconds per event name, each event counted without the events nested
    inside it on the same line (a ``while`` without its body), so that the
    names add up to the line's busy time."""
    total: dict = {}
    stack: list[list] = []          # [name, end, self_ns]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, _, self_ns = stack.pop()
            total[name] = total.get(name, 0.0) + self_ns

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return {k: v / 1e9 for k, v in total.items()}


def op_name(event_name: str) -> str:
    """``%fusion.45 = f32[10469376,128]{1,0:T(8,128)} fusion(...), kind=kCustom``
    -> ``fusion.45 f32[10469376,128] kCustom``: the operation, the shape it
    produces and its kind, without the operands."""
    m = re.match(r"^%?(\S+) = (\(?[a-z0-9]+\[[0-9,]*\])", event_name)
    if not m:
        return event_name[:80]
    kind = re.search(r"kind=(\w+)", event_name)
    return " ".join(filter(None, [m.group(1), m.group(2).lstrip("("),
                                  kind.group(1) if kind else ""]))


def module_name(event_name: str) -> str:
    """``jit__fit_jitted(1234)`` -> ``jit__fit_jitted``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def module_seconds(trace: dict, t0: float, t1: float) -> dict:
    """Device seconds per program name inside the window, averaged over the
    device planes."""
    planes = device_planes(trace)
    out: dict = {}
    for p in planes:
        for name, _, dur in clip(trace[p].get(MODULES_LINE, []), t0, t1):
            key = module_name(name)
            out[key] = out.get(key, 0.0) + dur / 1e9 / len(planes)
    return out


def annotations(trace: dict, prefix: str = "bench.") -> list[Event]:
    """The benchmark's own host spans, from every thread of the host."""
    return [e for events in trace.get(HOST_PLANE, {}).values()
            for e in events if e[0].startswith(prefix)]


def window(trace: dict, name: str = "bench.window") -> tuple[float, float]:
    """Start and end (ns) of the span the harness put around the traced
    window."""
    spans = [e for e in annotations(trace) if e[0] == name]
    if not spans:
        raise ValueError(f"no {name!r} span in the trace")
    _, start, dur = max(spans, key=lambda e: e[2])
    return start, start + dur


def _innermost(spans: list[Event], t: float) -> str:
    covering = [s for s in spans if s[1] <= t <= s[1] + s[2]]
    return min(covering, key=lambda s: s[2])[0] if covering else "(no span)"


def reduce(trace: dict, top: int = 10) -> dict:
    """``busy_s`` and ``window_s`` (averaged over the device planes), the
    ``top`` operations by device self time and the ``top`` kinds of idle gap
    by what the host was doing, and device seconds per program."""
    planes = device_planes(trace)
    if not planes:
        raise ValueError("the trace has no /device:TPU:<n> plane")
    t0, t1 = window(trace)
    spans = [s for s in annotations(trace) if s[0] != "bench.window"]
    busy = 0.0
    ops: dict = {}
    idle: dict = {}
    for p in planes:
        events = clip(trace[p].get(OPS_LINE, []), t0, t1)
        merged = union_intervals(events)
        busy += sum(b - a for a, b in merged) / 1e9 / len(planes)
        for name, s in self_times(events).items():
            key = op_name(name)
            ops[key] = ops.get(key, 0.0) + s / len(planes)
        for a, b in gaps(merged, t0, t1):
            key = (_innermost(spans, 0.5 * (a + b)) if b - a >= SHORT_GAP_NS
                   else "(gaps under 20 us between operations)")
            idle[key] = idle.get(key, 0.0) + (b - a) / 1e9 / len(planes)

    def ranked(d: dict) -> list:
        return [[k, v] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {
        "busy_s": busy,
        "window_s": (t1 - t0) / 1e9,
        "device_ops": ranked(ops),
        "idle_gaps": ranked(idle),
        "module_s": module_seconds(trace, t0, t1),
    }
