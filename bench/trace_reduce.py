"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

The harness marks the measured window with a host span named ``window``,
and each layer it calls with a span of the layer's name.  From the planes
of the TPU chips the cell uses (``/device:TPU:<id>``; the host's other
chips are left out) this module takes:

  busy_s      length of the union of the intervals in which an operation
              ran on the device (line ``XLA Ops``), inside the window,
              averaged over those planes
  window_s    length of the window span
  modules     device seconds of each compiled module (line ``XLA
              Modules``) inside the window, summed over the planes, keyed
              by the module's name without its ``(id)`` suffix
  ops         device seconds of each operation, by name, in the window
  idle        idle device seconds in the window, by the innermost layer
              span the host was in at the middle of each gap (``host``
              where it was in none)
  job_busy_s  busy seconds of each job in the window, a job running from
              the start of one outermost ``job`` span to the start of the
              next (or the window's end), averaged over the planes

Every job of a run is the same job, so each holds the same device time;
`complete` reads a job with less as a trace that lost events (the profiler
drops trace buffers past its limit).
"""
from __future__ import annotations

import re
from collections import defaultdict

import numpy as np

WINDOW = "window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_SUFFIX = re.compile(r"\(\d+\)$")


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(str(path))


def _events(line) -> list[tuple[str, float, float]]:
    return [(e.name, float(e.start_ns), float(e.start_ns) + float(e.duration_ns))
            for e in line.events]


def merge(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Union of intervals as sorted disjoint intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def reduce(pd, spans: tuple[str, ...], device_ids: list[int],
           job: str | None = None) -> dict:
    """The numbers above from a loaded trace; ``spans`` names the layer
    spans to attribute idle time to, ``device_ids`` the chips used, ``job``
    the span that opens each job."""
    host = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [ev for ev in _events(line) if ev[0] in (WINDOW,) + spans]
    windows = [ev for ev in host if ev[0] == WINDOW]
    if not windows:
        raise ValueError("the trace holds no window span")
    _, w0, w1 = max(windows, key=lambda ev: ev[2] - ev[1])
    layer = sorted((ev for ev in host if ev[0] in spans), key=lambda ev: ev[1])
    starts, end = [], w0  # the outermost ``job`` spans in the window
    for name, s, e in layer:
        if name == job and end <= s < w1:
            starts.append(s)
            end = e
    job_busy = [0.0] * len(starts)
    busy, modules, ops = [], defaultdict(float), defaultdict(float)
    idle = defaultdict(float)
    planes = [p for p in pd.planes if (m := _DEVICE_PLANE.match(p.name))
              and int(m.group(1)) in device_ids]
    for plane in planes:
        lines = {line.name: line for line in plane.lines}
        op_iv = []
        if "XLA Ops" in lines:
            for name, s, e in _events(lines["XLA Ops"]):
                for cs, ce in clip([(s, e)], w0, w1):
                    ops[name] += (ce - cs) * 1e-9
                    op_iv.append((cs, ce))
        if "XLA Modules" in lines:
            for name, s, e in _events(lines["XLA Modules"]):
                for cs, ce in clip([(s, e)], w0, w1):
                    modules[_SUFFIX.sub("", name)] += (ce - cs) * 1e-9
        merged = merge(op_iv)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        for i, (lo, hi) in enumerate(zip(starts, starts[1:] + [w1])):
            job_busy[i] += sum(e - s for s, e in clip(merged, lo, hi)) \
                * 1e-9 / len(planes)
        for gs, ge in _gaps(merged, w0, w1):
            idle[_span_at(layer, (gs + ge) / 2)] += (ge - gs) * 1e-9 / len(planes)
    return {
        "busy_s": float(np.mean(busy)) if busy else 0.0,
        "window_s": (w1 - w0) * 1e-9,
        "device_planes": len(planes),
        "modules": dict(modules),
        "ops": dict(ops),
        "idle": dict(idle),
        "job_busy_s": job_busy,
    }


def complete(reduced: dict) -> bool:
    """Whether every job of the window holds at least nine tenths of the
    median job's device time."""
    busy = sorted(reduced["job_busy_s"])
    return not busy or busy[0] >= 0.9 * busy[len(busy) // 2]


def _gaps(merged, lo: float, hi: float) -> list[tuple[float, float]]:
    out, cur = [], lo
    for s, e in merged:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


def _span_at(spans, t: float) -> str:
    """The innermost (latest-starting) span that contains ``t``."""
    inner = "host"
    for name, s, e in spans:
        if s > t:
            break
        if e >= t:
            inner = name
    return inner


def module_seconds(reduced: dict, name: str) -> float | None:
    """Device seconds of the modules named ``name`` (None where none ran)."""
    hits = [v for k, v in reduced["modules"].items() if k == name]
    return sum(hits) if hits else None


def top(table: dict, n: int = 10) -> list[list]:
    return [[k, v] for k, v in sorted(table.items(), key=lambda kv: -kv[1])[:n]]
