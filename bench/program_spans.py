"""The program's own spans of the window's jobs (`repro.telemetry`).

Each job records a root span ``profile`` (child ``lif_scan``) and a root
span ``toolchain`` (children ``partition``, ``mapping`` and ``evaluate``;
``evaluate`` holds ``stepper`` where the device replay stepper ran).  A
program without the recorder, or without the span asked for, gives None
or an empty list, and the metric that reads it is left out.
"""
from __future__ import annotations


def roots(ctx: dict, name: str) -> list | None:
    """The window's root spans named ``name``, one per job, or None."""
    try:
        from repro import telemetry
    except ImportError:  # a program that records no spans
        return None
    return telemetry.recent(name, len(ctx["jobs"]))


def find(ctx: dict, root: str, name: str) -> list:
    """Every span named ``name`` under the window's roots named ``root``."""
    return [s for r in roots(ctx, root) or () for s in r.find(name)]


def host_seconds(ctx: dict, root: str, name: str, device: str) -> float | None:
    """Mean seconds per job of the spans named ``name`` less their
    children named ``device``, or None where there is no such span."""
    spans = find(ctx, root, name)
    if not spans:
        return None
    host = sum(s.seconds - sum(c.seconds for c in s.children if c.name == device)
               for s in spans)
    return host / len(ctx["jobs"])
