"""In-memory spans and counters of the toolchain's hot path.

``span(name)`` times a block and records it with its parent, its children
and its counters.  It also opens a ``jax.profiler.TraceAnnotation`` of the
same name, so a profiler trace holds the same spans with the same nesting.
Times are wall-clock nanoseconds (``time.time_ns``), the clock of the
profiler's host plane: a trace event starts at its ``start_ns`` plus the
``profile_start_time`` stat of the trace's ``Task Environment`` plane, so
a device-trace gap can be put against any span.

``count(name, value)`` adds to a counter of the innermost open span.  A
``jax.monitoring`` listener adds ``compiles`` (programs XLA compiled),
``cache_loads`` (programs loaded from the persistent compile cache) and
``compile_s`` (the seconds both took) to it; every span starts with these
three at 0.

Completed root spans are kept, the newest ``KEEP``, for ``recent``.  One
stack of open spans serves the process: the toolchain opens its spans from
one thread.
"""
from __future__ import annotations

import collections
import contextlib
import time
from dataclasses import dataclass, field
from typing import Iterator

__all__ = ["Span", "count", "recent", "span"]

KEEP = 256
_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


@dataclass(eq=False)
class Span:
    name: str
    parent: "Span | None"
    start_ns: int
    end_ns: int = 0
    counters: dict = field(default_factory=lambda: {
        "compiles": 0, "cache_loads": 0, "compile_s": 0.0})
    children: list = field(default_factory=list)

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9

    def find(self, name: str) -> list["Span"]:
        """The spans named ``name`` in this span's tree, itself included,
        in the order they started."""
        own = [self] if self.name == name else []
        return own + [s for c in self.children for s in c.find(name)]

    def total(self, counter: str) -> float:
        """``counter`` summed over this span's tree."""
        return self.counters.get(counter, 0) + sum(
            c.total(counter) for c in self.children)


_open: list[Span] = []
_roots: collections.deque = collections.deque(maxlen=KEEP)
_listening = False
_cache_hit = False  # the next compile event is the cache load just counted


@contextlib.contextmanager
def span(name: str) -> Iterator[Span]:
    """Record the block as a span named ``name`` and yield it."""
    # JAX is imported here, not at module import: the numpy NoC replay
    # counts into spans without ever importing it.
    import jax

    _listen(jax)
    parent = _open[-1] if _open else None
    s = Span(name, parent, time.time_ns())
    _open.append(s)
    try:
        with jax.profiler.TraceAnnotation(name):
            yield s
    finally:
        s.end_ns = time.time_ns()
        _open.pop()
        if parent is None:
            _roots.append(s)
        else:
            parent.children.append(s)


def count(name: str, value: float) -> None:
    """Add ``value`` to counter ``name`` of the innermost open span (a no-op
    where no span is open)."""
    if _open:
        c = _open[-1].counters
        c[name] = c.get(name, 0) + value


def recent(name: str, n: int) -> list[Span] | None:
    """The last ``n`` completed root spans named ``name``, oldest first, or
    None where fewer were recorded."""
    hits = [s for s in _roots if s.name == name]
    return hits[len(hits) - n:] if 0 < n <= len(hits) else None


def _listen(jax) -> None:
    global _listening
    if not _listening:
        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True


def _on_event(event: str, **_) -> None:
    global _cache_hit
    if event == _CACHE_HIT:
        _cache_hit = True
        count("cache_loads", 1)


def _on_duration(event: str, duration: float, **_) -> None:
    # JAX reports a program loaded from the persistent cache as a compile
    # too, right after the cache hit.
    global _cache_hit
    if event == _COMPILE:
        count("compile_s", duration)
        if not _cache_hit:
            count("compiles", 1)
        _cache_hit = False
