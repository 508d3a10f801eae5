"""Optional JAX device path for the joint congested-window stepper.

A ``lax.while_loop`` version of `replay._joint_stepper` for large traces,
two sorts per NoC cycle.  The first sorts the lanes by (window-tagged
link, injection cycle, lane position) and carries the lane position, so
its outputs are the sorted tags and the permutation; grants are decided in
that order.  The second sorts the grants back to lane order, keyed by the
permutation.  No gather or scatter runs through the permutation: on the
TPU either costs far more than a sort of the same length.

Packets arrive over the loop, and a lane that has arrived does no work
but is still sorted on every cycle.  So the loop runs in a fixed ladder of
power-of-two buckets, from ``m0 = next_pow2(n)`` lanes down to
``_MIN_LANES``, each ``_SHRINK`` times narrower than the one before: a
bucket steps while more lanes are live than the next bucket holds, then
one more sort moves the live lanes to the front, in lane order, and the
next bucket keeps the front.  The rest holds arrived lanes only; their
(record index, latency) pairs go back to the host.  The ladder depends on
``m0`` alone, so every bucket is dispatched before anything is read back,
and a trace of at most ``_MIN_LANES`` lanes runs a single bucket.

Grant decisions mirror the numpy stepper exactly — per window-tagged
link, the ``link_capacity`` oldest-injected packets win, stable by record
order, which the lane position keeps through every compaction — so
latencies and congestion are identical; only the execution substrate
differs.  Imported lazily by ``simulate_noc(stepper="jax")`` so the
default numpy path never pays the JAX import.

Runs under JAX's default 32-bit ints: the wrapper checks that window-tagged
link ids, cycles, and the blocked-packet count all fit, and refuses
otherwise (fall back to the numpy stepper).
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro import telemetry

__all__ = ["joint_stepper_jax"]

_SENTINEL = np.int32(2**31 - 1)
# The ladder's last bucket: below it a shrink saves too few lane-cycles
# to pay for its compaction sort and its compiled program.
_MIN_LANES = 1 << 14
# Each bucket is this many times narrower than the one before.  Every
# bucket is a program of its own, and on a v5e one with its compaction
# sort compiles in 4-90 s, so a cold start pays for each: 2^21 lanes run
# three buckets (2^21, 2^17, 2^14) rather than the eight of a halving.
_SHRINK = 16


def _ladder(m0: int) -> list[int]:
    """The bucket widths a trace padded to ``m0`` lanes steps through."""
    widths = [m0]
    while widths[-1] > _MIN_LANES:
        widths.append(max(widths[-1] // _SHRINK, _MIN_LANES))
    return widths


def _next_link_jnp(cur, dst, w: int, h: int):
    """jnp mirror of ``xy.next_link`` (single XY step -> next core, link)."""
    cx, cy = cur % w, cur // w
    dx, dy = dst % w, dst // w
    e_base = 0
    w_base = (w - 1) * h
    s_base = 2 * (w - 1) * h
    n_base = s_base + w * (h - 1)

    go_e = cx < dx
    go_w = cx > dx
    go_s = (cx == dx) & (cy < dy)
    go_n = (cx == dx) & (cy > dy)

    nxt = cur
    link = jnp.full(cur.shape, -1, dtype=jnp.int32)
    nxt = jnp.where(go_e, cur + 1, nxt)
    link = jnp.where(go_e, e_base + cy * (w - 1) + cx, link)
    nxt = jnp.where(go_w, cur - 1, nxt)
    link = jnp.where(go_w, w_base + cy * (w - 1) + (cx - 1), link)
    nxt = jnp.where(go_s, cur + w, nxt)
    link = jnp.where(go_s, s_base + cx * (h - 1) + cy, link)
    nxt = jnp.where(go_n, cur - w, nxt)
    link = jnp.where(go_n, n_base + cx * (h - 1) + (cy - 1), link)
    return nxt, link


@functools.partial(
    jax.jit, static_argnames=("w", "h", "nl", "capacity", "max_cycles", "out"))
def _run(cur, wd, inject, win, arrived, lat, rec, cong, over, cycle, *,
         w: int, h: int, nl: int, capacity: int, max_cycles: int, out: int):
    """Step one bucket of lanes; returns the next bucket's state and this
    one's tail ``(rec, lat)``.

    The bucket steps while more than ``out`` lanes are live (and the cycle
    is under ``max_cycles``).  With ``out`` > 0 it then compacts: the next
    state is its first ``out`` lanes after a sort that puts the live lanes
    first, in lane order, and the tail is the rest, all arrived whenever
    the loop ended on its live count.  With ``out`` = 0 it steps until
    every lane has arrived, and the tail is all its lanes.
    """
    # Padded and arrived lanes are never active: their sentinel tags sort
    # to the tail, and no grant decision of a live packet can see them —
    # bitwise parity with the unpadded run (pinned by the stepper parity
    # tests).
    n = cur.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)

    def cond(state):
        _, arrived, _, _, _, cycle = state
        return ((~arrived).sum(dtype=jnp.int32) > out) & (cycle < max_cycles)

    def body(state):
        cur, arrived, lat, cong, over, cycle = state
        active = (~arrived) & (inject <= cycle)
        nxt, link = _next_link_jnp(cur, wd, w, h)
        tag = jnp.where(active, win * nl + link, _SENTINEL)
        # The lane position is the last key, so the keys are unique and
        # the sort needs no stability; its third output is the permutation.
        st, _, order = lax.sort((tag, inject, idx), num_keys=3,
                                is_stable=False)
        newg = jnp.concatenate([jnp.ones(1, dtype=bool), st[1:] != st[:-1]])
        start = lax.cummax(jnp.where(newg, idx, 0))
        # Only inactive lanes carry the sentinel tag: an active one's is
        # below ``n_cwin * nl``, which the wrapper holds under it.
        go_sorted = ((idx - start) < capacity) & (st != _SENTINEL)
        # Back to lane order: the permutation's values are unique keys.
        _, go = lax.sort((order, go_sorted.astype(jnp.int32)), num_keys=1,
                         is_stable=False)
        go = go.astype(bool)
        cong = cong + active.sum(dtype=jnp.int32) - go.sum(dtype=jnp.int32)
        # Latch before a 32-bit wrap is possible: per-cycle growth is < n
        # <= 2^30 (guarded in the wrapper), so cong passes 2^30 before it
        # can exceed 2^31.
        over = over | (cong >= jnp.int32(1 << 30))
        cur = jnp.where(go, nxt, cur)
        newly = go & (cur == wd)
        lat = jnp.where(newly, cycle + 1, lat)
        return cur, arrived | newly, lat, cong, over, cycle + 1

    cur, arrived, lat, cong, over, cycle = lax.while_loop(
        cond, body, (cur, arrived, lat, cong, over, cycle))
    if not out:
        return (cur, wd, inject, win, arrived, lat, rec, cong, over,
                cycle), (rec, lat)
    # Compaction: one sort moves the live lanes to the front.  Its key is
    # the record index with the arrived flag above it: live lanes hold
    # their records in lane order (by induction over the buckets), so
    # they keep their order, and lane position still orders records as
    # before.  The sort's compile time grows fast with its operand count
    # (for a v5e, the 2^21- and 2^17-lane buckets compile in 43 and 33 s
    # with these four operands, in 120 and 111 s with the eight of an
    # unpacked sort keyed on (arrived, lane)), so the lanes travel packed:
    # a live lane needs its core and an arrived one its latency, and the
    # window and destination share a word (the wrapper holds
    # ``n_cwin * w * h`` under 2^31).
    nc = w * h
    key = jnp.where(arrived, rec | (1 << 30), rec)
    key, cur, win_wd, inject = lax.sort(
        (key, jnp.where(arrived, lat, cur), win * nc + wd, inject),
        num_keys=1, is_stable=False)
    arrived = key >= (1 << 30)
    rec = key & ((1 << 30) - 1)
    lat = jnp.where(arrived, cur, 0)
    win, wd = win_wd // nc, win_wd % nc
    head = (cur, wd, inject, win, arrived, lat, rec)
    return (*(a[:out] for a in head), cong, over, cycle), \
        (rec[out:], lat[out:])


def joint_stepper_jax(
    src: np.ndarray,
    dst: np.ndarray,
    inject: np.ndarray,
    win: np.ndarray,
    w: int,
    h: int,
    nl: int,
    link_capacity: int,
    max_cycles: int,
) -> tuple[np.ndarray, int]:
    """Drop-in device replacement for ``replay._joint_stepper``.

    The packet arrays are zero-padded to the next power of two ``m0``
    (padded lanes start out arrived), so replays of different traces —
    e.g. across a sweep's config grid — bucket into a handful of compiled
    program shapes instead of recompiling per trace length.  The loop then
    runs the ladder of buckets ``m0, m0/_SHRINK, ..., _MIN_LANES`` (one
    bucket where ``m0 <= _MIN_LANES``), each a `_run` program, all
    dispatched before any result is read.  Padding and compaction are
    invisible in the results: grant decisions, latencies, and the
    congestion count are bitwise the unpadded single-bucket run's.

    Counts on the innermost telemetry span ``lanes``, the mean bucket width
    over the loop's cycles (so cycles x lanes is the lane-cycles stepped),
    and ``buckets``, the number of buckets that stepped a cycle.
    """
    n_cwin = int(win.max()) + 1 if win.shape[0] else 0
    n = int(src.shape[0])
    if (n_cwin * max(nl, w * h) >= int(_SENTINEL)
            or max_cycles >= int(_SENTINEL) or n >= 1 << 30):
        raise ValueError("trace too large for the 32-bit JAX stepper; "
                         "use stepper='numpy'")
    m0 = 1 << max(n - 1, 0).bit_length() if n else 1  # next pow2, min 1

    def padded(a: np.ndarray) -> np.ndarray:
        full = np.zeros(m0, dtype=np.int32)
        full[:n] = a
        return full

    widths = _ladder(m0)
    state = (padded(src), padded(dst), padded(inject), padded(win),
             np.arange(m0) >= n, np.zeros(m0, dtype=np.int32),
             np.arange(m0, dtype=np.int32), np.int32(0), np.bool_(False),
             np.int32(0))
    tails, ends = [], []
    for out in widths[1:] + [0]:
        state, tail = _run(*state, w=w, h=h, nl=nl, capacity=link_capacity,
                           max_cycles=max_cycles, out=out)
        tails.append(tail)
        ends.append(state[-1])
    tails, ends, arrived, cong, over = jax.device_get(
        (tails, ends, state[4], state[7], state[8]))
    if bool(over):
        raise ValueError("blocked-packet count exceeds 32 bits; "
                         "use stepper='numpy'")
    # A bucket cut off at ``max_cycles`` keeps only live lanes, so the
    # last bucket holds one that has not arrived.
    if not arrived.all():
        raise RuntimeError("NoC window failed to drain — capacity too low?")
    steps = np.diff(np.asarray(ends, dtype=np.int64), prepend=0)
    telemetry.count("lanes", int(np.dot(widths, steps))
                    / max(int(ends[-1]), 1))
    telemetry.count("buckets", int(np.count_nonzero(steps)))
    lat = np.empty(m0, dtype=np.int64)
    lat[np.concatenate([r for r, _ in tails])] = np.concatenate(
        [t for _, t in tails])
    return lat[:n], int(cong)
