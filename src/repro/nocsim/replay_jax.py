"""Optional JAX device path for the joint congested-window stepper.

A ``lax.while_loop`` version of `replay._joint_stepper` for large traces:
fixed-size state (no compaction), two sorts per NoC cycle.  The first sorts
the lanes by (window-tagged link, injection cycle, record index) and carries
the record index, so its outputs are the sorted tags and the permutation;
grants are decided in that order.  The second sorts the grants back to
record order, keyed by the permutation.  No gather or scatter runs through
the permutation: on the TPU either costs far more than a sort of the same
length.  Grant decisions mirror the numpy stepper exactly — per
window-tagged link, the ``link_capacity`` oldest-injected packets win,
stable by record order — so latencies and congestion are identical; only
the execution substrate differs.  Imported lazily by
``simulate_noc(stepper="jax")`` so the default numpy path never pays the
JAX import.

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


@functools.partial(jax.jit,
                   static_argnames=("w", "h", "nl", "capacity", "max_cycles"))
def _run(cur, wd, inject, win, valid, *, w: int, h: int, nl: int,
         capacity: int, max_cycles: int):
    # ``valid`` masks padding: padded records start out arrived, so they
    # are never active, their sentinel tags sort to the tail, and no grant
    # decision of a real packet can see them — bitwise parity with the
    # unpadded run (pinned by the stepper parity tests).
    n = cur.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)

    def cond(state):
        _, arrived, _, _, _, cycle = state
        return (~jnp.all(arrived)) & (cycle < max_cycles)

    def body(state):
        cur, arrived, lat, cong, over, cycle = state
        active = (~arrived) & (inject <= cycle)
        nxt, link = _next_link_jnp(cur, wd, w, h)
        tag = jnp.where(active, win * nl + link, _SENTINEL)
        # The record index is the last key, so the keys are unique and the
        # sort needs no stability; its third output is the permutation.
        st, _, order = lax.sort((tag, inject, idx), num_keys=3,
                                is_stable=False)
        newg = jnp.concatenate([jnp.ones(1, dtype=bool), st[1:] != st[:-1]])
        start = lax.cummax(jnp.where(newg, idx, 0))
        # Only inactive lanes carry the sentinel tag: an active one's is
        # below ``n_cwin * nl``, which the wrapper holds under it.
        go_sorted = ((idx - start) < capacity) & (st != _SENTINEL)
        # Back to record order: the permutation's values are unique keys.
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

    init = (cur, ~valid, jnp.zeros(n, dtype=jnp.int32),
            jnp.int32(0), jnp.bool_(False), jnp.int32(0))
    _, arrived, lat, cong, over, cycle = lax.while_loop(cond, body, init)
    return lat, cong, jnp.all(arrived), over


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

    The packet arrays are zero-padded to the next power of two (with a
    validity mask that keeps padded records inert), so replays of
    different traces — e.g. across a sweep's config grid — bucket into a
    handful of compiled program shapes instead of recompiling per trace
    length.  Padding is invisible in the results: grant decisions,
    latencies, and the congestion count are bitwise the unpadded run's.
    """
    n_cwin = int(win.max()) + 1 if win.shape[0] else 0
    n = int(src.shape[0])
    if (n_cwin * nl >= int(_SENTINEL) or max_cycles >= int(_SENTINEL)
            or n >= 1 << 30):
        raise ValueError("trace too large for the 32-bit JAX stepper; "
                         "use stepper='numpy'")
    m = 1 << max(n - 1, 0).bit_length() if n else 1  # next pow2, min 1
    pad = m - n

    def padded(a: np.ndarray) -> jnp.ndarray:
        a = np.asarray(a, dtype=np.int32)
        if pad:
            a = np.concatenate([a, np.zeros(pad, dtype=np.int32)])
        return jnp.asarray(a)

    telemetry.count("lanes", m)
    valid = np.zeros(m, dtype=bool)
    valid[:n] = True
    lat, cong, drained, over = _run(
        padded(src), padded(dst), padded(inject), padded(win),
        jnp.asarray(valid),
        w=w, h=h, nl=nl, capacity=link_capacity, max_cycles=max_cycles)
    if bool(over):
        raise ValueError("blocked-packet count exceeds 32 bits; "
                         "use stepper='numpy'")
    if not bool(drained):
        raise RuntimeError("NoC window failed to drain — capacity too low?")
    return np.asarray(lat, dtype=np.int64)[:n], int(cong)
