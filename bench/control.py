"""Controls and faults: the timed path broken underneath, to show that the
comparison in `check.py` fails when it should.

    python3 bench/control.py --workload <cell> --seeds 3 4 5 --control bf16_emul

runs the cell once per seed (a window of one job) with the control or
fault installed, and prints each run's failing comparisons.  The
benchmark's own runs never install one.

Controls, the reference put in the program's place at a lower precision
than the configuration states (float32 synaptic sums at HIGHEST):

  bf16       the LIF recurrence with its synaptic sums at the backend's
             ``Precision.DEFAULT``: no control on a v5e, whose compiler
             makes the spike vector's product with the weights an f32
             multiply-reduce at any precision; ``bf16_emul`` is the
             control there
  bf16_emul  the same with the weights rounded to bf16 written out, so
             that it reads the same on any backend
  high_emul  the weights split into their two leading bf16 parts, as three
             bf16 passes (``Precision.HIGH``) take them: no control, since
             the 2^-20 weight grid splits exactly (a test shows it)
  analytic   the program's own approximate NoC path (``mode=
             "analytic"``: latency = hops, no queueing) in place of the
             queued replay

Faults:

  state_unchanged  each LIF step starts from the initial state: potentials
                   and spikes are never carried to the next step
  half_batch       each replay scores every other packet record only
  alter_partition  one neuron moves to another part after partitioning
  alter_placement  two parts swap cores after the placement is scored
  alter_replay     each replay reports one more cycle of latency on one
                   packet

Faults of a replay under a fault schedule:

  drop_delivered    a packet whose XY and YX routes are both blocked is
                    delivered by YX, through the blocked link
  detour_dropped    a packet whose XY route is blocked is dropped, though
                    its YX route is clean
  detour_xy         a detoured packet is stepped along its XY route
  dead_core_kept    after each repair one part with neurons of a lost core
                    is placed on that core
  boundary_moved    the repaired mapping takes over one step late
  migrated_off      the job reports one more neuron migrated
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import inspect
import json
import os
import sys

import numpy as np


def _scan_raster(weights, drive, params, precision: str | None,
                 split: str | None, carry_state: bool = True) -> np.ndarray:
    """The LIF recurrence in JAX, with its synaptic sums at ``precision``;
    ``split`` replaces the weights by their bf16 part (``"bf16"``) or their
    two leading bf16 parts (``"high"``) first."""
    import jax
    import jax.numpy as jnp

    w = jnp.asarray(weights, dtype=jnp.float32)
    if split is not None:
        hi = w.astype(jnp.bfloat16).astype(jnp.float32)
        lo = (w - hi).astype(jnp.bfloat16).astype(jnp.float32)
        w = hi if split == "bf16" else hi + lo
    prec = {None: jax.lax.Precision.HIGHEST,
            "default": jax.lax.Precision.DEFAULT}[precision]
    return np.asarray(_jitted()(w, jnp.asarray(drive), params.decay,
                                params.threshold, params.v_reset,
                                params.refractory, prec, carry_state))


@functools.cache
def _jitted():
    import jax

    return jax.jit(_control_scan, static_argnums=(2, 3, 4, 5, 6, 7))


def _control_scan(w, drive, decay, threshold, v_reset, refractory, precision,
                  carry_state):
    import jax
    import jax.numpy as jnp

    n = w.shape[0]

    def body(carry, drive_t):
        v, refr, spikes = carry
        current = jnp.dot(spikes, w, precision=precision) + drive_t
        active = refr <= 0
        v1 = jnp.where(active, decay * v + current, v)
        fired = active & (v1 >= threshold)
        v1 = jnp.where(fired, v_reset, v1)
        refr1 = jnp.where(fired, refractory, jnp.maximum(refr - 1, 0))
        new = (v1, refr1, fired.astype(w.dtype))
        return (new if carry_state else carry), fired

    init = (jnp.zeros(n, jnp.float32), jnp.zeros(n, jnp.int32),
            jnp.zeros(n, jnp.float32))
    return jax.lax.scan(body, init, drive)[1].astype(jnp.uint8)


@contextlib.contextmanager
def _patched(owner, name: str, wrap):
    orig = getattr(owner, name)
    setattr(owner, name, wrap(orig))
    try:
        yield
    finally:
        setattr(owner, name, orig)


def _lif(precision=None, split=None, carry_state=True):
    from repro.snn import simulate

    def wrap(_orig):
        def lif_run(weights, input_drive, params, **_):
            return _scan_raster(weights, input_drive, params, precision, split,
                                carry_state)
        return lif_run

    return _patched(simulate, "lif_run", wrap)


def _phase(name: str, after=None, before=None):
    """Wrap a function of the toolchain, as ``run_toolchain`` resolves it:
    ``before`` rewrites its arguments, a dict by parameter name, in place;
    ``after`` rewrites its result."""
    from repro.core import pipeline

    def wrap(orig):
        signature = inspect.signature(orig)

        def phase(*args, **kwargs):
            if before is not None:
                bound = signature.bind(*args, **kwargs)
                bound.apply_defaults()
                before(bound.arguments)
                args, kwargs = bound.args, bound.kwargs
            out = orig(*args, **kwargs)
            return after(out) if after is not None else out
        return phase

    return _patched(pipeline, name, wrap)


def _analytic(a: dict) -> None:
    a["mode"] = "analytic"


def _half(a: dict) -> None:
    for name in ("trace_t", "trace_src", "trace_dst"):
        a[name] = a[name][::2]


def _routes(yx_blocked: bool):
    """Every YX escape route reads as blocked, or as clean."""
    from repro.nocsim import sim

    def wrap(orig):
        def routes_blocked(src, dst, w, h, blocked, order=None):
            if order is None:
                return orig(src, dst, w, h, blocked)
            return np.full(np.shape(src)[0], yx_blocked)
        return routes_blocked

    return _patched(sim, "routes_blocked", wrap)


def _xy_only():
    from repro.nocsim import sim

    def wrap(orig):
        def queued_unicast(*args, **kwargs):
            return orig(*args, **{**kwargs, "order": None})
        return queued_unicast

    return _patched(sim, "queued_unicast", wrap)


def _left_on_dead(part, placement, dead, res):
    """The repair with the part that took in the first neuron of a lost
    core put back on that core."""
    core = np.asarray(placement)[np.asarray(part)]
    lost = np.flatnonzero(dead[core])
    if not lost.shape[0]:
        return res
    new = np.array(res.placement)  # a full permutation of the cores
    p = res.part[lost[0]]
    q = np.flatnonzero(new == core[lost[0]])[0]
    new[[p, q]] = new[[q, p]]
    return dataclasses.replace(res, placement=new)


def _remap(after):
    from repro.core import pipeline

    def wrap(orig):
        def incremental_remap(graph, part, placement, dead, *args, **kwargs):
            res = orig(graph, part, placement, dead, *args, **kwargs)
            return after(part, placement, dead, res)
        return incremental_remap

    return _patched(pipeline, "incremental_remap", wrap)


def _late(a: dict) -> None:
    a["detect_windows"] += 1


def _one_more_migrated(out):
    noc, degradation = out
    return noc, {**degradation,
                 "neurons_migrated": degradation["neurons_migrated"] + 1}


def _move_one(pres):
    part = np.array(pres.part)
    part[0] = (part[0] + 1) % pres.k
    return dataclasses.replace(pres, part=part)


def _swap_two(out):
    mres = out[0]
    placement = np.array(mres.placement)
    placement[[0, 1]] = placement[[1, 0]]
    return (dataclasses.replace(mres, placement=placement),) + tuple(out[1:])


def _one_more_cycle(noc):
    n = max(noc.num_noc_spikes, 1)
    return dataclasses.replace(noc, avg_latency=noc.avg_latency + 1.0 / n)


CONTROLS = {
    "bf16": lambda: _lif("default"),
    "high_emul": lambda: _lif(split="high"),
    "bf16_emul": lambda: _lif(split="bf16"),
    "analytic": lambda: _phase("simulate_noc", before=_analytic),
    "state_unchanged": lambda: _lif(carry_state=False),
    "half_batch": lambda: _phase("simulate_noc", before=_half),
    "alter_partition": lambda: _phase("partition_phase", after=_move_one),
    "alter_placement": lambda: _phase("mapping_phase", after=_swap_two),
    "alter_replay": lambda: _phase("simulate_noc", after=_one_more_cycle),
    "drop_delivered": lambda: _routes(yx_blocked=False),
    "detour_dropped": lambda: _routes(yx_blocked=True),
    "detour_xy": _xy_only,
    "dead_core_kept": lambda: _remap(_left_on_dead),
    "boundary_moved": lambda: _phase("_faulty_replay", before=_late),
    "migrated_off": lambda: _phase("_faulty_replay", after=_one_more_migrated),
}


def failing(result: dict) -> dict:
    """The compared numbers that exceed their limits."""
    return {k: c["value"] for k, c in result["checks"].items()
            if c["value"] is None or c["value"] > c["limit"]}


def main() -> int:
    import run

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", nargs="+", required=True,
                    choices=sorted(CONTROLS) + ["none"])
    args = ap.parse_args()
    _, cell, config, traffic = run.load_cell(args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(run.CACHE_DIR)
    sys.path.insert(0, str(run.ROOT / "src"))
    for name in args.control:
        for seed in args.seeds:
            installed = CONTROLS[name]() if name != "none" \
                else contextlib.nullcontext()
            with installed:
                result = run.run_cell(config, traffic, seed, 0.0, False, [],
                                      chips=int(cell["chips"]),
                                      log=lambda s: print(s, flush=True))
            print(json.dumps({"control": name, "seed": seed,
                              "correct": result["correct"],
                              "failing": failing(result),
                              "checks": {k: c["value"] for k, c in
                                         result["checks"].items()}}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
