"""Device-resident mapping search (beyond-paper acceleration).

The paper runs one serial SA chain on a host CPU.  Here the same search is
reformulated for accelerators:

  * `sa_search_jax` — a *population* of SA chains advanced in lock-step by
    one `lax.scan`; each chain proposes a random swap, scores it with the
    O(K) incremental delta (gather arithmetic, vmapped over chains), and
    applies Metropolis acceptance.  Thousands of chains cost the same
    wall-clock as one.
  * `greedy_polish` — full-neighborhood steepest descent: the
    `swap_delta` Pallas kernel scores all O(K^2) swaps per step on the
    MXU and the single best swap is applied until no swap improves.
  * `island_sa` — shard_map island parallelism: chain populations run per
    device, periodically all-gathering the global best and re-seeding the
    worst chains (parallel tempering across the TPU mesh).

All variants share the objective of paper Eq. 2 (minimize average hop) —
their inner loops are gather-arithmetic reformulations of the pairwise
delta, so they do not take a `placecost` objective (see
`mapping.OBJECTIVE_AWARE_MAPPERS`).  They are not a parallel API: every
search here is registered in `repro.core.mapping.MAPPERS` ("sa_jax",
"polish" via the uniform-signature `polish_search` adapter, and "island",
which needs a `mesh=` kwarg), so `run_toolchain(mapper=...)` selects them
like any host mapper.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.swap_delta import swap_deltas

from .hopcost import hop_distance_matrix
from .mapping import MappingResult, pad_traffic

__all__ = [
    "sa_search_jax",
    "sa_search_jax_batch",
    "greedy_polish",
    "polish_search",
    "island_sa",
]


def _coords(num_cores: int, mesh_w: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    ids = jnp.arange(num_cores)
    return (ids % mesh_w).astype(jnp.float32), (ids // mesh_w).astype(jnp.float32)


def _cost(sym: jnp.ndarray, placement: jnp.ndarray, dist: jnp.ndarray) -> jnp.ndarray:
    d = dist[placement[:, None], placement[None, :]]
    return jnp.sum(sym * d) / 2.0


def _delta_one(sym, dist, placement, a, b):
    """O(K) incremental swap delta.

    The formula and its derivation live in one place:
    `repro.core.hopcost.swap_delta` (the host/numpy original).  This is its
    jnp twin, kept branch-free so it traces cleanly under scan/vmap.
    """
    ca = placement[a]
    cb = placement[b]
    d_a = dist[ca, placement]
    d_b = dist[cb, placement]
    diff = (sym[a] - sym[b]) * (d_b - d_a)
    return jnp.sum(diff) - diff[a] - diff[b]


@functools.partial(jax.jit, static_argnames=("iters", "sweeps_per_temp"))
def _sa_population(
    sym: jnp.ndarray,
    dist: jnp.ndarray,
    placements: jnp.ndarray,  # (P, NC)
    key: jnp.ndarray,
    t0: jnp.ndarray,
    iters: int,
    sweeps_per_temp: int,
    alpha: float = 0.95,
):
    nc = placements.shape[1]

    def chain_step(state, key_t):
        placement, cost, T = state
        ka, kb, ku = jax.random.split(key_t, 3)
        a = jax.random.randint(ka, (), 0, nc)
        b0 = jax.random.randint(kb, (), 0, nc - 1)
        b = jnp.where(b0 >= a, b0 + 1, b0)
        delta = _delta_one(sym, dist, placement, a, b)
        accept = (delta <= 0) | (jax.random.uniform(ku) < jnp.exp(-delta / T))
        pa, pb = placement[a], placement[b]
        new_placement = placement.at[a].set(jnp.where(accept, pb, pa))
        new_placement = new_placement.at[b].set(jnp.where(accept, pa, pb))
        new_cost = jnp.where(accept, cost + delta, cost)
        return (new_placement, new_cost, T), new_cost

    def temp_epoch(carry, key_e):
        placement, cost, T = carry
        keys = jax.random.split(key_e, sweeps_per_temp)
        (placement, cost, _), costs = jax.lax.scan(
            chain_step, (placement, cost, T), keys
        )
        return (placement, cost, T * alpha), jnp.min(costs)

    def run_chain(placement, key_c, t_init):
        cost = _cost(sym, placement, dist)
        epochs = max(iters // sweeps_per_temp, 1)
        keys = jax.random.split(key_c, epochs)
        (placement, cost, _), best_hist = jax.lax.scan(
            temp_epoch, (placement, cost, t_init), keys
        )
        return placement, cost, best_hist

    keys = jax.random.split(key, placements.shape[0])
    return jax.vmap(run_chain, in_axes=(0, 0, None))(placements, keys, t0)


def sa_search_jax(
    traffic: np.ndarray,
    num_cores: int,
    mesh_w: int,
    trace_length: int,
    seed: int = 0,
    iters: int = 20_000,
    chains: int = 16,
    sweeps_per_temp: int = 64,
    t0_frac: float = 0.25,
    torus: bool = False,
    polish: bool = True,
    polish_backend: str = "auto",
) -> MappingResult:
    """Population SA on device + optional kernel-powered greedy polish."""
    start = time.perf_counter()
    k = traffic.shape[0]
    trace_length = max(trace_length, 1)  # zero-traffic profiles normalize by 1
    padded = pad_traffic(np.asarray(traffic, dtype=np.float64), num_cores)
    sym = jnp.asarray(padded + padded.T, dtype=jnp.float32)
    dist = jnp.asarray(
        hop_distance_matrix(num_cores, mesh_w, torus=torus), dtype=jnp.float32
    )
    key = jax.random.PRNGKey(seed)
    kinit, krun = jax.random.split(key)
    placements = jax.vmap(lambda kk: jax.random.permutation(kk, num_cores))(
        jax.random.split(kinit, chains)
    )
    c0 = _cost(sym, placements[0], dist)
    t0 = t0_frac * c0 / max(k, 1)
    placements, costs, best_hist = _sa_population(
        sym, dist, placements, krun, t0, iters, sweeps_per_temp
    )
    best_i = int(jnp.argmin(costs))
    best = placements[best_i]
    if polish:
        x, y = _coords(num_cores, mesh_w)
        best, _ = greedy_polish(sym, best, x, y, backend=polish_backend)
    final_cost = float(_cost(sym, best, dist))
    seconds = time.perf_counter() - start
    # The scan runs entirely on device, so per-epoch wall-clock timestamps
    # do not exist; history is keyed by temperature-epoch index instead
    # (see MappingResult.history), with elapsed time recorded once above.
    best_by_epoch = np.minimum.accumulate(
        np.asarray(best_hist, dtype=np.float64).min(axis=0)
    )
    hist = [(float(i), c / trace_length) for i, c in enumerate(best_by_epoch)]
    return MappingResult(
        placement=np.asarray(best)[:k].astype(np.int64),
        avg_hop=final_cost / trace_length,
        seconds=seconds,
        history=hist,
        evaluations=int(iters) * int(chains),
    )


@functools.partial(jax.jit, static_argnames=("iters", "sweeps_per_temp"))
def _sa_population_multi(
    syms: jnp.ndarray,       # (C, NC, NC)
    dist: jnp.ndarray,       # (NC, NC) shared across configs
    placements: jnp.ndarray, # (C, P, NC)
    keys: jnp.ndarray,       # (C, 2)
    t0s: jnp.ndarray,        # (C,)
    iters: int,
    sweeps_per_temp: int,
):
    """`_sa_population` vmapped over a bucket of same-shape configs.

    One device program advances every config's whole chain population in
    lock-step; the per-config math is element-for-element the single-call
    path's, so batched results are bitwise those of C sequential
    `_sa_population` calls (pinned by the sweep parity tests).
    """
    return jax.vmap(
        lambda s, p, k, t: _sa_population(s, dist, p, k, t, iters, sweeps_per_temp)
    )(syms, placements, keys, t0s)


def sa_search_jax_batch(
    traffics: list[np.ndarray],
    num_cores: int,
    mesh_w: int,
    trace_lengths: list[int],
    seeds: list[int],
    iters: int = 20_000,
    chains: int = 16,
    sweeps_per_temp: int = 64,
    t0_frac: float = 0.25,
    torus: bool = False,
    polish: bool = True,
    polish_backend: str = "auto",
) -> list[MappingResult]:
    """Batched `sa_search_jax`: one device program for a whole config bucket.

    All configs must share ``(num_cores, mesh_w, iters, chains,
    sweeps_per_temp, torus)`` — that is what makes their populations
    stackable into one ``(C, P, NC)`` vmapped scan (the sweep driver's
    bucketing key).  Traffic matrices may have different ``k`` (they are
    zero-padded to ``num_cores`` exactly as the single path pads).  Each
    config's RNG stream, initial placements, and temperature schedule are
    derived per-seed identically to ``sa_search_jax(seed=s)``, so element
    ``i`` of the returned list is bitwise the single call's result; the
    polish tail runs per config through the same shape-cached kernel.
    Reported ``seconds`` are the bucket wall-clock amortized per config.
    """
    start = time.perf_counter()
    c = len(traffics)
    if not (len(trace_lengths) == len(seeds) == c):
        raise ValueError("traffics, trace_lengths, seeds must align")
    if c == 0:
        return []
    ks = [int(t.shape[0]) for t in traffics]
    syms_np = np.empty((c, num_cores, num_cores), dtype=np.float64)
    for i, t in enumerate(traffics):
        padded = pad_traffic(np.asarray(t, dtype=np.float64), num_cores)
        syms_np[i] = padded + padded.T
    syms = jnp.asarray(syms_np, dtype=jnp.float32)
    dist = jnp.asarray(
        hop_distance_matrix(num_cores, mesh_w, torus=torus), dtype=jnp.float32
    )
    kruns = []
    placements = []
    for s in seeds:
        kinit, krun = jax.random.split(jax.random.PRNGKey(int(s)))
        kruns.append(krun)
        placements.append(
            jax.vmap(lambda kk: jax.random.permutation(kk, num_cores))(
                jax.random.split(kinit, chains)
            )
        )
    placements = jnp.stack(placements)  # (C, P, NC)
    c0s = jax.vmap(lambda s, p: _cost(s, p, dist))(syms, placements[:, 0])
    t0s = t0_frac * c0s / jnp.asarray([max(k, 1) for k in ks], dtype=c0s.dtype)
    placements, costs, best_hists = _sa_population_multi(
        syms, dist, placements, jnp.stack(kruns), t0s, iters, sweeps_per_temp
    )
    if polish:
        x, y = _coords(num_cores, mesh_w)
    results = []
    for i in range(c):
        best_i = int(jnp.argmin(costs[i]))
        best = placements[i, best_i]
        if polish:
            best, _ = greedy_polish(syms[i], best, x, y, backend=polish_backend)
        denom = max(int(trace_lengths[i]), 1)
        final_cost = float(_cost(syms[i], best, dist))
        best_by_epoch = np.minimum.accumulate(
            np.asarray(best_hists[i], dtype=np.float64).min(axis=0)
        )
        hist = [(float(j), cst / denom) for j, cst in enumerate(best_by_epoch)]
        results.append(MappingResult(
            placement=np.asarray(best)[: ks[i]].astype(np.int64),
            avg_hop=final_cost / denom,
            seconds=0.0,
            history=hist,
            evaluations=int(iters) * int(chains),
        ))
    seconds = (time.perf_counter() - start) / c
    for r in results:
        r.seconds = seconds
    return results


@functools.partial(jax.jit, static_argnames=("max_steps", "backend"))
def _polish_loop(sym, placement, x, y, max_steps: int, backend: str):
    nc = placement.shape[0]
    eye = jnp.eye(nc, dtype=bool)

    def body(state):
        placement, improved, steps = state
        px = x[placement]
        py = y[placement]
        deltas = swap_deltas(sym, px, py, backend=backend)
        deltas = jnp.where(eye, jnp.inf, deltas)
        flat = jnp.argmin(deltas)
        a, b = flat // nc, flat % nc
        best_delta = deltas[a, b]
        do = best_delta < -1e-6
        pa, pb = placement[a], placement[b]
        placement = placement.at[a].set(jnp.where(do, pb, pa))
        placement = placement.at[b].set(jnp.where(do, pa, pb))
        return placement, do, steps + 1

    def cond(state):
        _, improved, steps = state
        return improved & (steps < max_steps)

    placement, _, steps = jax.lax.while_loop(cond, body, (placement, jnp.bool_(True), 0))
    return placement, steps


def greedy_polish(
    sym: jnp.ndarray,
    placement: jnp.ndarray,
    x: jnp.ndarray,
    y: jnp.ndarray,
    max_steps: int = 256,
    backend: str = "auto",
) -> tuple[jnp.ndarray, int]:
    """Steepest-descent over the full swap neighborhood (swap_delta kernel).

    Each step scores all O(K^2) swaps in one kernel launch and applies the
    best one; terminates at a local optimum of the swap neighborhood —
    strictly stronger than the paper's first-improvement SA tail.
    """
    placement, steps = _polish_loop(sym, placement, x, y, max_steps, backend)
    return placement, int(steps)


def polish_search(
    traffic: np.ndarray,
    num_cores: int,
    mesh_w: int,
    trace_length: int,
    seed: int = 0,
    init: np.ndarray | None = None,
    max_steps: int = 256,
    backend: str = "auto",
    torus: bool = False,
) -> MappingResult:
    """Uniform-signature mapper over `greedy_polish` (registry: "polish").

    Starts from ``init`` (or a seeded random permutation) and runs
    full-neighborhood steepest descent to a swap-local optimum.  The
    swap-delta kernel rebuilds plain Manhattan distances from coordinates,
    so torus meshes are not supported.
    """
    if torus:
        raise ValueError("polish_search is mesh-only (kernel distance is Manhattan)")
    start = time.perf_counter()
    k = traffic.shape[0]
    trace_length = max(trace_length, 1)  # zero-traffic profiles normalize by 1
    padded = pad_traffic(np.asarray(traffic, dtype=np.float64), num_cores)
    sym = jnp.asarray(padded + padded.T, dtype=jnp.float32)
    dist = jnp.asarray(hop_distance_matrix(num_cores, mesh_w), dtype=jnp.float32)
    placement = (np.asarray(init, dtype=np.int64).copy() if init is not None
                 else np.random.default_rng(seed).permutation(num_cores))
    x, y = _coords(num_cores, mesh_w)
    best, steps = greedy_polish(sym, jnp.asarray(placement), x, y,
                                max_steps=max_steps, backend=backend)
    final_cost = float(_cost(sym, best, dist))
    seconds = time.perf_counter() - start
    # One kernel launch scores the whole O(K^2) neighborhood per step.
    return MappingResult(
        placement=np.asarray(best)[:k].astype(np.int64),
        avg_hop=final_cost / trace_length,
        seconds=seconds,
        history=[(float(steps), final_cost / trace_length)],
        evaluations=int(steps) * num_cores * num_cores,
    )


def island_sa(
    traffic: np.ndarray,
    num_cores: int,
    mesh_w: int,
    trace_length: int,
    mesh: jax.sharding.Mesh,
    axis: str = "data",
    seed: int = 0,
    rounds: int = 4,
    iters_per_round: int = 4_000,
    chains_per_device: int = 4,
    torus: bool = False,
) -> MappingResult:
    """Island-model SA under shard_map: independent populations per device,
    periodic all-gather of the global best to reseed each island's worst
    chain (the distributed-search story for large meshes)."""
    from jax.sharding import PartitionSpec as P

    start = time.perf_counter()
    k = traffic.shape[0]
    trace_length = max(trace_length, 1)  # zero-traffic profiles normalize by 1
    padded = pad_traffic(np.asarray(traffic, dtype=np.float64), num_cores)
    sym = jnp.asarray(padded + padded.T, dtype=jnp.float32)
    dist = jnp.asarray(
        hop_distance_matrix(num_cores, mesh_w, torus=torus), dtype=jnp.float32
    )
    n_dev = mesh.shape[axis]
    total_chains = n_dev * chains_per_device

    key = jax.random.PRNGKey(seed)
    kinit, krun = jax.random.split(key)
    placements = jax.vmap(lambda kk: jax.random.permutation(kk, num_cores))(
        jax.random.split(kinit, total_chains)
    )
    keys = jax.random.split(krun, total_chains * rounds).reshape(total_chains, rounds, 2)
    c0 = _cost(sym, placements[0], dist)
    t0 = 0.25 * c0 / max(k, 1)

    def island(placements_l, keys_l):
        # placements_l: (chains_per_device, NC); keys_l: (cpd, rounds, 2)
        t_now = t0
        for r in range(rounds):
            placements_l, costs_l, _ = _sa_population(
                sym, dist, placements_l, keys_l[0, r], jnp.asarray(t_now),
                iters_per_round, 64,
            )
            # Exchange: adopt the global best into the locally worst slot.
            all_costs = jax.lax.all_gather(costs_l, axis)  # (n_dev, cpd)
            all_place = jax.lax.all_gather(placements_l, axis)
            flat_costs = all_costs.reshape(-1)
            gbest = jnp.argmin(flat_costs)
            gplace = all_place.reshape(-1, placements_l.shape[1])[gbest]
            worst = jnp.argmax(costs_l)
            placements_l = placements_l.at[worst].set(gplace)
            t_now = t_now * (0.95 ** (iters_per_round // 64))
        costs_l = jax.vmap(lambda p: _cost(sym, p, dist))(placements_l)
        return placements_l, costs_l

    sharded = jax.shard_map(
        island, mesh=mesh,
        in_specs=(P(axis), P(axis)),
        out_specs=(P(axis), P(axis)),
        check_vma=False,
    )
    # Gather to the host before indexing: on a mesh with Explicit axes a
    # device-side gather from a sharded array is a sharding error.
    placements, costs = (np.asarray(a) for a in sharded(placements, keys))
    best = jnp.asarray(placements[int(np.argmin(costs))])
    final_cost = float(_cost(sym, best, dist))
    seconds = time.perf_counter() - start
    return MappingResult(
        placement=np.asarray(best)[:k].astype(np.int64),
        avg_hop=final_cost / trace_length,
        seconds=seconds,
        history=[(seconds, final_cost / trace_length)],
        evaluations=rounds * iters_per_round * total_chains,
    )
