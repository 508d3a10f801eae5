"""The comparison that decides ``correct``.

The last job of the window is compared, layer by layer, with the plain
reference (`reference.py`) run on the same network, stimulus and seed.
Every comparison is exact, so each limit is 0:

  profile_steps      |kept steps - reference's|
  profile_fires      neurons whose fire count over the kept steps differs
  profile_trace      transmissions in one trace and not the other
  partition_invalid  neurons outside [0, k), parts over capacity, k > cores
  partition_objective  |reported objective - recount from the trace|
  placement_invalid  placements off the mesh or sharing a core
  placement_avg_hop  |reported avg_hop - recount from the trace|
  noc_<field>        |replay statistic - reference replay's| per field;
                     noc_per_link_hops counts the links that differ
  jobs_differing     window jobs whose summary differs from the last's

``outcome`` also returns the reference's own replay statistics, which the
harness reports as the end-to-end quality metrics.
"""
from __future__ import annotations

import dataclasses

import numpy as np

import network
import reference

# NoCStats fields compared one by one.
NOC_FIELDS = ("avg_latency", "max_latency", "avg_hop", "total_hops",
              "congestion_count", "edge_variance", "dynamic_energy_pj",
              "num_noc_spikes", "num_local_spikes", "cycles_simulated",
              "per_link_hops", "cast", "link_traversals", "spikes_dropped",
              "detour_hops")


def job_summary(prof, res, objective: str) -> tuple:
    """What two jobs on one input must agree on."""
    noc = res.noc
    return (prof.num_steps, prof.num_spikes, res.partition.k,
            reported_objective(res, objective), res.mapping.avg_hop,
            tuple(res.mapping.placement.tolist()),
            *(getattr(noc, f) for f in NOC_FIELDS if f != "per_link_hops"),
            tuple(np.asarray(noc.per_link_hops).tolist()))


def reported_objective(res, objective: str) -> int:
    p = res.partition
    return int(p.edge_cut if objective == "cut" else p.comm_volume)


def outcome(net: network.Network, config: dict, traffic: dict, seed: int,
            prof, res, summaries: list[tuple]) -> tuple[dict, dict]:
    """(checks, reference stats): checks maps each compared number's name to
    ``{"value": ..., "limit": ...}``."""
    tc = config["toolchain"]
    n = net.num_neurons
    drive = network.input_drive(net, int(traffic["num_steps"]), seed)
    ref = reference.profile(net, drive, config["lif"])
    keys = reference.trace_keys(prof.trace_t, prof.trace_src, prof.trace_dst, n)
    checks: dict[str, float] = {
        "profile_steps": abs(int(prof.num_steps) - ref["num_steps"]),
        "profile_fires": int((np.asarray(prof.fire_counts)
                              != ref["fire_counts"]).sum()),
        "profile_trace": reference.set_mismatch(keys, ref["trace"]),
    }
    fires, trace = ref["fire_counts"], ref["trace"]
    part = np.asarray(res.partition.part, dtype=np.int64)
    k = int(res.partition.k)
    cores = int(tc["mesh_w"]) * int(tc["mesh_h"])
    checks["partition_invalid"] = reference.partition_violations(
        part, k, int(tc["capacity"]), cores)
    cast = res.cast
    if checks["partition_invalid"] == 0:
        recount = (reference.cut_spikes(net, fires, part)
                   if tc["objective"] == "cut"
                   else reference.multicast_volume(net, fires, part))
        checks["partition_objective"] = abs(
            reported_objective(res, tc["objective"]) - recount)
    placement = np.asarray(res.mapping.placement, dtype=np.int64)
    checks["placement_invalid"] = reference.placement_violations(placement, cores)
    stats = None
    if checks["partition_invalid"] == 0 and checks["placement_invalid"] == 0 \
            and placement.shape[0] == k:
        checks["placement_avg_hop"] = abs(res.mapping.avg_hop - reference.avg_hop(
            trace, n, part, placement, int(tc["mesh_w"]), cast))
        noc = {"link_capacity": tc["link_capacity"],
               "inject_capacity": tc["noc_kwargs"]["inject_capacity"],
               "energy_pj": config["energy_pj"]}
        stats = reference.replay(trace, n, part, placement, int(tc["mesh_w"]),
                                 int(tc["mesh_h"]), noc, cast)
        got = dataclasses.asdict(res.noc)
        for f in NOC_FIELDS:
            checks[f"noc_{f}"] = _gap(got[f], stats[f])
    last = summaries[-1]
    checks["jobs_differing"] = sum(1 for s in summaries if s != last)
    out = {name: {"value": value, "limit": 0} for name, value in checks.items()}
    # A layer that could not be compared (its input was invalid) fails.
    for name in ("partition_objective", "placement_avg_hop"):
        out.setdefault(name, {"value": None, "limit": 0})
    if stats is not None:
        stats["transmissions"] = int(trace.shape[0])
    return out, stats


def passed(checks: dict) -> bool:
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in checks.values())


def _gap(got, want) -> float:
    if isinstance(want, np.ndarray):
        got = np.asarray(got)
        if got.shape != want.shape:
            return int(max(got.size, want.size))
        return int((got != want).sum())
    if isinstance(want, str):
        return int(got != want)
    return abs(got - want)
