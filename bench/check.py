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

Under a fault schedule (``toolchain.fault_schedule``) the job replays its
trace in segments, and the harness records what each ran on (`run.py`).
The reference cuts the segments from the schedule itself, replays each on
the mapping the job used there, and combines them for the ``noc_`` checks:

  fault_segments      recorded segments whose records (count, first and
                      last step) differ from the reference's segment at
                      their place, plus the difference in their number
  fault_stale_mapping segments, other than the first on a repaired
                      mapping, whose neurons' cores differ from the
                      segment before (the first: from the job's reported
                      partition and placement)
  remap_invalid       in each segment after a repair: neurons on a core
                      dead at that repair, parts over capacity, placements
                      off the mesh or sharing a core
  remap_migrated      |reported neurons_migrated - the neurons whose core
                      changes at each repair, summed|

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


def job_summary(prof, res, objective: str, segments: list) -> tuple:
    """What two jobs on one input must agree on: every answer, and under a
    fault schedule the counts of the repair and what each segment ran on."""
    noc = res.noc
    repair = sorted((k, v) for k, v in (res.degradation or {}).items()
                    if isinstance(v, (int, str)))
    return (prof.num_steps, prof.num_spikes, res.partition.k,
            reported_objective(res, objective), res.mapping.avg_hop,
            tuple(res.mapping.placement.tolist()),
            *(getattr(noc, f) for f in NOC_FIELDS if f != "per_link_hops"),
            tuple(np.asarray(noc.per_link_hops).tolist()), tuple(repair),
            tuple((s["records"], s["t_first"], s["t_last"], s["part"].tobytes(),
                   s["placement"].tobytes()) for s in segments))


def reported_objective(res, objective: str) -> int:
    p = res.partition
    return int(p.edge_cut if objective == "cut" else p.comm_volume)


def outcome(net: network.Network, config: dict, traffic: dict, seed: int,
            prof, res, summaries: list[tuple],
            segments: list[dict]) -> tuple[dict, dict]:
    """(checks, reference stats): checks maps each compared number's name to
    ``{"value": ..., "limit": ...}``.  ``segments`` are the job's NoC
    replays, as `run.recording` lists them."""
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
        w, h = int(tc["mesh_w"]), int(tc["mesh_h"])
        # Under multicast every firing of a neuron reaches the same cores:
        # the reference counts and replays the trace from its firings.
        hop = (reference.multicast_avg_hop(net, fires, part, placement, w)
               if cast == "multicast"
               else reference.avg_hop(trace, n, part, placement, w, cast))
        checks["placement_avg_hop"] = abs(res.mapping.avg_hop - hop)
        noc = {"link_capacity": tc["link_capacity"],
               "inject_capacity": tc["noc_kwargs"]["inject_capacity"],
               "energy_pj": config["energy_pj"]}
        if "fault_schedule" in tc:
            stats = _faulted(checks, tc, noc, trace, n, part, placement, res,
                             segments)
        elif cast == "multicast":
            stats = reference.replay_firings(net, ref["firings"], part,
                                             placement, w, h, noc)
        else:
            stats = reference.replay(trace, n, part, placement, w, h, noc,
                                     cast)
        got = dataclasses.asdict(res.noc)
        for f in NOC_FIELDS:
            checks[f"noc_{f}"] = None if stats is None else _gap(got[f], stats[f])
    last = summaries[-1]
    checks["jobs_differing"] = sum(1 for s in summaries if s != last)
    out = {name: {"value": value, "limit": 0} for name, value in checks.items()}
    # A layer that could not be compared (its input was invalid) fails.
    names = ["partition_objective", "placement_avg_hop"]
    if "fault_schedule" in tc:
        names += FAULT_CHECKS + [f"noc_{f}" for f in NOC_FIELDS]
    for name in names:
        out.setdefault(name, {"value": None, "limit": 0})
    if stats is not None:
        stats["transmissions"] = int(trace.shape[0])
    return out, stats


FAULT_CHECKS = ["fault_segments", "fault_stale_mapping", "remap_invalid",
                "remap_migrated"]


def _faulted(checks: dict, tc: dict, noc: dict, trace: np.ndarray, n: int,
             part: np.ndarray, placement: np.ndarray, res,
             segments: list[dict]) -> dict | None:
    """The fault checks of a job, into ``checks``; returns the reference's
    combined replay of its segments, or None where the segments do not
    match the reference's."""
    w, h = int(tc["mesh_w"]), int(tc["mesh_h"])
    cores = w * h
    t_end = int(trace[-1] // (np.int64(n) * n)) + 1 if trace.shape[0] else 0
    want = reference.replayed(reference.fault_segments(
        tc["fault_schedule"], int(tc["detect_windows"]), t_end, w, h), trace, n)
    checks["fault_segments"] = abs(len(segments) - len(want)) + sum(
        1 for g, s in zip(segments, want)
        if (g["records"], g["t_first"], g["t_last"])
        != (s["records"], s["t_first"], s["t_last"]))
    stale = invalid = moved = 0
    prev = placement[part]
    mapped = True  # every segment's mapping gives each neuron a core
    for g, s in zip(segments, want):
        if s["avoid"] is not None:
            invalid += reference.remap_violations(
                g["part"], g["placement"], int(tc["capacity"]), cores, s["avoid"])
        if g["part"].shape != part.shape or (g["part"] < 0).any() \
                or (g["part"] >= g["placement"].shape[0]).any():
            mapped = False
            break
        core = g["placement"][g["part"]]
        changed = int((core != prev).sum())
        if s["repaired"]:
            moved += changed
        else:
            stale += int(changed > 0)
        prev = core
    checks["fault_stale_mapping"] = stale
    checks["remap_invalid"] = invalid
    reported = (res.degradation or {}).get("neurons_migrated")
    checks["remap_migrated"] = None if reported is None else abs(reported - moved)
    if checks["fault_segments"] or not mapped:
        return None
    return reference.combine([
        reference.replay_faulty(s["keys"], n, g["part"], g["placement"], w, h,
                                noc, s["dead"], s["blocked"])
        for g, s in zip(segments, want)])


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
