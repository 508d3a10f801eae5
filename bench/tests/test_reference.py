"""The plain reference against the program's own host references, at small
sizes: the same answers from independent code."""
import dataclasses

import numpy as np
import pytest

import network
import reference
import tiny

NOC = {"link_capacity": 2, "inject_capacity": 3,
       "energy_pj": {"router": 0.98, "link": 0.34, "local": 0.1}}


def _random_job(seed, n=60, k=7, w=3, h=3, steps=12, records=900):
    rng = np.random.default_rng(seed)
    keys = np.unique((rng.integers(0, steps, records) * np.int64(n)
                      + rng.integers(0, n, records)) * n
                     + rng.integers(0, n, records))
    part = rng.integers(0, k, n)
    placement = rng.permutation(w * h)[:k]
    return keys, part, placement


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("cast", ["unicast", "multicast"])
def test_replay_matches_the_program(seed, cast):
    """Unicast against the program's scalar reference engine, multicast
    against its tree-fork engine: every statistic equal."""
    from repro.nocsim import simulate_noc
    from repro.nocsim.energy import EnergyModel

    n, w, h = 60, 3, 3
    keys, part, placement = _random_job(seed, n=n, w=w, h=h)
    t, s, d = reference.unpack(keys, n)
    engine = "ref" if cast == "unicast" else "batched"
    got = dataclasses.asdict(simulate_noc(
        t.astype(np.int32), s.astype(np.int32), d.astype(np.int32), part,
        placement, w, h, link_capacity=NOC["link_capacity"],
        inject_capacity=NOC["inject_capacity"], cast=cast, engine=engine,
        energy=EnergyModel()))
    want = reference.replay(keys, n, part, placement, w, h, NOC, cast)
    assert want["congestion_count"] > 0  # the queues are exercised
    for field, value in want.items():
        if isinstance(value, np.ndarray):
            assert np.array_equal(got[field], value), field
        else:
            assert got[field] == value, field


def test_profile_matches_the_program():
    """Raster statistics and trace of `profile_snn` on the tiny network."""
    from repro.snn import LIFParams, profile_snn

    import run

    cfg, traffic = tiny.config(), tiny.traffic()
    net = network.build_network(cfg["network"])
    prof = profile_snn(run.make_topology(net), num_steps=traffic["num_steps"],
                       seed=11, params=LIFParams())
    ref = reference.profile(net, network.input_drive(
        net, traffic["num_steps"], 11), cfg["lif"])
    assert ref["num_steps"] == prof.num_steps < traffic["num_steps"]
    assert np.array_equal(ref["fire_counts"], prof.fire_counts)
    keys = reference.trace_keys(prof.trace_t, prof.trace_src, prof.trace_dst,
                                net.num_neurons)
    assert reference.set_mismatch(keys, ref["trace"]) == 0


def test_objectives_and_avg_hop_match_the_program():
    from repro.core import comm_volume, edge_cut
    from repro.core.hopcost import traffic_matrix
    from repro.core.placecost import evaluate_placement
    from repro.snn import LIFParams, profile_snn

    import run

    cfg, traffic = tiny.config(), tiny.traffic()
    net = network.build_network(cfg["network"])
    prof = profile_snn(run.make_topology(net), num_steps=traffic["num_steps"],
                       seed=3, params=LIFParams())
    rng = np.random.default_rng(0)
    part = rng.integers(0, 5, net.num_neurons)
    placement = rng.permutation(9)[:5]
    keys = reference.trace_keys(prof.trace_t, prof.trace_src, prof.trace_dst,
                                net.num_neurons)
    assert reference.cut_spikes(net, prof.fire_counts, part) == \
        edge_cut(prof.graph, part)
    assert reference.multicast_volume(net, prof.fire_counts, part) == \
        comm_volume(prof.graph.hyper, part)
    for cast in ("unicast", "multicast"):
        tm = traffic_matrix(part, prof.trace_src, prof.trace_dst, 5,
                            trace_t=prof.trace_t, cast=cast)
        want, _ = evaluate_placement(placement, tm, 9, 3, int(tm.sum()))
        assert reference.avg_hop(keys, net.num_neurons, part, placement, 3,
                                 cast) == want


def _assert_same(got: dict, want: dict) -> None:
    for field, value in want.items():
        if isinstance(value, np.ndarray):
            assert np.array_equal(got[field], value), field
        else:
            assert got[field] == value, field


@pytest.mark.parametrize("seed", [0, 1])
def test_random_job_replays_as_the_program(seed):
    """A random-connectivity job (the tiny random network drawn from
    ``seed``, a random partition and placement on a 3x3 mesh, one packet
    per link per cycle): the reference's replay from the firings, the
    check's path, equals its replay of the trace and the program's
    multicast replay in every field; its hop count from the fire counts
    equals the one from the trace."""
    from repro.nocsim import simulate_noc
    from repro.nocsim.energy import EnergyModel

    cfg, traffic = tiny.config(), tiny.traffic()
    net = network.build_network(dict(tiny.RANDOM, seed=seed))
    ref = reference.profile(net, network.input_drive(
        net, traffic["num_steps"], seed), cfg["lif"])
    n, w, h = net.num_neurons, 3, 3
    rng = np.random.default_rng(seed)
    part = rng.integers(0, 6, n)
    placement = rng.permutation(w * h)[:6]
    noc = dict(NOC, link_capacity=1)
    want = reference.replay_firings(net, ref["firings"], part, placement, w,
                                    h, noc)
    assert want["congestion_count"] > 0  # the queues are exercised
    _assert_same(reference.replay(ref["trace"], n, part, placement, w, h, noc,
                                  "multicast"), want)
    t, s, d = reference.unpack(ref["trace"], n)
    _assert_same(dataclasses.asdict(simulate_noc(
        t.astype(np.int32), s.astype(np.int32), d.astype(np.int32), part,
        placement, w, h, link_capacity=1,
        inject_capacity=NOC["inject_capacity"], cast="multicast",
        engine="batched", energy=EnergyModel())), want)
    assert reference.multicast_avg_hop(net, ref["fire_counts"], part,
                                       placement, w) == \
        reference.avg_hop(ref["trace"], n, part, placement, w, "multicast")


def test_violations():
    assert reference.partition_violations(np.array([0, 1, 1]), 2, 2, 4) == 0
    assert reference.partition_violations(np.array([0, 1, 1]), 2, 1, 4) == 1
    assert reference.partition_violations(np.array([0, 2, 1]), 2, 2, 4) == 1
    assert reference.partition_violations(np.array([0, 1, 1]), 2, 2, 1) == 1
    assert reference.placement_violations(np.array([0, 3]), 4) == 0
    assert reference.placement_violations(np.array([0, 0]), 4) == 1
    assert reference.placement_violations(np.array([0, 4]), 4) == 1


FAULTS = [  # (dead cores, dead links by tail and head core) on a 3x3 mesh
    ([4], []),
    ([], [(1, 2), (3, 4)]),
    ([8], [(4, 1), (0, 3)]),
]


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("faults", FAULTS, ids=["core", "links", "both"])
@pytest.mark.parametrize("engine", ["batched", "ref"])
def test_faulty_replay_matches_the_program(seed, faults, engine):
    """Drops, YX detours and the queued replay under failures, against
    the program's batched and scalar engines: every statistic equal."""
    from repro.nocsim import simulate_noc
    from repro.nocsim.energy import EnergyModel
    from repro.runtime.faults import FaultState

    n, w, h = 60, 3, 3
    keys, part, placement = _random_job(seed, n=n, w=w, h=h)
    cores, links = faults
    events = ([{"t": 0, "kind": "core", "ids": cores}] +
              [{"t": 0, "kind": "link", "from": a, "to": b} for a, b in links])
    dead, blocked = reference.fault_state(events, 0, w, h)
    state = FaultState.none(w, h)
    state.dead_cores[cores] = True
    for a, b in links:
        state.dead_links[reference.link_of(a, b, w, h)] = True
    t, s, d = reference.unpack(keys, n)
    got = dataclasses.asdict(simulate_noc(
        t.astype(np.int32), s.astype(np.int32), d.astype(np.int32), part,
        placement, w, h, link_capacity=NOC["link_capacity"],
        inject_capacity=NOC["inject_capacity"], engine=engine,
        energy=EnergyModel(), faults=state))
    want = reference.replay_faulty(keys, n, part, placement, w, h, NOC, dead,
                                   blocked)
    assert want["spikes_dropped"] > 0
    for field, value in want.items():
        if isinstance(value, np.ndarray):
            assert np.array_equal(got[field], value), field
        else:
            assert got[field] == value, field


def test_a_detour_goes_y_first():
    """A packet from core 0 to core 4 of a 3x3 mesh whose XY route's first
    link (0 -> 1) is dead goes 0 -> 3 -> 4 and counts its 2 hops."""
    n, w, h = 2, 3, 3
    keys = np.array([(0 * n + 0) * n + 1])  # step 0, neuron 0 -> neuron 1
    part, placement = np.array([0, 1]), np.array([0, 4])
    dead, blocked = reference.fault_state(
        [{"t": 0, "kind": "link", "from": 0, "to": 1}], 0, w, h)
    st = reference.replay_faulty(keys, n, part, placement, w, h, NOC, dead,
                                 blocked)
    assert (st["detour_hops"], st["spikes_dropped"], st["avg_latency"]) == (2, 0, 2)
    south, east = reference.link_of(0, 3, w, h), reference.link_of(3, 4, w, h)
    assert np.flatnonzero(st["per_link_hops"]).tolist() == sorted([south, east])


def test_fault_segments_by_hand():
    """A link dies at 10, a core at 20 and 22, a core past the trace's end:
    segments [0, 10), [10, 20), the lag [20, 22), an empty one up to the
    next event, which starts the first repair's mapping, the lag [22, 24),
    then [24, 30) on the second repair."""
    events = [{"t": 10, "kind": "link", "from": 0, "to": 1},
              {"t": 20, "kind": "core", "ids": [4]},
              {"t": 22, "kind": "core", "ids": [8]},
              {"t": 40, "kind": "core", "ids": [0]}]
    segs = reference.fault_segments(events, 2, 30, 3, 3)
    assert [(s["lo"], s["hi"], s["lag"], s["repaired"]) for s in segs] == [
        (0, 10, False, False), (10, 20, False, False), (20, 22, True, False),
        (22, 22, False, True), (22, 24, True, False), (24, 30, False, True)]
    assert segs[1]["blocked"].sum() == 1 and not segs[1]["dead"].any()
    assert segs[2]["avoid"] is None and segs[4]["avoid"].tolist() == \
        segs[2]["dead"].tolist()
    assert segs[5]["dead"][[4, 8]].all() and not segs[5]["dead"][0]


def test_replayed_passes_a_repair_on_past_an_empty_segment():
    n = 2
    per_step = n * n
    keys = np.array([0, 1 * per_step, 5 * per_step + 1])  # steps 0, 1, 5
    segs = [{"lo": 0, "hi": 2, "repaired": False},
            {"lo": 2, "hi": 4, "repaired": True},
            {"lo": 4, "hi": 6, "repaired": False}]
    out = reference.replayed(segs, keys, n)
    assert [(s["records"], s["t_first"], s["t_last"], s["repaired"])
            for s in out] == [(2, 0, 1, False), (1, 5, 5, True)]


def test_combine_matches_the_programs_combination():
    from repro.nocsim import combine_stats
    from repro.nocsim.stats import NoCStats

    n, w, h = 60, 3, 3
    parts = []
    for seed in range(3):
        keys, part, placement = _random_job(seed, n=n, w=w, h=h)
        dead, blocked = reference.fault_state(
            [{"t": 0, "kind": "core", "ids": [seed]}], 0, w, h)
        parts.append(reference.replay_faulty(keys, n, part, placement, w, h,
                                             NOC, dead, blocked))
    want = dataclasses.asdict(combine_stats([NoCStats(**p) for p in parts]))
    got = reference.combine(parts)
    for field, value in want.items():
        if isinstance(value, np.ndarray):
            assert np.array_equal(got[field], value), field
        else:
            assert got[field] == value, field


def test_remap_violations():
    dead = np.array([False, True, False, False])
    ok = reference.remap_violations(np.array([0, 0, 1]), np.array([0, 2, 1]),
                                    2, 4, dead)
    assert ok == 0
    # Part 1 (one neuron) on dead core 1: the neuron and the part.
    assert reference.remap_violations(np.array([0, 0, 1]), np.array([0, 1, 2]),
                                      2, 4, dead) == 2
    # Over capacity, shared core, off the mesh, a neuron outside the parts.
    assert reference.remap_violations(np.array([0, 0, 0]), np.array([0, 2]),
                                      2, 4, dead) == 1
    assert reference.remap_violations(np.array([0, 1]), np.array([2, 2]),
                                      2, 4, dead) == 1
    assert reference.remap_violations(np.array([0, 1]), np.array([0, 4]),
                                      2, 4, dead) == 1
    assert reference.remap_violations(np.array([0, 3]), np.array([0, 2]),
                                      2, 4, dead) == 1
