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


def test_violations():
    assert reference.partition_violations(np.array([0, 1, 1]), 2, 2, 4) == 0
    assert reference.partition_violations(np.array([0, 1, 1]), 2, 1, 4) == 1
    assert reference.partition_violations(np.array([0, 2, 1]), 2, 2, 4) == 1
    assert reference.partition_violations(np.array([0, 1, 1]), 2, 2, 1) == 1
    assert reference.placement_violations(np.array([0, 3]), 4) == 0
    assert reference.placement_violations(np.array([0, 0]), 4) == 1
    assert reference.placement_violations(np.array([0, 4]), 4) == 1
