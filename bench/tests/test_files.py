"""Every file that BENCHMARK.json names loads, and keeps to its role."""
import importlib.util
import json

import pytest
from conftest import BENCH

ROOT = BENCH.parent
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
METRICS = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda c: c["name"])
def test_config_loads(entry):
    cfg = json.loads((ROOT / entry["file"]).read_text())
    assert cfg["name"] == entry["name"]
    assert cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"]
    for key in ("network", "lif", "toolchain", "energy_pj", "assumed"):
        assert key in cfg


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda w: w["name"])
def test_cell_names_its_files(cell):
    import run

    bench, found, config, traffic = run.load_cell(cell["name"])
    assert found == cell
    assert config["name"] == cell["config"]
    assert traffic["num_steps"] > 0


@pytest.mark.parametrize("name", METRICS)
def test_metric_reader_loads(name):
    spec = importlib.util.spec_from_file_location(
        name, BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert callable(mod.read)


@pytest.mark.parametrize("faults", ["LINKS_ONLY", "CORES"])
def test_fault_schedule_loads(faults):
    """The harness builds the program's schedule from a configuration's
    events, and the program and the reference agree on what has failed
    once they all have: the same dead cores and the same blocked links."""
    import numpy as np

    import reference
    import run
    import tiny

    tc = tiny.config(faults=getattr(tiny, faults))["toolchain"]
    w, h = tc["mesh_w"], tc["mesh_h"]
    schedule = run.toolchain_kwargs(tc)["fault_schedule"]
    assert len(schedule) == len(tc["fault_schedule"])
    last = max(e["t"] for e in tc["fault_schedule"])
    state = schedule.state_at(last, w, h)
    dead, blocked = reference.fault_state(tc["fault_schedule"], last, w, h)
    assert np.array_equal(state.dead_cores, dead)
    assert np.array_equal(state.blocked_links(), blocked)
    assert int(state.dead_links.sum()) == sum(
        e["kind"] == "link" for e in tc["fault_schedule"])


def test_a_link_between_cores_that_are_not_neighbours_is_refused():
    import run

    tc = {"mesh_w": 3, "mesh_h": 3,
          "fault_schedule": [{"t": 1, "kind": "link", "from": 0, "to": 2}]}
    with pytest.raises(ValueError, match="no mesh link"):
        run.toolchain_kwargs(tc)


# SNEAP Table 1's MLP_2048 and Random_6212 as the program's `make_snn`
# lists them.
MLP_2048 = {"name": "mlp_2048", "layers": [1024, 1024],
            "connections": [{"kind": "full"}], "gain": 2.0,
            "weight_quantum": 2.0 ** -20, "input_rate": 0.06,
            "input_amp": 1.5, "table1_transmissions": 15_905_792}
RANDOM_6212 = {"name": "random_6212", "layers": [2071, 2070, 2071],
               "connections": [{"kind": "random", "p": 0.10},
                               {"kind": "random", "p": 0.10}],
               "gain": 2.5, "weight_quantum": 2.0 ** -20, "input_rate": 0.12,
               "input_amp": 1.5, "table1_transmissions": 51_756_245,
               "seed": 0}
NETWORKS = [json.loads((ROOT / c["file"]).read_text())["network"]
            for c in SPEC["configs"]] + [MLP_2048, RANDOM_6212]


@pytest.mark.parametrize("spec", NETWORKS, ids=lambda s: s["name"])
def test_networks_match_make_snn(spec):
    """The benchmark's own network generator makes the Table 1 networks the
    program's `make_snn` makes, synapse for synapse and weight for weight."""
    import numpy as np

    import network
    from repro.snn import make_snn

    net = network.build_network(spec)
    topo = make_snn(spec["name"])
    assert np.array_equal(net.weights, topo.weights)
    n = net.num_neurons
    assert np.array_equal(
        np.sort(net.syn_src * n + net.syn_dst),
        np.sort(topo.syn_src.astype(np.int64) * n + topo.syn_dst))
    assert net.target_spikes == topo.target_spikes
    assert net.input_rate == topo.input_rate


def test_an_unknown_connectivity_names_its_missing_file():
    import network
    import tiny

    spec = dict(tiny.NETWORK, connections=[{"kind": "ring"}])
    with pytest.raises(ValueError, match=r"no file bench/networks/ring\.py"):
        network.build_network(spec)
