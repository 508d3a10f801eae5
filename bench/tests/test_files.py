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


def test_networks_match_make_snn():
    """The benchmark's own network generator makes the Table 1 networks the
    program's `make_snn` makes, synapse for synapse and weight for weight."""
    import numpy as np

    import network
    from repro.snn import make_snn

    for entry in SPEC["configs"]:
        spec = json.loads((ROOT / entry["file"]).read_text())["network"]
        net = network.build_network(spec)
        topo = make_snn(spec["name"])
        assert np.array_equal(net.weights, topo.weights)
        n = net.num_neurons
        assert np.array_equal(
            np.sort(net.syn_src * n + net.syn_dst),
            np.sort(topo.syn_src.astype(np.int64) * n + topo.syn_dst))
        assert net.target_spikes == topo.target_spikes
        assert net.input_rate == topo.input_rate
