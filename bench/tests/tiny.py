"""A cell small enough for the CPU: Smooth_320 on a 3x3 mesh, or two
random layers of the same size."""
import copy
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

NETWORK = {"name": "smooth_320", "layers": [160, 160],
           "connections": [{"kind": "local", "radius": 1}], "gain": 2.0,
           "weight_quantum": 2.0 ** -20, "input_rate": 0.14, "input_amp": 1.5,
           "table1_transmissions": 60000}

# Random_6212's connectivity at the size of Smooth_320.
RANDOM = {"name": "random_320", "layers": [160, 160],
          "connections": [{"kind": "random", "p": 0.1}], "gain": 2.5,
          "weight_quantum": 2.0 ** -20, "input_rate": 0.12, "input_amp": 1.5,
          "table1_transmissions": 40000, "seed": 3}


# Fault schedules on the tiny job's 3x3 mesh, where its six parts sit on
# cores 4, 7, 2, 5, 8 and 1: links that carry traffic, and two busy cores.
LINKS_ONLY = [{"t": 60, "kind": "link", "from": 1, "to": 2},
              {"t": 120, "kind": "link", "from": 4, "to": 1}]
CORES = [{"t": 50, "kind": "link", "from": 1, "to": 2},
         {"t": 100, "kind": "core", "ids": [4]},
         {"t": 200, "kind": "core", "ids": [8]}]


def config(cast: str = "unicast", faults: list | None = None,
           network: dict = NETWORK) -> dict:
    with open(BENCH / "configs" / "edge_5120_unicast_5x5.json") as f:
        cfg = json.load(f)
    cfg["network"] = copy.deepcopy(network)
    cfg["toolchain"].update(
        mesh_w=3, mesh_h=3, capacity=64,
        objective="cut" if cast == "unicast" else "volume", cast=cast,
        noc_kwargs={"stepper": "jax", "screen": "interpret",
                    "inject_capacity": 256},
        # A short search: on the CPU every step of the device scan is an
        # event of the profiler's trace.
        mapper_kwargs={"iters": 640})
    if faults is not None:
        # The fault-aware replay runs on the host backends only.
        cfg["toolchain"].update(
            fault_schedule=copy.deepcopy(faults), detect_windows=2,
            remap_strategy="incremental",
            noc_kwargs={"stepper": "numpy", "screen": "numpy",
                        "inject_capacity": 256})
    return cfg


def traffic() -> dict:
    with open(BENCH / "traffic" / "table1.json") as f:
        t = json.load(f)
    t["num_steps"] = 400
    return t


def run(cast="unicast", trace=False, seed=2**33 + 5, faults=None,
        network=NETWORK):
    """One run of the harness at the tiny size, off the chip."""
    import run as harness

    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    metrics = bench["per_layer" if trace else "end_to_end"]
    return harness.run_cell(config(cast, faults, network), traffic(), seed,
                            0.0, trace, metrics, require_tpu=False,
                            compile_cache=False, log=lambda _: None)
