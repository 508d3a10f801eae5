"""The benchmark's inputs, made from data: a network from a configuration
file and a stimulus from a traffic file and the run's seed.

A configuration's ``network`` block names its layers, the connectivity
between each pair of consecutive layers, the fan-in gain, the weight grid,
the stimulus rate and amplitude, the Table 1 transmission count, at which
the profile's trace is cut, and the ``seed`` (default 0) of the generator
that random connectivity draws from.  Each connectivity ``kind`` is a file
of its own, ``bench/networks/<kind>.py``, whose ``connect(spec, n_src,
n_dst, rng)`` returns the (source, destination) index pairs of one pair of
layers; one generator is handed to each pair in order.  The construction
follows the one the SNEAP paper's networks use (CARLsim image-processing
tutorials for Smooth/Edge, a fully connected MLP, random layers), with
weights on a 2^-20 grid so that every synaptic sum is exact in float32.
The same seed gives the same stimulus.
"""
from __future__ import annotations

import importlib.util
from dataclasses import dataclass
from pathlib import Path

import numpy as np


@dataclass
class Network:
    name: str
    layer_sizes: list[int]
    syn_src: np.ndarray  # (E,) int64, synapses sorted by source, stable
    syn_dst: np.ndarray  # (E,) int64
    syn_w: np.ndarray  # (E,) float32 weight of each synapse
    weights: np.ndarray  # (N, N) float32 dense matrix, weights[i, j]: i -> j
    input_size: int
    input_rate: float
    input_amp: float
    target_spikes: int | None

    @property
    def num_neurons(self) -> int:
        return int(sum(self.layer_sizes))

    @property
    def xadj(self) -> np.ndarray:
        """CSR row starts of the outgoing synapses of each neuron."""
        counts = np.bincount(self.syn_src, minlength=self.num_neurons)
        return np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)


NETWORKS = Path(__file__).resolve().parent / "networks"


def connectivity(kind: str):
    """The ``connect`` of ``bench/networks/<kind>.py``."""
    path = NETWORKS / f"{kind}.py"
    if not path.is_file():
        raise ValueError(f"unknown connectivity {kind!r}: no file "
                         f"bench/networks/{kind}.py")
    spec = importlib.util.spec_from_file_location(f"bench_network_{kind}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.connect


def build_network(spec: dict) -> Network:
    """The network of a configuration's ``network`` block."""
    sizes = [int(s) for s in spec["layers"]]
    if len(spec["connections"]) != len(sizes) - 1:
        raise ValueError("one connection spec per pair of consecutive layers")
    n = sum(sizes)
    offsets = np.cumsum([0] + sizes)
    quantum = float(spec["weight_quantum"])
    gain = float(spec["gain"])
    weights = np.zeros((n, n), dtype=np.float32)
    rng = np.random.default_rng(int(spec.get("seed", 0)))
    all_src, all_dst = [], []
    for li, conn in enumerate(spec["connections"]):
        s, d = connectivity(conn["kind"])(conn, sizes[li], sizes[li + 1], rng)
        gs = np.asarray(s, dtype=np.int64) + offsets[li]
        gd = np.asarray(d, dtype=np.int64) + offsets[li + 1]
        # Fan-in normalisation, rounded to the grid.
        fan_in = np.bincount(gd, minlength=n).astype(np.float64)
        weights[gs, gd] = np.round(gain / np.maximum(fan_in[gd], 1.0)
                                   / quantum) * quantum
        all_src.append(gs)
        all_dst.append(gd)
    src = np.concatenate(all_src)
    dst = np.concatenate(all_dst)
    order = np.argsort(src, kind="stable")
    src, dst = src[order], dst[order]
    target = spec.get("table1_transmissions")
    return Network(
        name=spec["name"], layer_sizes=sizes, syn_src=src, syn_dst=dst,
        syn_w=weights[src, dst], weights=weights, input_size=sizes[0],
        input_rate=float(spec["input_rate"]),
        input_amp=float(spec["input_amp"]),
        target_spikes=int(target) if target is not None else None,
    )


def input_drive(net: Network, num_steps: int, seed: int) -> np.ndarray:
    """(T, N) float32 stimulus: Bernoulli events on the input layer, drawn
    from ``seed`` (the profiler's own stimulus rule)."""
    rng = np.random.default_rng(seed)
    drive = np.zeros((num_steps, net.num_neurons), dtype=np.float32)
    events = rng.random((num_steps, net.input_size)) < net.input_rate
    drive[:, :net.input_size] = events * net.input_amp
    return drive
