"""The benchmark's inputs, made from data: a network from a configuration
file and a stimulus from a traffic file and the run's seed.

A configuration's ``network`` block names its layers, the connectivity
between each pair of consecutive layers (``local`` receptive fields on 2D
grids, or ``full``), the fan-in gain, the weight grid, the stimulus rate and
amplitude, and the Table 1 transmission count, at which the profile's trace
is cut.  The construction follows the one the SNEAP paper's networks use
(CARLsim image-processing tutorials for Smooth/Edge, a fully connected
MLP), with weights on a 2^-20 grid so that every synaptic sum is exact in
float32.  The same seed gives the same stimulus.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

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


def _grid(n: int) -> tuple[int, int]:
    """Near-square (h, w) with h * w == n."""
    h = int(math.sqrt(n))
    while n % h:
        h -= 1
    return h, n // h


def _local(n_src: int, n_dst: int, radius: int) -> tuple[np.ndarray, np.ndarray]:
    """Receptive fields: each source feeds the (2r+1)^2 block around its
    position scaled into the destination grid."""
    hs, ws = _grid(n_src)
    hd, wd = _grid(n_dst)
    src_r, src_c = np.divmod(np.arange(n_src), ws)
    ctr_r = (src_r * hd) // hs
    ctr_c = (src_c * wd) // ws
    srcs, dsts = [], []
    for dr in range(-radius, radius + 1):
        for dc in range(-radius, radius + 1):
            rr, cc = ctr_r + dr, ctr_c + dc
            ok = (rr >= 0) & (rr < hd) & (cc >= 0) & (cc < wd)
            srcs.append(np.nonzero(ok)[0])
            dsts.append(rr[ok] * wd + cc[ok])
    return np.concatenate(srcs), np.concatenate(dsts)


def _connect(spec: dict, n_src: int, n_dst: int) -> tuple[np.ndarray, np.ndarray]:
    kind = spec["kind"]
    if kind == "local":
        return _local(n_src, n_dst, int(spec["radius"]))
    if kind == "full":
        return (np.repeat(np.arange(n_src), n_dst),
                np.tile(np.arange(n_dst), n_src))
    raise ValueError(f"unknown connectivity {kind!r}")


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
    all_src, all_dst = [], []
    for li, conn in enumerate(spec["connections"]):
        s, d = _connect(conn, sizes[li], sizes[li + 1])
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
