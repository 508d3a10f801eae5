"""Least work of the device programs the benchmark reads a roofline for.

The LIF recurrence of a profile (`snn/lif.py` ``_lif_scan``) needs, for the
steps the profile keeps and no others:

  * 8 bytes per transmission of the kept trace: the weight (float32) and
    the target index (int32) of each synapse of a neuron that fired;
  * 21 bytes per neuron per kept step: the membrane potential and the
    refractory counter read and written (4 bytes each way, each), the
    drive read (4 bytes) and the raster written (1 byte).

Its arithmetic (one add per transmission, a few per neuron and step) is
negligible against those bytes, so bandwidth bounds it.  The count is the
same whatever implements the step: dense, event-driven, or stopping at the
truncation step.
"""
from __future__ import annotations

import json
from pathlib import Path

BYTES_PER_TRANSMISSION = 4 + 4
BYTES_PER_NEURON_STEP = 4 * 2 + 4 * 2 + 4 + 1


def lif_least_bytes(neurons: int, kept_steps: int, transmissions: int) -> int:
    """Bytes the LIF recurrence must move to produce a profile."""
    return (BYTES_PER_TRANSMISSION * transmissions
            + BYTES_PER_NEURON_STEP * neurons * kept_steps)


def lif_least_seconds(neurons: int, kept_steps: int, transmissions: int,
                      peak: dict) -> float:
    """The least time the chip could take: least bytes over HBM bandwidth."""
    return (lif_least_bytes(neurons, kept_steps, transmissions)
            / peak["hbm_bytes_per_s"])


def peak_for(kind: str) -> dict:
    """The peaks of a device kind from ``peaks.json``; a kind missing from
    the table is an error, not a default."""
    with open(Path(__file__).resolve().parent / "peaks.json") as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table[kind]
