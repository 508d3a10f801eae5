"""The least-work count of the LIF recurrence, by hand."""
import pytest

import work


def test_least_bytes_three_neurons():
    # Three neurons, 0 -> 1, 0 -> 2 and 1 -> 2; a raster of 2 kept steps
    # in which neuron 0 fires at step 0 and neuron 1 at step 1: the kept
    # trace holds 2 + 1 = 3 transmissions.
    #   transmissions: 3 x (4-byte weight + 4-byte target)      =  24
    #   state:         3 neurons x 2 steps x (4+4 potential, 4+4
    #                  refractory, 4 drive, 1 raster byte)       = 126
    assert work.lif_least_bytes(neurons=3, kept_steps=2, transmissions=3) == 150


def test_least_seconds_uses_the_bandwidth():
    peak = {"hbm_bytes_per_s": 150.0}
    assert work.lif_least_seconds(3, 2, 3, peak) == pytest.approx(1.0)


def test_peak_table_refuses_unknown_devices():
    assert work.peak_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peak_for("cpu")
