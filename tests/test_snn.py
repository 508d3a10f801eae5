import jax.numpy as jnp
import numpy as np

from repro.snn.lif import LIFParams, lif_run
from repro.snn.simulate import _expand_trace, profile_snn
from repro.snn.topology import PAPER_SNNS, make_snn


def test_lif_fires_on_suprathreshold_input():
    n = 4
    w = jnp.zeros((n, n), jnp.float32)
    drive = np.zeros((10, n), np.float32)
    drive[2, 1] = 5.0  # strong input to neuron 1 at t=2
    raster = lif_run(w, jnp.asarray(drive), LIFParams(threshold=1.0))
    assert raster[2, 1] == 1
    assert raster.sum() == 1  # nothing else fires


def test_lif_subthreshold_decays_no_fire():
    n = 2
    w = jnp.zeros((n, n), jnp.float32)
    drive = np.full((50, n), 0.05, np.float32)  # steady-state v = .05/(1-.9) = .5
    raster = lif_run(w, jnp.asarray(drive), LIFParams(decay=0.9, threshold=1.0))
    assert raster.sum() == 0


def test_lif_synaptic_propagation():
    # 0 -> 1 with strong synapse: firing 0 at t fires 1 at t+1
    w = jnp.zeros((2, 2), jnp.float32).at[0, 1].set(2.0)
    drive = np.zeros((6, 2), np.float32)
    drive[1, 0] = 2.0
    raster = lif_run(w, jnp.asarray(drive), LIFParams())
    assert raster[1, 0] == 1 and raster[2, 1] == 1


def test_expand_trace_counts():
    raster = np.zeros((3, 3), np.uint8)
    raster[0, 0] = 1
    raster[2, 1] = 1
    xadj = np.array([0, 2, 3, 3])  # n0 -> {a, b}, n1 -> {c}
    adjncy = np.array([1, 2, 2])
    t, s, d = _expand_trace(raster, xadj, adjncy)
    assert len(t) == 3
    assert (s == np.array([0, 0, 1])).all()
    assert (d == np.array([1, 2, 2])).all()
    assert (t == np.array([0, 0, 2])).all()


def test_profile_consistency_small():
    topo = make_snn("smooth_320")
    prof = profile_snn(topo, num_steps=100, seed=0)
    # graph total weight == number of trace transmissions (both count
    # per-synapse spike deliveries over the window)
    assert prof.graph.total_adjwgt == prof.num_spikes
    assert prof.graph.num_vertices == topo.num_neurons
    # every trace record rides an existing synapse
    syn = set(zip(topo.syn_src.tolist(), topo.syn_dst.tolist()))
    pick = np.random.default_rng(0).integers(0, prof.num_spikes, 50)
    for i in pick:
        assert (int(prof.trace_src[i]), int(prof.trace_dst[i])) in syn


def test_profile_cache_misses_on_content_change(tmp_path):
    """Regression: same-name, same-size topology with different weights
    must miss the cache instead of returning the stale profile."""
    topo = make_snn("smooth_320")
    first = profile_snn(topo, num_steps=100, seed=0, cache_dir=tmp_path)
    assert len(list(tmp_path.glob("profile_*.npz"))) == 1

    # Rebuild the "same" network with different synaptic weights.
    mutated = make_snn("smooth_320")
    mutated.weights = mutated.weights * 1.5
    second = profile_snn(mutated, num_steps=100, seed=0, cache_dir=tmp_path)
    assert len(list(tmp_path.glob("profile_*.npz"))) == 2  # cache miss
    assert not np.array_equal(first.fire_counts, second.fire_counts) or \
        first.num_spikes != second.num_spikes

    # The unmutated topology still hits its own entry bitwise.
    again = profile_snn(make_snn("smooth_320"), num_steps=100, seed=0,
                        cache_dir=tmp_path)
    assert len(list(tmp_path.glob("profile_*.npz"))) == 2  # cache hit
    assert np.array_equal(first.trace_t, again.trace_t)
    assert np.array_equal(first.trace_src, again.trace_src)
    assert np.array_equal(first.fire_counts, again.fire_counts)


def test_all_paper_snns_build():
    for name in PAPER_SNNS:
        topo = make_snn(name)
        assert topo.num_neurons == int(name.split("_")[1])
        assert topo.weights.shape == (topo.num_neurons,) * 2


def test_lif_raster_matches_numpy_reference():
    """jnp, Pallas and the host numpy recurrence agree bit for bit, on an
    edge_5120-shaped net whose 0.1 weights make ten inputs hit the threshold
    exactly: only weights on which every sum is exact make that hold."""
    from repro.snn.lif import lif_run_ref
    from repro.snn.simulate import input_drive
    from repro.snn.topology import _assemble, _local_edges

    topo = _assemble("edge_small", [512, 512, 256],
                     [_local_edges(512, 512, 2), _local_edges(512, 256, 2)],
                     gain=2.5, input_rate=0.10, target_spikes=None)
    drive = input_drive(topo, 300, seed=0)
    ref = lif_run_ref(topo.weights, drive, LIFParams())
    assert ref.sum() > 10_000
    for use_pallas in (False, True):
        raster = lif_run(topo.weights, drive, LIFParams(), use_pallas=use_pallas)
        assert np.array_equal(raster, ref)
