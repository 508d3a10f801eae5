import numpy as np

from repro.sharding.layout import logical_traffic_matrix, sneap_device_layout


def test_logical_traffic_ring_edges():
    t = logical_traffic_matrix({"data": 4, "model": 4},
                               {"data": 1.0, "model": 10.0})
    # model-axis ring neighbors exchange the model volume symmetrically
    assert t[0, 1] == 10.0 and t[1, 0] == 10.0
    assert t[0, 4] == 1.0  # data neighbor
    assert t.sum() > 0 and np.allclose(t, t.T)


def test_layout_never_regresses_identity():
    order, base, optimized = sneap_device_layout(
        {"data": 8, "model": 8}, {"data": 1e6, "model": 64e6},
        phys_w=8, iters=8_000, seed=0)
    assert sorted(order.tolist()) == list(range(64))
    assert optimized <= base + 1e-9


def test_layout_respects_dead_chips():
    order, base, optimized = sneap_device_layout(
        {"data": 6, "model": 10}, {"data": 1e6, "model": 64e6},
        phys_w=8, iters=10_000, seed=0, dead_chips=[5, 22, 40, 41])
    alive = [c for c in range(64) if c not in (5, 22, 40, 41)]
    assert sorted(order.tolist()) == alive
    assert optimized <= base


def test_layout_improves_alltoall_traffic():
    """MoE expert-parallel all-to-all on the model axis: row-major lines
    are suboptimal (compact blocks have lower mean pairwise distance);
    seeded-hot SA must strictly improve."""
    order, base, optimized = sneap_device_layout(
        {"data": 16, "model": 16}, {"data": 5e8, "model": 5e9},
        phys_w=16, iters=120_000, seed=0, patterns={"model": "alltoall"})
    assert optimized < base * 0.95
    assert sorted(order.tolist()) == list(range(256))
