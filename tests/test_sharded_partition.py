"""Sharded partitioning engine: plans, halos, parity, and scale guards.

Metamorphic contracts (ISSUE 10):

* the shard plan is a partition of [0, n) into contiguous blocks;
* halo exchange is exact — ``comm_volume_sharded`` equals the global
  ``comm_volume`` for every shard count, and sharded refinement is
  bitwise-identical to single-host (scheduling changes, semantics don't);
* sharded matching is invariant under the shard count (hash tie keys);
* fat conflict rounds keep batch gains exactly additive (the incremental
  score equals a from-scratch recount after refinement);
* the index-capacity audit raises loudly at >2^31 scale — shape math
  only, nothing near that size is allocated.
"""
import os

import numpy as np
import pytest

from repro.core.coarsen import LevelStore, coarsen, heavy_edge_matching_vec
from repro.core.graph import (
    IndexCapacityError,
    ShardedGraphView,
    build_graph,
    check_index_capacity,
    comm_volume,
    comm_volume_sharded,
    edge_partition_counts,
)
from repro.core.partition import sneap_partition
from repro.core.refine import VolumeState
from repro.core.refine_vec import refine_level_vec
from repro.sharding.planner import plan_vertex_shards

from conftest import fanout_snn_graph, random_hypergraph


def feasible_part(n: int, k: int, seed: int = 0) -> np.ndarray:
    """Balanced random partition (unit weights, so any equal split fits)."""
    r = np.random.default_rng(seed)
    part = np.arange(n) % k
    return r.permutation(part).astype(np.int64)


# ---------------------------------------------------------------- plans


def test_plan_vertex_shards_partitions_the_range():
    plan = plan_vertex_shards(103, 4)
    assert plan.num_shards == 4
    assert plan.bounds[0] == 0 and plan.bounds[-1] == 103
    blocks = [plan.block(s) for s in range(4)]
    assert all(lo < hi for lo, hi in blocks)
    assert [lo for lo, _ in blocks[1:]] == [hi for _, hi in blocks[:-1]]
    v = np.arange(103)
    owner = plan.owner(v)
    for s, (lo, hi) in enumerate(blocks):
        assert (owner[lo:hi] == s).all()


def test_plan_vertex_shards_split_routes_sorted_rows():
    plan = plan_vertex_shards(100, 3)
    rows = np.array([0, 5, 33, 34, 66, 99])
    parts = plan.split(rows)
    assert len(parts) == 3
    got = np.concatenate(parts)
    assert np.array_equal(got, rows)
    for s, chunk in enumerate(parts):
        lo, hi = plan.block(s)
        assert ((chunk >= lo) & (chunk < hi)).all()


# ---------------------------------------------------------------- halos


def test_halo_cut_is_exactly_external_neighbors():
    g = fanout_snn_graph(200, fan=5, seed=1)
    plan = plan_vertex_shards(200, 3)
    view = ShardedGraphView(g, plan)
    for s in range(3):
        lo, hi = plan.block(s)
        halo = view.halo(s, mode="cut")
        nbrs = g.adjncy[g.xadj[lo]:g.xadj[hi]].astype(np.int64)
        expect = np.unique(nbrs[(nbrs < lo) | (nbrs >= hi)])
        assert np.array_equal(np.sort(halo), expect)


def test_local_part_poisons_outside_halo():
    g = fanout_snn_graph(120, fan=4, seed=2)
    plan = plan_vertex_shards(120, 4)
    view = ShardedGraphView(g, plan)
    part = feasible_part(120, 6)
    lp = view.local_part(1, part, mode="cut")
    lo, hi = plan.block(1)
    assert np.array_equal(lp[lo:hi], part[lo:hi])
    halo = view.halo(1, mode="cut")
    assert np.array_equal(lp[halo], part[halo])
    covered = np.zeros(120, dtype=bool)
    covered[lo:hi] = True
    covered[halo] = True
    assert (lp[~covered] == -1).all()


@pytest.mark.parametrize("num_shards", [1, 2, 3, 5])
def test_comm_volume_sharded_matches_global(num_shards):
    g = random_hypergraph(150, 900, seed=3)
    part = feasible_part(150, 7, seed=4)
    plan = plan_vertex_shards(150, num_shards)
    assert comm_volume_sharded(g.hyper, part, plan) == comm_volume(g.hyper, part)


# ----------------------------------------------------- sharded refinement


@pytest.mark.parametrize("objective", ["cut", "volume"])
@pytest.mark.parametrize("shards", [2, 4])
def test_sharded_refine_bitwise_parity(objective, shards):
    """Sharding only reschedules evaluation: identical movers, identical
    score, identical partition — for any shard count."""
    g = fanout_snn_graph(600, fan=6, seed=5)
    part = feasible_part(600, 10, seed=6)
    base_part, base_score = refine_level_vec(
        g, part, k=10, capacity=80, objective=objective)
    got_part, got_score = refine_level_vec(
        g, part, k=10, capacity=80, objective=objective, shards=shards)
    assert got_score == base_score
    assert np.array_equal(got_part, base_part)


def test_fat_round_gains_exactly_additive():
    """The incremental score (sum of batch gains) must equal a from-scratch
    recount — any non-additive admission inside a fat conflict round would
    diverge here."""
    g = fanout_snn_graph(800, fan=8, seed=7)
    part = feasible_part(800, 12, seed=8)
    new_part, score = refine_level_vec(g, part, k=12, capacity=100,
                                       objective="volume")
    assert score == comm_volume(g.hyper, new_part)
    assert score <= comm_volume(g.hyper, part)


def test_apply_moves_merges_shared_slots():
    """Two movers sharing a hyperedge and a destination column touch the
    same (edge, column) slot; the batched phi update must merge the +-1s
    instead of letting one overwrite the other."""
    g = fanout_snn_graph(60, fan=6, seed=9)
    part = feasible_part(60, 4, seed=10)
    st = VolumeState(g, part, 4)
    movers = np.arange(10, dtype=np.int64)
    prev = part[movers].copy()
    dest = (prev + 1) % 4
    st.apply_moves(movers, prev, dest)
    part2 = part.copy()
    part2[movers] = dest
    assert np.array_equal(st.phi, edge_partition_counts(g.hyper, part2, 4))


# ------------------------------------------------------- sharded matching


def test_sharded_matching_shard_count_invariant():
    g = fanout_snn_graph(500, fan=5, seed=11)
    ms = [heavy_edge_matching_vec(g, np.random.default_rng(12), max_vwgt=20,
                                  shards=s)
          for s in (1, 2, 3, 8)]
    for m in ms[1:]:
        assert np.array_equal(ms[0], m)
    m = ms[0]
    v = np.arange(500)
    assert np.array_equal(m[m], v)  # involution: partner's partner is me
    paired = m != v
    assert (g.vwgt[v[paired]] + g.vwgt[m[paired]] <= 20).all()


def test_sharded_coarsen_levels_match_any_shard_count():
    g = fanout_snn_graph(700, fan=5, seed=13)
    l2 = coarsen(g, np.random.default_rng(1), coarsen_to=100, max_vwgt=20,
                 impl="vec", shards=2)
    l5 = coarsen(g, np.random.default_rng(1), coarsen_to=100, max_vwgt=20,
                 impl="vec", shards=5)
    assert len(l2) == len(l5)
    for a, b in zip(l2, l5):
        assert np.array_equal(a.xadj, b.xadj)
        assert np.array_equal(a.adjncy, b.adjncy)
        assert np.array_equal(a.vwgt, b.vwgt)


# ------------------------------------------------------------ out-of-core


def test_levelstore_roundtrip_and_cleanup():
    g = fanout_snn_graph(400, fan=5, seed=14)
    mem = coarsen(g, np.random.default_rng(2), coarsen_to=60, max_vwgt=20,
                  impl="vec", shards=2)
    store = LevelStore()
    spill = coarsen(g, np.random.default_rng(2), coarsen_to=60, max_vwgt=20,
                    impl="vec", shards=2, store=store)
    assert spill is store
    assert len(store) == len(mem)
    for i in range(len(mem)):
        a, b = mem[i], store[i]
        assert np.array_equal(a.xadj, b.xadj)
        assert np.array_equal(a.adjncy, b.adjncy)
        assert np.array_equal(a.adjwgt, b.adjwgt)
        assert np.array_equal(a.vwgt, b.vwgt)
        assert (a.cmap is None) == (b.cmap is None)
        if a.cmap is not None:
            assert np.array_equal(a.cmap, b.cmap)
        assert (a.hyper is None) == (b.hyper is None)
        if a.hyper is not None:
            assert np.array_equal(a.hyper.hpins, b.hyper.hpins)
            assert np.array_equal(a.hyper.hfire, b.hyper.hfire)
            assert comm_volume(a.hyper, feasible_part(a.num_vertices, 4)) == \
                comm_volume(b.hyper, feasible_part(b.num_vertices, 4))
    assert len(store._cache) <= LevelStore._CACHE_SLOTS
    path = store._dir
    store.close()
    assert not os.path.exists(path)


def test_stream_levels_matches_in_memory():
    g = fanout_snn_graph(1500, fan=6, seed=15)
    kw = dict(capacity=64, seed=0, impl="vec", objective="volume",
              hyper=g.hyper, shards=2)
    in_mem = sneap_partition(g, **kw)
    streamed = sneap_partition(g, stream_levels=True, **kw)
    assert np.array_equal(in_mem.part, streamed.part)
    assert in_mem.comm_volume == streamed.comm_volume
    assert in_mem.num_levels == streamed.num_levels


# ------------------------------------------------------------- end to end


def test_end_to_end_sharded_quality_within_5pct():
    """Sharded coarsening draws different (hash) tie keys than the
    single-host rng stream, so the partitions differ — quality must not:
    the ISSUE's acceptance bound is 5% comm_volume drift."""
    g = fanout_snn_graph(4000, fan=8, seed=16)
    kw = dict(capacity=64, seed=0, impl="vec", objective="volume",
              hyper=g.hyper)
    single = sneap_partition(g, **kw)
    two = sneap_partition(g, shards=2, **kw)
    four = sneap_partition(g, shards=4, **kw)
    assert np.array_equal(two.part, four.part)  # shard-count invariance
    drift = abs(two.comm_volume - single.comm_volume) / single.comm_volume
    assert drift <= 0.05, f"sharded comm_volume drifted {drift:.1%}"


def test_sharded_cut_partition_at_scale_within_5pct():
    """The same bound under the cut objective on a larger fan-out SNN with
    1024-neuron cores: 4 shards and 2 shards give the identical partition,
    and it drifts at most 5% in comm_volume from single host."""
    g = fanout_snn_graph(10_000, fan=10, seed=0, max_fire=30)
    kw = dict(capacity=1024, seed=0, impl="vec", objective="cut")
    single = sneap_partition(g, **kw)
    four = sneap_partition(g, shards=4, **kw)
    two = sneap_partition(g, shards=2, **kw)
    assert np.array_equal(four.part, two.part)  # shard-count invariance
    drift = abs(four.comm_volume - single.comm_volume) / single.comm_volume
    assert drift <= 0.05, f"sharded comm_volume drifted {drift:.1%}"


# ----------------------------------------------------- index-dtype audit


def test_index_capacity_vertex_overflow_raises():
    with pytest.raises(IndexCapacityError, match="int32"):
        check_index_capacity(2**31 + 10)


def test_index_capacity_packed_key_overflow_raises():
    # n fits int32 but n*k packed keys overflow int64: shape math only.
    with pytest.raises(IndexCapacityError):
        check_index_capacity(2**31 - 10, k=2**33)
    with pytest.raises(IndexCapacityError):
        check_index_capacity(1000, num_hyperedges=2**31 - 10, k=2**33)


def test_index_capacity_build_graph_guard_fires_before_allocating():
    # >2^31 vertices must fail fast at the boundary — if this ever
    # allocated, the test machine would notice.
    with pytest.raises(IndexCapacityError):
        build_graph(2**31 + 5, np.empty(0, np.int64), np.empty(0, np.int64),
                    np.empty(0, np.int64))


def test_index_capacity_ok_at_realistic_scale():
    check_index_capacity(10**6, num_hyperedges=10**6, k=4096)
