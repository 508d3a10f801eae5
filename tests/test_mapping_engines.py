"""Cross-engine mapping suite: scalar vs batched SA parity, the tree-hop
objective's incremental deltas against full recompute, and the tree
objective's total against the multicast replay's tree-link accounting —
plus the unified registry and the shared placement evaluator."""
import math

import numpy as np
import pytest

from repro.core.hopcost import hop_distance_matrix, swap_delta_batch
from repro.core.mapping import (
    MAPPERS,
    OBJECTIVE_AWARE_MAPPERS,
    sa_search,
    tabu_search,
)
from repro.core.placecost import (
    PairwiseObjective,
    TreeHopObjective,
    evaluate_placement,
    make_objective,
)

from conftest import fanout_snn_graph


def _pairwise_instance(k=20, seed=0):
    rng = np.random.default_rng(seed)
    c = rng.integers(0, 200, (k, k)).astype(np.float64)
    np.fill_diagonal(c, 0)
    return c, int(c.sum())


def _tree_instance(n=120, fan=8, k=12, cores=16, mesh_w=4, seed=0):
    """Fan-out SNN + random partition: (objective, traffic-like k, part)."""
    g = fanout_snn_graph(n, fan=fan, seed=seed)
    rng = np.random.default_rng(seed + 1)
    part = rng.integers(0, k, n)
    obj = TreeHopObjective(g.hyper, part, cores, mesh_w, cores // mesh_w)
    return g, part, obj


# ---------------------------------------------------------------------------
# Incremental deltas: exact against full recompute.

def test_pairwise_batch_delta_matches_scalar_formula():
    c, _ = _pairwise_instance()
    rng = np.random.default_rng(3)
    obj = PairwiseObjective(c, 25, 5)
    obj.attach(rng.permutation(25).astype(np.int64))
    aa = rng.integers(0, 25, 200)
    b0 = rng.integers(0, 24, 200)
    bb = np.where(b0 >= aa, b0 + 1, b0)
    dist = hop_distance_matrix(25, 5).astype(np.float64)
    ref = swap_delta_batch(obj.sym, obj._placement, dist, aa, bb)
    np.testing.assert_allclose(obj.swap_delta_batch(aa, bb), ref, atol=1e-9)
    # and both equal the true change of the full objective
    for a, b in zip(aa[:20], bb[:20]):
        p2 = obj._placement.copy()
        p2[a], p2[b] = p2[b], p2[a]
        np.testing.assert_allclose(
            obj.swap_delta(int(a), int(b)),
            obj.total(p2) - obj.total(obj._placement), atol=1e-9)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_tree_swap_delta_exact_against_recompute(seed):
    _, _, obj = _tree_instance(seed=seed)
    rng = np.random.default_rng(seed)
    placement = rng.permutation(16).astype(np.int64)
    obj.attach(placement)
    for _ in range(40):
        a, b = rng.choice(16, 2, replace=False)
        delta = obj.swap_delta(int(a), int(b))
        p2 = placement.copy()
        p2[a], p2[b] = p2[b], p2[a]
        np.testing.assert_allclose(
            delta, obj.total(p2) - obj.total(placement), atol=1e-9)


def test_tree_batch_delta_matches_scalar():
    _, _, obj = _tree_instance(seed=4)
    rng = np.random.default_rng(7)
    obj.attach(rng.permutation(16).astype(np.int64))
    aa = rng.integers(0, 16, 96)
    b0 = rng.integers(0, 15, 96)
    bb = np.where(b0 >= aa, b0 + 1, b0)
    batch = obj.swap_delta_batch(aa, bb)
    scalar = np.array([obj.swap_delta(int(a), int(b)) for a, b in zip(aa, bb)])
    np.testing.assert_allclose(batch, scalar, atol=1e-9)


@pytest.mark.parametrize("objective", ["pairwise", "tree"])
def test_apply_swaps_keeps_exact_total(objective):
    rng = np.random.default_rng(5)
    if objective == "pairwise":
        c, _ = _pairwise_instance(seed=5)
        obj = PairwiseObjective(c, 25, 5)
        nc = 25
    else:
        _, _, obj = _tree_instance(seed=5)
        nc = 16
    placement = rng.permutation(nc).astype(np.int64)
    obj.attach(placement)
    for m in (1, 3, 6):
        pos = rng.choice(nc, 2 * m, replace=False)
        total = obj.apply_swaps(pos.reshape(m, 2))
        np.testing.assert_allclose(total, obj.total(placement), atol=1e-9)


# ---------------------------------------------------------------------------
# Member-level aggregates: the synced incremental state must equal a
# from-scratch build after *arbitrary* accepted-swap sequences, and the
# aggregate-priced batch deltas must equal the scalar chain bitwise —
# every contribution is an integer tree-size change times an integer fire
# weight, so exact equality (not allclose) is the contract.

_AGG_TABLES = ("_cnt", "_rmin1", "_rmin2", "_rmax1", "_rmax2",
               "_cmin1", "_cmin2", "_cmax1", "_cmax2",
               "_hsp", "_vsp", "_srcx", "_srcy")


def _assert_aggregates_match_scratch(obj, hyper, part):
    """Synced tables, size cache and total == a fresh attach + sync."""
    obj._agg_sync()
    fresh = TreeHopObjective(hyper, part, obj.num_positions, obj.mesh_w,
                             obj.mesh_h)
    fresh.attach(obj._placement.copy())
    fresh._agg_sync()
    for name in _AGG_TABLES:
        np.testing.assert_array_equal(
            getattr(obj, name), getattr(fresh, name), err_msg=name)
    np.testing.assert_array_equal(obj._sizes, fresh._sizes)
    assert obj._total == fresh._total


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_tree_aggregates_match_scratch_after_swap_sequences(seed):
    """Mixed scalar-pending and batched multi-pair commits leave the lazy
    aggregates identical to a from-scratch measurement at every sync."""
    g, part, obj = _tree_instance(seed=seed)
    rng = np.random.default_rng(100 + seed)
    obj.attach(rng.permutation(16).astype(np.int64))
    for step in range(24):
        if rng.random() < 0.5:
            a, b = rng.choice(16, 2, replace=False)
            d = obj.swap_delta(int(a), int(b))
            obj.apply_swaps(np.array([[a, b]]), total_delta=d)
        else:
            m = int(rng.integers(1, 4))
            pos = rng.choice(16, 2 * m, replace=False)
            obj.swap_delta_batch(pos[:m], pos[m:])  # builds/syncs lazily
            obj.apply_swaps(np.column_stack([pos[:m], pos[m:]]))
        if step % 6 == 5:
            _assert_aggregates_match_scratch(obj, g.hyper, part)
    _assert_aggregates_match_scratch(obj, g.hyper, part)


def test_tree_aggregates_directed_move_cases():
    """Directed metamorphic cases on a handmade mesh layout: dest-only
    moves (same and different column), horizontal/vertical extreme-member
    removals, and source moves — each committed swap's scalar delta,
    batch delta and aggregate state checked against full recompute."""
    from repro.core.graph import build_hypergraph

    n = 13
    src = np.array([0, 0, 0, 4, 4])
    dst = np.array([1, 2, 3, 8, 12])
    fire = np.zeros(n, dtype=np.int64)
    fire[0], fire[4] = 3, 5
    hyper = build_hypergraph(n, src, dst, fire)
    part = np.arange(n, dtype=np.int64)  # partition i == neuron i
    obj = TreeHopObjective(hyper, part, 16, 4, 4)
    # Identity placement on the 4x4 mesh: edge 0 = source core 0 with
    # members on row 0, columns 1..3 (horizontal extremes); edge 1 =
    # source core 4 with members down column 0, rows 2..3 (vertical).
    obj.attach(np.arange(16, dtype=np.int64))
    obj.swap_delta_batch(np.array([0]), np.array([1]))  # force build
    for a, b in [
        (3, 15),   # member-only: empties extreme column 3, same column re-entry
        (2, 13),   # member-only: horizontal extreme removal to a new column
        (12, 5),   # member-only: vertical extreme removal (row 3 of column 0)
        (0, 10),   # source-only move of edge 0
        (4, 3),    # source move landing on a member's old core
        (8, 12),   # member-member swap inside one edge (dest set unchanged)
    ]:
        before = obj.total(obj._placement)
        p2 = obj._placement.copy()
        p2[a], p2[b] = p2[b], p2[a]
        want = obj.total(p2) - before
        got_batch = obj.swap_delta_batch(np.array([a]), np.array([b]))[0]
        got_scalar = obj.swap_delta(a, b)
        assert got_scalar == want
        assert got_batch == want  # bitwise, not approximately
        obj.apply_swaps(np.array([[a, b]]), total_delta=got_scalar)
        _assert_aggregates_match_scratch(obj, hyper, part)


def test_tree_dedup_merges_congruent_patterns_and_stays_exact():
    """Hyperedges with identical (source partition, dest-partition set)
    merge at construction with summed fire weights, and the aggregates
    stay exact through swaps of the merged pattern's positions."""
    from repro.core.graph import build_hypergraph

    n = 8
    src = np.array([0, 0, 1, 1, 6, 6])
    dst = np.array([2, 3, 2, 3, 4, 5])
    fire = np.array([3, 5, 1, 1, 1, 1, 2, 1], dtype=np.int64)
    hyper = build_hypergraph(n, src, dst, fire)
    # Neurons 0 and 1 share partition 0 and the dest set {1, 2}: their
    # patterns are congruent under every placement and must merge.
    part = np.array([0, 0, 1, 2, 3, 4, 5, 5], dtype=np.int64)
    obj = TreeHopObjective(hyper, part, 9, 3, 3)
    assert obj.num_hyperedges == 2
    assert obj.tw.sum() == fire[0] + fire[1] + fire[6]
    rng = np.random.default_rng(11)
    obj.attach(rng.permutation(9).astype(np.int64))
    for _ in range(12):
        a, b = rng.choice(9, 2, replace=False)
        p2 = obj._placement.copy()
        p2[a], p2[b] = p2[b], p2[a]
        want = obj.total(p2) - obj.total(obj._placement)
        assert obj.swap_delta_batch(np.array([a]), np.array([b]))[0] == want
        d = obj.swap_delta(int(a), int(b))
        assert d == want
        obj.apply_swaps(np.array([[a, b]]), total_delta=d)
    _assert_aggregates_match_scratch(obj, hyper, part)


@pytest.mark.parametrize("seed", [0, 1])
def test_tree_batch_delta_bitwise_equals_scalar(seed):
    _, _, obj = _tree_instance(seed=seed)
    rng = np.random.default_rng(30 + seed)
    obj.attach(rng.permutation(16).astype(np.int64))
    for _ in range(4):
        aa = rng.integers(0, 16, 64)
        b0 = rng.integers(0, 15, 64)
        bb = np.where(b0 >= aa, b0 + 1, b0)
        batch = obj.swap_delta_batch(aa, bb)
        for i in range(64):
            assert batch[i] == obj.swap_delta(int(aa[i]), int(bb[i]))
        pos = rng.choice(16, 6, replace=False)
        obj.apply_swaps(pos.reshape(3, 2))  # mutate state between rounds


def test_tree_scalar_chain_never_builds_aggregates():
    """The propose-then-commit scalar chain must not pay for the lazy
    aggregate tables — they belong to the batched path alone."""
    _, _, obj = _tree_instance(seed=6)
    rng = np.random.default_rng(6)
    obj.attach(rng.permutation(16).astype(np.int64))
    for _ in range(10):
        a, b = rng.choice(16, 2, replace=False)
        d = obj.swap_delta(int(a), int(b))
        obj.apply_swaps(np.array([[a, b]]), total_delta=d)
    assert obj._cnt is None


# ---------------------------------------------------------------------------
# Tree objective == replay tree-link accounting.

def test_closed_form_tree_sizes_match_route_expansion():
    """`multicast_tree_sizes`'s span arithmetic counts exactly the distinct
    links of `multicast_tree_links`'s route-expansion union, on random
    meshes/groups including empty groups and dests equal to the source."""
    from repro.nocsim.xy import multicast_tree_links, multicast_tree_sizes

    rng = np.random.default_rng(0)
    for _ in range(150):
        w = int(rng.integers(2, 17))
        h = int(rng.integers(2, 17))
        ng = int(rng.integers(1, 24))
        m = int(rng.integers(1, 80))
        grp = np.sort(rng.integers(0, ng, m))
        gsrc = rng.integers(0, w * h, ng)
        src, dst = gsrc[grp], rng.integers(0, w * h, m)
        _, gid = multicast_tree_links(src, dst, grp, w, h)
        ref = np.bincount(gid, minlength=ng)
        got = multicast_tree_sizes(src, dst, grp, w, h, ng)
        np.testing.assert_array_equal(got, ref)

def test_tree_total_equals_replay_link_traversals():
    """For a fixed placement, the tree objective's total cost is exactly the
    multicast replay's per-link traversal sum: both charge one traversal
    per (firing, tree link) of the XY multicast tree."""
    from repro.nocsim import simulate_noc

    n, fan, k, w, h = 150, 6, 10, 4, 4
    rng = np.random.default_rng(11)
    src_syn = np.repeat(np.arange(n), fan)
    dst_syn = rng.integers(0, n, n * fan)
    fire = rng.integers(0, 15, n)
    from repro.core.graph import build_hypergraph
    hyper = build_hypergraph(n, src_syn, dst_syn, fire)
    part = rng.integers(0, k, n)
    placement = rng.permutation(w * h).astype(np.int64)[: k]

    # Expand the trace the profiler way: each firing of neuron i (one per
    # time step) transmits on every outgoing synapse of i.
    tt, ts, td = [], [], []
    for i in range(n):
        tgt = dst_syn[src_syn == i]
        for t in range(fire[i]):
            tt.append(np.full(tgt.shape[0], t))
            ts.append(np.full(tgt.shape[0], i))
            td.append(tgt)
    tt, ts, td = map(np.concatenate, (tt, ts, td))

    obj = TreeHopObjective(hyper, part, w * h, w, h)
    full_place = np.concatenate(
        [placement, np.setdiff1d(np.arange(w * h), placement)])
    stats = simulate_noc(tt, ts, td, part, placement, w, h,
                         mode="analytic", cast="multicast")
    assert int(round(obj.total(full_place))) == stats.link_traversals
    assert int(stats.per_link_hops.sum()) == stats.link_traversals
    # queued tree-fork engine keeps the same static accounting
    queued = simulate_noc(tt, ts, td, part, placement, w, h,
                          mode="queued", cast="multicast")
    assert queued.link_traversals == stats.link_traversals


# ---------------------------------------------------------------------------
# Scalar vs batched SA engines: quality parity at equal proposal budgets.

@pytest.mark.parametrize("objective", ["pairwise", "tree"])
def test_batched_sa_quality_matches_scalar(objective):
    tol_each, wins_needed = 1.10, 2
    ok = 0
    for seed in range(3):
        if objective == "pairwise":
            c, tl = _pairwise_instance(k=20, seed=seed)
            kwargs = {}
            nc, w = 25, 5
        else:
            g, part, obj = _tree_instance(seed=seed)
            c = np.zeros((12, 12))  # traffic only sizes the result
            # crude pairwise proxy for trace length normalization
            tl = max(int(obj.tw.sum()), 1)
            kwargs = {"objective": obj}
            nc, w = 16, 4
        scalar = sa_search(c, nc, w, tl, seed=seed, iters=8000, **kwargs)
        if objective == "tree":
            # objectives hold attached state; rebuild for an independent run
            _, _, obj2 = _tree_instance(seed=seed)
            kwargs = {"objective": obj2}
        vec = sa_search(c, nc, w, tl, seed=seed, iters=8000, impl="vec",
                        batch=32, **kwargs)
        s_cost = scalar.tree_hop if objective == "tree" else scalar.avg_hop
        v_cost = vec.tree_hop if objective == "tree" else vec.avg_hop
        if v_cost <= s_cost * tol_each + 1e-9:
            ok += 1
        assert len(set(vec.placement.tolist())) == vec.placement.shape[0]
    assert ok >= wins_needed, f"batched SA quality off on {3 - ok}/3 seeds"


def test_batched_sa_deterministic():
    c, tl = _pairwise_instance(seed=2)
    a = sa_search(c, 25, 5, tl, seed=7, iters=4000, impl="vec", batch=32)
    b = sa_search(c, 25, 5, tl, seed=7, iters=4000, impl="vec", batch=32)
    assert np.array_equal(a.placement, b.placement)
    assert a.avg_hop == b.avg_hop


def test_batched_sa_records_objective_units():
    """history/tree_hop/objective fields say what the samples mean."""
    c, tl = _pairwise_instance()
    r = sa_search(c, 25, 5, tl, seed=0, iters=2000, impl="vec")
    assert r.objective == "pairwise" and r.tree_hop is None
    _, _, obj = _tree_instance(seed=1)
    c12 = np.zeros((12, 12))
    rt = sa_search(c12, 16, 4, 100, seed=0, iters=2000, objective=obj)
    assert rt.objective == "tree"
    assert rt.tree_hop is not None
    # final history sample is the (exact) tree score, not the pairwise one
    np.testing.assert_allclose(rt.history[-1][1], rt.tree_hop, rtol=1e-9)


def _tree_traffic(obj, k):
    """Multicast packet counts of a tree instance as a (k, k) pairwise
    traffic matrix: one packet per (firing, destination partition)."""
    traffic = np.zeros((k, k), dtype=np.float64)
    lens = np.diff(obj.tptr)
    np.add.at(traffic, (np.repeat(obj.tsrc, lens), obj.tdst),
              np.repeat(obj.tw, lens))
    return traffic, int(traffic.sum())


# Proposal budget of the batched tree engine's second try, as a multiple
# of the scalar chain's: the smallest speedup of the batched engine over
# the scalar chain measured on a CPU at these sizes (2.8x, at 32x32), so
# the second try costs no more wall time than the scalar chain did.
_EQUAL_CLOCK_BUDGET = 2.8


@pytest.mark.parametrize("objective,n,k,mesh_w,iters,tol", [
    ("pairwise", None, 48, 8, 8_000, 0.10),
    ("tree", 1024, 48, 8, 1_500, 0.15),
    ("tree", 2048, 160, 16, 800, 0.15),
    ("tree", 4096, 640, 32, 600, 0.15),  # member-aggregate scoring path
], ids=["pairwise_8x8", "tree_8x8", "tree_16x16", "tree_32x32"])
def test_batched_sa_quality_at_mesh_scale(objective, n, k, mesh_w, iters, tol):
    """Batched SA lands within ``tol`` of the scalar chain at an equal
    proposal budget on meshes up to 32x32; a tree instance may instead
    pass on a second try at the equal-wall-clock budget."""
    cores = mesh_w * mesh_w

    def tree():  # objectives hold attached state: a fresh one per search
        return _tree_instance(n=n, fan=6, k=k, cores=cores, mesh_w=mesh_w)[2]

    if objective == "pairwise":
        traffic, trace_len = _pairwise_instance(k=k)
    else:
        traffic, trace_len = _tree_traffic(tree(), k)

    def cost(impl, budget):
        kwargs = {} if objective == "pairwise" else {"objective": tree()}
        r = sa_search(traffic, cores, mesh_w, trace_len, seed=0, iters=budget,
                      impl=impl, **kwargs)
        return r.tree_hop if objective == "tree" else r.avg_hop

    bound = cost("scalar", iters) * (1 + tol) + 1e-12
    got = cost("vec", iters)
    if objective == "tree" and got > bound:
        got = min(got, cost("vec", math.ceil(_EQUAL_CLOCK_BUDGET * iters)))
    assert got <= bound, (objective, cores, got, bound)


def test_kernel_score_backend_matches_numpy_deltas():
    """The MXU all-pairs scorer and the numpy batch produce the same deltas
    (f32 tolerance) for the same proposals."""
    jnp = pytest.importorskip("jax.numpy")
    from repro.kernels.swap_delta import swap_deltas_pairs

    c, _ = _pairwise_instance(k=15, seed=3)
    rng = np.random.default_rng(0)
    nc, w = 25, 5
    obj = PairwiseObjective(c, nc, w)
    placement = rng.permutation(nc).astype(np.int64)
    obj.attach(placement)
    aa = rng.integers(0, nc, 64)
    b0 = rng.integers(0, nc - 1, 64)
    bb = np.where(b0 >= aa, b0 + 1, b0)
    ref = obj.swap_delta_batch(aa, bb)
    x = (np.arange(nc) % w).astype(np.float32)
    y = (np.arange(nc) // w).astype(np.float32)
    got = np.asarray(swap_deltas_pairs(
        jnp.asarray(obj.sym, jnp.float32),
        jnp.asarray(x[placement]), jnp.asarray(y[placement]),
        aa, bb, backend="jnp"))
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-3)


def test_vec_sa_with_kernel_scoring_runs():
    c, tl = _pairwise_instance(seed=6)
    r = sa_search(c, 25, 5, tl, seed=0, iters=1500, impl="vec", batch=32,
                  score_backend="jnp")
    assert len(set(r.placement.tolist())) == 20
    # kernel scoring is pairwise-only
    _, _, obj = _tree_instance(seed=2)
    with pytest.raises(ValueError, match="pairwise"):
        sa_search(np.zeros((12, 12)), 16, 4, 10, iters=100, impl="vec",
                  objective=obj, score_backend="jnp")


# ---------------------------------------------------------------------------
# Tree-objective searches beat pairwise placement on the tree metric.

def test_tree_objective_search_lowers_tree_cost():
    g, part, obj = _tree_instance(n=200, fan=10, k=14, seed=9)
    c = np.zeros((14, 14))
    rng = np.random.default_rng(0)
    rand_costs = []
    for _ in range(10):
        rand_costs.append(obj.total(rng.permutation(16).astype(np.int64)))
    res = sa_search(c, 16, 4, 1, seed=0, iters=6000, objective=obj)
    assert res.tree_hop < np.mean(rand_costs)


def test_tabu_accepts_tree_objective():
    _, _, obj = _tree_instance(seed=3)
    res = tabu_search(np.zeros((12, 12)), 16, 4, 1, seed=0, iters=40,
                      candidates=48, objective=obj)
    assert res.objective == "tree" and res.tree_hop is not None
    assert len(set(res.placement.tolist())) == 12


# ---------------------------------------------------------------------------
# Registry and pipeline integration.

def test_registry_unifies_host_and_device_mappers():
    assert set(MAPPERS) == {"sa", "pso", "tabu", "sa_jax", "polish", "island"}
    assert OBJECTIVE_AWARE_MAPPERS == {"sa", "pso", "tabu"}


def test_polish_registry_entry_runs():
    pytest.importorskip("jax")
    c, tl = _pairwise_instance(k=12, seed=1)
    res = MAPPERS["polish"](c, 16, 4, tl, seed=0, backend="jnp")
    assert len(set(res.placement.tolist())) == 12
    rng = np.random.default_rng(1)
    rand = np.mean([
        PairwiseObjective(c, 16, 4).total(rng.permutation(16)) / tl
        for _ in range(10)
    ])
    assert res.avg_hop <= rand


def test_evaluate_placement_shared_path():
    """avg_hop from the shared evaluator == Algorithm 1 by hand; tree_hop
    == the tree objective total (same normalization)."""
    g, part, obj = _tree_instance(seed=8)
    from repro.core.hopcost import traffic_matrix
    rng = np.random.default_rng(2)
    # a toy trace over the graph's synapses
    tsrc = rng.integers(0, 120, 500)
    tdst = rng.integers(0, 120, 500)
    traffic = traffic_matrix(part, tsrc, tdst, 12)
    placement = rng.permutation(16).astype(np.int64)[:12]
    avg, tree = evaluate_placement(placement, traffic, 16, 4, 500,
                                   mesh_h=4, hyper=g.hyper, part=part)
    dist = hop_distance_matrix(16, 4)
    by_hand = float(
        (dist[placement[:, None], placement[None, :]] * traffic).sum() / 500)
    np.testing.assert_allclose(avg, by_hand, rtol=1e-12)
    full = np.concatenate([placement, np.setdiff1d(np.arange(16), placement)])
    np.testing.assert_allclose(tree, obj.total(full) / 500, rtol=1e-12)


def test_make_objective_validation():
    c, _ = _pairwise_instance()
    with pytest.raises(ValueError, match="hyper"):
        make_objective("tree", c, 25, 5)
    with pytest.raises(ValueError, match="torus"):
        g, part, _ = _tree_instance()
        make_objective("tree", c, 16, 4, hyper=g.hyper, part=part, torus=True)
    with pytest.raises(ValueError, match="unknown"):
        make_objective("voltage", c, 25, 5)


@pytest.fixture(scope="module")
def small_profile():
    from repro.snn import make_snn, profile_snn
    return profile_snn(make_snn("smooth_320"), num_steps=200, seed=0)


def test_run_toolchain_multicast_places_with_tree(small_profile):
    from repro.core import run_toolchain

    # Four seeds: both arms are finite-budget SA chains, and with the
    # SeedSequence-derived per-phase seeds a two-seed sample can draw an
    # unlucky pair (per-seed ratios span ~0.91-1.08 at this budget).
    tree_hops = {"tree": 0.0, "pairwise": 0.0}
    for seed in (0, 1, 2, 3):
        res = run_toolchain(small_profile, method="sneap", mesh_w=5, mesh_h=5,
                            capacity=16, seed=seed, cast="multicast",
                            mapper_kwargs={"iters": 12_000})
        assert res.place_objective == "tree"
        assert res.mapping.objective == "tree"
        s = res.summary()
        assert s["tree_hop"] is not None and s["tree_hop"] > 0
        assert s["place_objective"] == "tree"
        tree_hops["tree"] += s["tree_hop"]
        # explicit pairwise placement still reports tree_hop (evaluator)
        pw = run_toolchain(small_profile, method="sneap", mesh_w=5, mesh_h=5,
                           capacity=16, seed=seed, cast="multicast",
                           place_objective="pairwise",
                           mapper_kwargs={"iters": 12_000})
        assert pw.place_objective == "pairwise"
        assert pw.summary()["tree_hop"] is not None
        tree_hops["pairwise"] += pw.summary()["tree_hop"]
    # On the metric it optimizes, tree placement must not lose to pairwise
    # placement on average over seeds (both are finite-budget SA chains, so
    # single seeds can tie or flip within noise).
    assert tree_hops["tree"] <= tree_hops["pairwise"] * 1.02


def test_run_toolchain_sco_hop_comes_from_evaluator(small_profile):
    from repro.core import run_toolchain
    res = run_toolchain(small_profile, method="sco", mesh_w=5, mesh_h=5,
                        seed=0)
    assert np.isfinite(res.mapping.avg_hop)
    assert res.mapping.tree_hop is not None  # hypergraph is profiled
    # unicast default: reported avg_hop is Algorithm 1 over the placement
    from repro.core.hopcost import traffic_matrix
    traffic = traffic_matrix(res.partition.part, small_profile.trace_src,
                             small_profile.trace_dst, res.partition.k)
    avg, _ = evaluate_placement(res.mapping.placement, traffic, 25, 5,
                                int(traffic.sum()))
    np.testing.assert_allclose(res.mapping.avg_hop, avg, rtol=1e-12)


def test_run_toolchain_rejects_tree_for_device_mapper(small_profile):
    from repro.core import run_toolchain
    with pytest.raises(ValueError, match="cannot run the tree objective"):
        run_toolchain(small_profile, method="sneap", mesh_w=5, mesh_h=5,
                      capacity=16, seed=0, cast="multicast", mapper="polish",
                      place_objective="tree")
    # ... and sco, which runs no search at all, rejects it the same way
    # instead of silently placing sequentially.
    with pytest.raises(ValueError, match="sco"):
        run_toolchain(small_profile, method="sco", mesh_w=5, mesh_h=5,
                      seed=0, cast="multicast", place_objective="tree")
