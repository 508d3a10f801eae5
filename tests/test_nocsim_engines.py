"""Metamorphic suite for the queued NoC replay engines.

Pins the contract between the batched two-tier replay (`repro.nocsim.replay`)
and the scalar reference engine (`sim._queued_ref`):

  (a) unicast: the batched engine reproduces every NoCStats field exactly,
      including congested windows, injection stagger, and both steppers;
  (b) with unbounded capacities the queued replay degenerates to the
      analytic latency (hops + injection stagger);
  (c) multicast tree-fork flits are strictly tighter than the replica
      upper bound per window, with static quantities (link loads, energy,
      hops, packet counts) unchanged;
  (d) every stat is invariant under permutation of trace records within a
      time step (canonical record order).
"""
from dataclasses import asdict

import numpy as np
import pytest

from repro.nocsim.sim import _queued_ref, simulate_noc  # noqa: F401
from repro.nocsim.stats import NoCStats
from repro.nocsim.xy import link_count, link_endpoints, link_ids_for_routes, next_link

from conftest import random_spike_trace


def stats_equal(a, b):
    da, db = asdict(a), asdict(b)
    mism = []
    for k in da:
        same = (np.array_equal(da[k], db[k]) if isinstance(da[k], np.ndarray)
                else da[k] == db[k])
        if not same:
            mism.append(k)
    return mism


# ------------------------------------------------------------ xy helpers


def test_route_steps_follow_stepwise_walk():
    rng = np.random.default_rng(0)
    w, h = 5, 4
    src = rng.integers(0, w * h, 50)
    dst = rng.integers(0, w * h, 50)
    ids, pkt, step = link_ids_for_routes(src, dst, w, h, with_steps=True)
    for p in range(50):
        order = np.argsort(step[pkt == p])
        mine = ids[pkt == p][order].tolist()
        cur, walked = np.array([src[p]]), []
        while cur[0] != dst[p]:
            cur, link = next_link(cur, np.array([dst[p]]), w, h)
            walked.append(int(link[0]))
        assert mine == walked  # in traversal order, not just as a multiset


def test_link_endpoints_roundtrip():
    for w, h in ((2, 2), (3, 5), (4, 4)):
        ids = np.arange(link_count(w, h))
        tail, head = link_endpoints(ids, w, h)
        nxt, link = next_link(tail, head, w, h)
        np.testing.assert_array_equal(nxt, head)  # one hop apart
        np.testing.assert_array_equal(link, ids)  # and it is this link


# ------------------------------------------------- (a) exact unicast parity


@pytest.mark.parametrize("link_capacity,inject_capacity", [
    (1, 256), (2, 256), (4, 3), (2, 1), (10_000, 256),
])
def test_batched_matches_ref_exactly(link_capacity, inject_capacity):
    for seed in range(4):
        t, src, dst, part, placement = random_spike_trace(
            seed=seed, n_spikes=1500, timesteps=8)
        ref = simulate_noc(t, src, dst, part, placement, 3, 3,
                           link_capacity=link_capacity,
                           inject_capacity=inject_capacity, engine="ref")
        new = simulate_noc(t, src, dst, part, placement, 3, 3,
                           link_capacity=link_capacity,
                           inject_capacity=inject_capacity, engine="batched")
        assert ref.congestion_count > 0 or link_capacity >= 1000 \
            or ref.avg_latency == ref.avg_hop
        assert stats_equal(ref, new) == [], (seed, link_capacity)


def test_congested_windows_actually_step():
    """The parity sweep must cover real congestion, not just fast paths."""
    t, src, dst, part, placement = random_spike_trace(
        seed=0, n_spikes=1500, timesteps=8)
    jam = simulate_noc(t, src, dst, part, placement, 3, 3, link_capacity=1)
    assert jam.congestion_count > 0
    assert jam.avg_latency > jam.avg_hop


def bursty_trace(n_firings, fan=1, hot=False, hot_frac=0.7, nhot=2,
                 timesteps=1200, n_neurons=2048, cores=64, seed=0):
    """Each firing goes to ``fan`` random targets; with ``hot``, a quarter
    of the time steps send ``hot_frac`` of their traffic to ``nhot``
    destination neurons, so those windows queue."""
    r = np.random.default_rng(seed)
    part = r.integers(0, cores, n_neurons)
    placement = r.permutation(cores)
    t = np.repeat(np.sort(r.integers(0, timesteps, n_firings)), fan)
    src = np.repeat(r.integers(0, n_neurons, n_firings), fan)
    dst = r.integers(0, n_neurons, t.shape[0])
    if hot:
        hot_steps = r.permutation(timesteps)[:timesteps // 4]
        m = np.isin(t, hot_steps) & (r.random(t.shape[0]) < hot_frac)
        dst[m] = r.integers(0, n_neurons, nhot)[r.integers(0, nhot, m.sum())]
    return t, src, dst, part, placement


_STATIC_FIELDS = {"num_noc_spikes", "num_local_spikes", "total_hops",
                  "link_traversals", "dynamic_energy_pj", "per_link_hops"}


@pytest.mark.parametrize("cast,hot,link_capacity", [
    ("unicast", False, 256), ("unicast", True, 4),
    ("multicast", False, 256), ("multicast", True, 4),
])
def test_batched_matches_ref_on_8x8_bursty_trace(cast, hot, link_capacity):
    """The same parity on a 64-core mesh, with routes of up to 14 hops and
    hot-destination windows: unicast equal in every field, multicast
    (tree fork against replicas) in the static ones."""
    if cast == "unicast":
        trace = bursty_trace(60_000, hot=hot)
    else:
        trace = bursty_trace(8_000, fan=6, hot=hot, hot_frac=0.5, nhot=4)
    args = dict(link_capacity=link_capacity, cast=cast)
    ref = simulate_noc(*trace, 8, 8, engine="ref", **args)
    new = simulate_noc(*trace, 8, 8, engine="batched", **args)
    assert (ref.congestion_count > 0) == hot  # the hot windows do queue
    mism = stats_equal(ref, new)
    if cast == "multicast":
        mism = [k for k in mism if k in _STATIC_FIELDS]
    assert mism == [], (cast, link_capacity)


def hot_link_trace(seed):
    """Random traffic with a burst over one link: 400 spikes from core 0 to
    its east neighbour in two time steps, so many packets share an inject
    cycle on that link and only record order breaks their ties."""
    t, src, dst, part, placement = random_spike_trace(
        seed=seed, n_spikes=300, timesteps=6)
    r = np.random.default_rng(seed)
    part = part.copy()
    part[:10], part[10:20] = 0, 1
    placement = placement.copy()
    placement[:2] = 0, 1
    t = np.concatenate([t, r.integers(2, 4, 400)])
    src = np.concatenate([src, r.integers(0, 10, 400)])
    dst = np.concatenate([dst, r.integers(10, 20, 400)])
    order = np.argsort(t, kind="stable")
    return t[order], src[order], dst[order], part, placement


@pytest.mark.parametrize("link_capacity", [1, 2, 3])
@pytest.mark.parametrize("seed", [1, 7])
@pytest.mark.parametrize("trace", ["random", "hot_link"])
@pytest.mark.parametrize("floor", ["default", "lowered"])
def test_jax_stepper_matches_ref(floor, trace, seed, link_capacity,
                                 monkeypatch):
    pytest.importorskip("jax")
    from repro import telemetry
    from repro.nocsim import replay_jax

    if floor == "lowered":  # several buckets, each compacting its lanes
        monkeypatch.setattr(replay_jax, "_MIN_LANES", 1 << 4)
    if trace == "random":
        args = random_spike_trace(seed=seed, n_spikes=800, timesteps=6)
    else:
        args = hot_link_trace(seed)
    ref = simulate_noc(*args, 3, 3, link_capacity=link_capacity,
                       engine="ref")
    with telemetry.span("t_jax_stepper") as root:
        new = simulate_noc(*args, 3, 3, link_capacity=link_capacity,
                           engine="batched", stepper="jax")
    assert stats_equal(ref, new) == []
    (stepper,) = root.find("stepper")
    if floor == "default":
        # Some lanes are padding: the stepped count is not a power of two.
        assert 0 < root.counters["stepped"] < stepper.counters["lanes"]
        assert stepper.counters["buckets"] == 1
    else:
        assert stepper.counters["buckets"] > 1
    assert new.congestion_count > 0


def test_jax_stepper_drains_in_first_bucket(monkeypatch):
    """Two packets from core 2 contend for its west link: the two-hop one
    to core 0 wins (it is first in record order), the one-hop one to core
    1 follows a cycle later, and both arrive at cycle 2.  The first bucket
    steps until then; the second steps no cycle."""
    pytest.importorskip("jax")
    from repro import telemetry
    from repro.nocsim import replay_jax

    monkeypatch.setattr(replay_jax, "_MIN_LANES", 1)
    args = (np.array([0, 0]), np.array([2, 2]), np.array([0, 1]),
            np.arange(3), np.arange(9))
    ref = simulate_noc(*args, 3, 3, link_capacity=1, engine="ref")
    with telemetry.span("t_first_bucket") as root:
        new = simulate_noc(*args, 3, 3, link_capacity=1, stepper="jax")
    assert stats_equal(ref, new) == []
    assert (new.congestion_count, new.max_latency) == (1, 2)
    (stepper,) = root.find("stepper")
    assert root.counters["stepped"] == 2
    assert (stepper.counters["buckets"], stepper.counters["lanes"],
            stepper.counters["cycles"]) == (1, 2, 2)


def test_jax_stepper_has_no_gather_or_scatter():
    """The stepper's loop reorders lanes by sorts alone: a gather or a
    scatter through the sort's permutation costs the TPU far more."""
    jax = pytest.importorskip("jax")
    from repro.nocsim.replay_jax import _run

    n, w = 1024, 3
    ints = [jax.ShapeDtypeStruct((n,), np.int32) for _ in range(4)]
    lanes = [jax.ShapeDtypeStruct((n,), np.bool_)] + [
        jax.ShapeDtypeStruct((n,), np.int32) for _ in range(2)]
    scalars = [jax.ShapeDtypeStruct((), t)
               for t in (np.int32, np.bool_, np.int32)]
    # A shrinking bucket, with its compaction sort, and the last bucket.
    for out in (n // 2, 0):
        text = _run.lower(*ints, *lanes, *scalars, w=w, h=w,
                          nl=link_count(w, w), capacity=2, max_cycles=1000,
                          out=out).as_text()
        assert "stablehlo.sort" in text
        assert "gather" not in text and "scatter" not in text


def test_screen_backends_do_not_change_results():
    pytest.importorskip("jax")
    t, src, dst, part, placement = random_spike_trace(
        seed=2, n_spikes=800, timesteps=6)
    base = simulate_noc(t, src, dst, part, placement, 3, 3, link_capacity=2)
    for screen in ("linkload", "interpret"):
        got = simulate_noc(t, src, dst, part, placement, 3, 3,
                           link_capacity=2, screen=screen)
        assert stats_equal(base, got) == [], screen
    mc = simulate_noc(t, src, dst, part, placement, 3, 3, link_capacity=2,
                      cast="multicast")
    mc2 = simulate_noc(t, src, dst, part, placement, 3, 3, link_capacity=2,
                       cast="multicast", screen="linkload")
    assert stats_equal(mc, mc2) == []


def test_undrainable_window_raises(monkeypatch):
    t, src, dst, part, placement = random_spike_trace(seed=0, n_spikes=200)
    for engine in ("ref", "batched"):
        with pytest.raises(RuntimeError):
            simulate_noc(t, src, dst, part, placement, 3, 3, link_capacity=0,
                         engine=engine, max_cycles_per_window=50)
    # The JAX stepper hits max_cycles in a bucket that would shrink: the
    # first of several, with more than half its lanes live.
    pytest.importorskip("jax")
    from repro.nocsim import replay_jax

    monkeypatch.setattr(replay_jax, "_MIN_LANES", 1 << 4)
    with pytest.raises(RuntimeError):
        simulate_noc(t, src, dst, part, placement, 3, 3, link_capacity=0,
                     stepper="jax", max_cycles_per_window=50)


# ------------------------------------- (b) unbounded -> analytic degeneracy


@pytest.mark.parametrize("engine", ["ref", "batched"])
def test_unbounded_capacities_degenerate_to_hops(engine):
    t, src, dst, part, placement = random_spike_trace(seed=3)
    q = simulate_noc(t, src, dst, part, placement, 3, 3,
                     link_capacity=10_000, inject_capacity=10_000,
                     engine=engine)
    a = simulate_noc(t, src, dst, part, placement, 3, 3, mode="analytic")
    assert q.congestion_count == 0
    assert q.avg_latency == a.avg_latency  # == avg hop: zero queueing
    assert q.max_latency == a.max_latency
    assert q.total_hops == a.total_hops


@pytest.mark.parametrize("engine", ["ref", "batched"])
def test_unbounded_links_latency_is_hops_plus_stagger(engine):
    """With only the crossbar egress limit active, latency must equal
    hops + (injection rank // inject_capacity), computed independently."""
    inject_capacity = 2
    t, src, dst, part, placement = random_spike_trace(seed=4, n_spikes=600)
    q = simulate_noc(t, src, dst, part, placement, 3, 3,
                     link_capacity=10_000, inject_capacity=inject_capacity,
                     engine=engine)
    # Independent model over the canonical record order.
    core = placement[part]
    s, d = core[src], core[dst]
    order = np.lexsort((d, s, t))
    ts, ss, ds = t[order], s[order], d[order]
    remote = ss != ds
    ts, ss, ds = ts[remote], ss[remote], ds[remote]
    lat = []
    for step_t in np.unique(ts):
        m = ts == step_t
        ws, wd = ss[m], ds[m]
        rank = np.empty(ws.shape[0], dtype=int)
        for c in np.unique(ws):
            cm = np.flatnonzero(ws == c)
            rank[cm] = np.arange(cm.shape[0])
        hops = np.abs(ws % 3 - wd % 3) + np.abs(ws // 3 - wd // 3)
        lat.extend((rank // inject_capacity + hops).tolist())
    assert q.avg_latency == pytest.approx(np.mean(lat))
    assert q.max_latency == max(lat)
    assert q.congestion_count == 0


# ------------------------------ (c) tree-fork flits vs replica upper bound


def _per_window(t, src, dst, part, placement, **kw):
    """Run one simulate_noc per time step so window stats are observable."""
    out = []
    for step_t in np.unique(t):
        m = t == step_t
        out.append(simulate_noc(t[m], src[m], dst[m], part, placement, 3, 3,
                                **kw))
    return out


@pytest.mark.parametrize("link_capacity", [1, 2, 4])
def test_tree_latency_tighter_than_replica_per_window(link_capacity):
    t, src, dst, part, placement = random_spike_trace(
        seed=5, n_spikes=1200, timesteps=6)
    tree = _per_window(t, src, dst, part, placement, cast="multicast",
                       link_capacity=link_capacity, engine="batched")
    repl = _per_window(t, src, dst, part, placement, cast="multicast",
                       link_capacity=link_capacity, engine="ref")
    for wtree, wrepl in zip(tree, repl):
        assert wtree.avg_latency <= wrepl.avg_latency + 1e-12
        assert wtree.max_latency <= wrepl.max_latency
        assert wtree.congestion_count <= wrepl.congestion_count


def test_tree_static_quantities_match_replica_engine():
    """Tree accounting was already exact under the replica engine: link
    loads, traversals, energy, hops and packet counts must be unchanged."""
    t, src, dst, part, placement = random_spike_trace(seed=6, n_spikes=1500)
    for cap in (1, 4, 10_000):
        tree = simulate_noc(t, src, dst, part, placement, 3, 3,
                            link_capacity=cap, cast="multicast")
        repl = simulate_noc(t, src, dst, part, placement, 3, 3,
                            link_capacity=cap, cast="multicast", engine="ref")
        assert tree.cast == repl.cast == "multicast"
        assert tree.num_noc_spikes == repl.num_noc_spikes
        assert tree.num_local_spikes == repl.num_local_spikes
        assert tree.total_hops == repl.total_hops
        assert tree.link_traversals == repl.link_traversals
        np.testing.assert_array_equal(tree.per_link_hops, repl.per_link_hops)
        assert tree.dynamic_energy_pj == repl.dynamic_energy_pj
        assert tree.edge_variance == repl.edge_variance


def test_tree_engine_is_the_multicast_default():
    """ROADMAP item 2: queued multicast must not simulate replicas
    individually by default — the tree engine simulates at most as many
    flit-hops as there are tree links (< replica hop sum on shared
    prefixes) and is what a bare cast="multicast" call runs."""
    t, src, dst, part, placement = random_spike_trace(seed=7, n_spikes=1500)
    default = simulate_noc(t, src, dst, part, placement, 3, 3,
                           link_capacity=1, cast="multicast")
    tree = simulate_noc(t, src, dst, part, placement, 3, 3,
                        link_capacity=1, cast="multicast", engine="batched")
    repl = simulate_noc(t, src, dst, part, placement, 3, 3,
                        link_capacity=1, cast="multicast", engine="ref")
    assert stats_equal(default, tree) == []
    assert default.link_traversals < default.total_hops  # shared prefixes
    assert default.avg_latency < repl.avg_latency  # strictly tighter here


def test_tree_unbounded_matches_analytic_plus_stagger():
    t, src, dst, part, placement = random_spike_trace(seed=8)
    q = simulate_noc(t, src, dst, part, placement, 3, 3, cast="multicast",
                     link_capacity=10_000, inject_capacity=10_000)
    a = simulate_noc(t, src, dst, part, placement, 3, 3, cast="multicast",
                     mode="analytic")
    assert q.congestion_count == 0
    assert q.avg_latency == a.avg_latency
    assert q.cycles_simulated > 0


# ----------------------------------------- (d) permutation invariance


def _shuffle_within_steps(t, src, dst, seed):
    rng = np.random.default_rng(seed)
    idx = np.arange(t.shape[0])
    for v in np.unique(t):
        m = np.flatnonzero(t == v)
        idx[m] = rng.permutation(idx[m])
    return src[idx], dst[idx]


@pytest.mark.parametrize("cast", ["unicast", "multicast"])
@pytest.mark.parametrize("engine", ["ref", "batched"])
def test_stats_invariant_under_within_step_permutation(cast, engine):
    t, src, dst, part, placement = random_spike_trace(
        seed=9, n_spikes=1200, timesteps=6)
    base = simulate_noc(t, src, dst, part, placement, 3, 3, link_capacity=2,
                        inject_capacity=3, cast=cast, engine=engine)
    for pseed in (1, 2):
        s2, d2 = _shuffle_within_steps(t, src, dst, pseed)
        got = simulate_noc(t, s2, d2, part, placement, 3, 3, link_capacity=2,
                           inject_capacity=3, cast=cast, engine=engine)
        assert stats_equal(base, got) == [], (cast, engine, pseed)


# ------------------------------------------------------- stats plumbing


def test_per_link_hops_optional_and_guarded():
    s = NoCStats(avg_latency=0.0, max_latency=0, avg_hop=0.0, total_hops=0,
                 congestion_count=0, edge_variance=0.0, dynamic_energy_pj=0.0,
                 num_noc_spikes=0, num_local_spikes=0, cycles_simulated=0)
    assert s.per_link_hops is None
    assert s.max_link_load() == 0
    t, src, dst, part, placement = random_spike_trace(seed=10)
    q = simulate_noc(t, src, dst, part, placement, 3, 3)
    assert q.per_link_hops is not None
    assert q.max_link_load() == int(q.per_link_hops.max())


def test_simulate_noc_rejects_unknown_knobs():
    t, src, dst, part, placement = random_spike_trace(seed=0, n_spikes=50)
    for kw in ({"engine": "bogus"}, {"stepper": "bogus"}, {"screen": "bogus"},
               {"mode": "bogus"}, {"cast": "bogus"}):
        with pytest.raises(ValueError):
            simulate_noc(t, src, dst, part, placement, 3, 3, **kw)
