"""The span and counter recorder (`repro.telemetry`) and the toolchain's
spans: nesting, counters, the bounded root buffer, compile counts, the
profiler's clock, ``phase_seconds``, and the replay stepper's counters."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.core import run_toolchain
from repro.nocsim import replay_jax
from repro.nocsim.sim import simulate_noc
from repro.nocsim.xy import route_hops
from repro.snn import make_snn, profile_snn

from conftest import random_spike_trace


def test_spans_nest_with_parent_links():
    with telemetry.span("t_nest") as root:
        with telemetry.span("t_nest_a") as a:
            with telemetry.span("t_nest_b") as b:
                pass
        with telemetry.span("t_nest_c") as c:
            pass
    assert root.parent is None and a.parent is root and b.parent is a
    assert c.parent is root
    assert root.children == [a, c] and a.children == [b]
    assert root.start_ns <= a.start_ns <= b.start_ns <= b.end_ns <= a.end_ns
    assert a.end_ns <= c.start_ns <= c.end_ns <= root.end_ns
    assert root.find("t_nest_b") == [b]
    assert telemetry.recent("t_nest", 1) == [root]
    # Only roots are kept for ``recent``.
    assert telemetry.recent("t_nest_a", 1) is None


def test_counters_land_on_the_innermost_span():
    telemetry.count("t_orphan", 5)  # no span open: dropped
    with telemetry.span("t_count") as root:
        telemetry.count("items", 1)
        with telemetry.span("t_count_inner") as inner:
            telemetry.count("items", 2)
            telemetry.count("items", 3)
        telemetry.count("other", 0.5)
    assert root.counters["items"] == 1 and root.counters["other"] == 0.5
    assert inner.counters["items"] == 5 and "other" not in inner.counters
    assert root.total("items") == 6
    assert "t_orphan" not in root.counters
    for s in (root, inner):
        assert s.counters["compiles"] == 0 and s.counters["cache_loads"] == 0


def test_root_buffer_stays_bounded():
    for i in range(telemetry.KEEP + 10):
        with telemetry.span("t_bound"):
            telemetry.count("i", i)
    assert len(telemetry._roots) <= telemetry.KEEP
    last = telemetry.recent("t_bound", telemetry.KEEP)
    assert last is not None and last[-1].counters["i"] == telemetry.KEEP + 9
    assert telemetry.recent("t_bound", telemetry.KEEP + 1) is None


def test_recent_is_oldest_first_and_none_when_short():
    for i in range(3):
        with telemetry.span("t_order"):
            telemetry.count("i", i)
        with telemetry.span("t_order_other"):
            pass
    got = telemetry.recent("t_order", 2)
    assert [s.counters["i"] for s in got] == [1, 2]
    assert telemetry.recent("t_order", 4) is None
    assert telemetry.recent("t_never", 1) is None


def test_span_records_even_when_the_block_raises():
    with pytest.raises(KeyError):
        with telemetry.span("t_raise"):
            raise KeyError("x")
    (s,) = telemetry.recent("t_raise", 1)
    assert s.end_ns >= s.start_ns
    with telemetry.span("t_after") as after:
        pass
    assert after.parent is None


def test_a_fresh_jit_compiles_on_its_span():
    x = jnp.arange(7, dtype=jnp.int32)
    fresh = jax.jit(lambda v: v * 3 + 1)
    with telemetry.span("t_jit") as outer:
        with telemetry.span("t_jit_inner") as inner:
            fresh(x).block_until_ready()
    assert inner.counters["compiles"] == 1
    assert inner.counters["cache_loads"] == 0
    assert inner.counters["compile_s"] > 0
    assert outer.counters["compiles"] == 0 and outer.total("compiles") == 1
    with telemetry.span("t_jit_again") as again:
        fresh(x).block_until_ready()
    assert again.counters["compiles"] == 0


def test_spans_start_with_their_profiler_events(tmp_path):
    """Each span and its trace event start within 1 ms: the trace's host
    events count from the profile's start on the wall clock."""
    from jax.profiler import ProfileData

    names = ("t_clock", "t_clock_a", "t_clock_b")
    spans = []
    jax.profiler.start_trace(str(tmp_path))
    try:
        with telemetry.span(names[0]) as root:
            with telemetry.span(names[1]) as a:
                jnp.ones(4).block_until_ready()
            with telemetry.span(names[2]) as b:
                pass
        spans = [root, a, b]
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    pd = ProfileData.from_file(str(path))
    env = next(p for p in pd.planes if p.name == "Task Environment")
    base = dict(env.stats)["profile_start_time"]
    events = {e.name: e for p in pd.planes if p.name.startswith("/host:")
              for line in p.lines for e in line.events if e.name in names}
    assert set(events) == set(names)
    for s in spans:
        e = events[s.name]
        assert abs(base + e.start_ns - s.start_ns) < 1e6, s.name
        assert abs(base + e.start_ns + e.duration_ns - s.end_ns) < 1e6, s.name


@pytest.fixture(scope="module")
def small_profile():
    return profile_snn(make_snn("smooth_320"), num_steps=150, seed=0)


def test_profile_and_toolchain_spans(small_profile):
    (prof,) = telemetry.recent("profile", 1)
    assert small_profile.seconds == prof.seconds > 0
    (scan,) = prof.children
    assert scan.name == "lif_scan" and 0 < scan.seconds < prof.seconds

    res = run_toolchain(small_profile, mesh_w=3, mesh_h=3, capacity=64,
                        seed=0, link_capacity=1,
                        mapper_kwargs={"iters": 200},
                        noc_kwargs={"stepper": "jax"})
    (root,) = telemetry.recent("toolchain", 1)
    assert [c.name for c in root.children] == ["partition", "mapping",
                                              "evaluate"]
    assert res.phase_seconds == {c.name: c.seconds for c in root.children}
    (evaluate,) = root.find("evaluate")
    assert evaluate.counters["noc_records"] == res.noc.num_noc_spikes
    assert 0 < evaluate.counters["stepped"] <= res.noc.num_noc_spikes
    (stepper,) = evaluate.children
    assert stepper.name == "stepper"
    assert stepper.counters["blocked"] == res.noc.congestion_count


def _stepped_run(monkeypatch):
    """A congested 3x3 replay on the JAX stepper inside a root span, with
    the stepper's input and output captured."""
    t, src, dst, part, placement = random_spike_trace(
        seed=1, n_spikes=800, timesteps=6)
    seen = {}
    real = replay_jax.joint_stepper_jax

    def spy(s, d, *args):
        lat, cong = real(s, d, *args)
        seen.update(hops=int(route_hops(s, d, 3).sum()), lat=lat, cong=cong)
        return lat, cong

    monkeypatch.setattr(replay_jax, "joint_stepper_jax", spy)
    with telemetry.span("t_replay") as root:
        stats = simulate_noc(t, src, dst, part, placement, 3, 3,
                             link_capacity=1, stepper="jax")
    (stepper,) = root.find("stepper")
    return stats, stepper.counters, seen, root.counters


def test_stepper_counters_on_a_congested_trace(monkeypatch):
    stats, c, seen, outer = _stepped_run(monkeypatch)
    t, src, dst, part, placement = random_spike_trace(
        seed=1, n_spikes=800, timesteps=6)
    ref = simulate_noc(t, src, dst, part, placement, 3, 3, link_capacity=1,
                       stepper="numpy")
    assert c["grants"] == seen["hops"] > 0
    assert c["blocked"] == ref.congestion_count == stats.congestion_count > 0
    assert c["cycles"] == int(np.max(seen["lat"]))
    n = outer["stepped"]
    assert c["lanes"] == 1 << (n - 1).bit_length() and c["lanes"] >= n
    assert 0 < n <= outer["noc_records"] == stats.num_noc_spikes
    assert c["grants"] + c["blocked"] <= c["cycles"] * c["lanes"]
    # The work counters repeat exactly; the second run compiles nothing.
    _, again, _, outer_again = _stepped_run(monkeypatch)
    work = ("lanes", "cycles", "grants", "blocked")
    assert {k: again[k] for k in work} == {k: c[k] for k in work}
    assert (outer_again["stepped"], outer_again["noc_records"]) == \
        (outer["stepped"], outer["noc_records"])
    assert again["compiles"] == 0


def test_stepper_counters_across_buckets(monkeypatch):
    """With the ladder's floor lowered the loop runs several buckets; its
    lane-cycles are recomputed here from the numpy stepper's latencies."""
    from repro.nocsim import replay

    floor = 1 << 4
    monkeypatch.setattr(replay_jax, "_MIN_LANES", floor)
    stats, c, seen, outer = _stepped_run(monkeypatch)
    numpy_lat = {}
    real = replay._joint_stepper

    def spy(*args):
        numpy_lat["lat"], cong = real(*args)
        return numpy_lat["lat"], cong

    monkeypatch.setattr(replay, "_joint_stepper", spy)
    t, src, dst, part, placement = random_spike_trace(
        seed=1, n_spikes=800, timesteps=6)
    simulate_noc(t, src, dst, part, placement, 3, 3, link_capacity=1,
                 stepper="numpy")
    lat = numpy_lat["lat"]
    np.testing.assert_array_equal(lat, seen["lat"])
    # A bucket of m lanes steps cycle c while more than the next bucket's
    # width are live, the floor's until none is; live at cycle c: the
    # packets with lat > c.
    n = outer["stepped"]
    widths = replay_jax._ladder(1 << (n - 1).bit_length())
    assert widths[-1] == floor
    lane_cycles, b, stepped = 0, 0, set()
    for cycle in range(int(lat.max())):
        live = int(np.count_nonzero(lat > cycle))
        while b + 1 < len(widths) and live <= widths[b + 1]:
            b += 1
        lane_cycles += widths[b]
        stepped.add(b)
    assert c["cycles"] == int(lat.max())
    assert round(c["cycles"] * c["lanes"]) == lane_cycles
    assert c["buckets"] == len(stepped) > 1
    assert c["grants"] == seen["hops"] > 0
    assert c["blocked"] == stats.congestion_count > 0
    assert c["grants"] + c["blocked"] <= c["cycles"] * c["lanes"]
    _, again, _, _ = _stepped_run(monkeypatch)
    work = ("lanes", "buckets", "cycles", "grants", "blocked")
    assert {k: again[k] for k in work} == {k: c[k] for k in work}
