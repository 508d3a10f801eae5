"""One run of the SNEAP toolchain on a TPU, with every phase's output checked.

    python chip_smoke.py                    # edge_5120 on one chip
    python chip_smoke.py --snn random_6212  # the widest paper SNN
    python chip_smoke.py --four-chips       # island SA over four chips only

The default run drives the toolchain through its entry points.
`profile_snn` simulates a paper SNN (Table 1) for 1200 steps from seed 0,
with a cold profile, truncated at its Table 1 transmission count.
`run_toolchain` then partitions it at 256 neurons per core, places the
partitions on a 5x5 mesh and replays the trace, each phase on its device
path: the vec partitioner, the `sa_jax` mapper, and the JAX replay stepper
behind the Pallas link-load screen.  The checks use the repository's host
references:

  profile    jnp and Pallas LIF rasters equal the numpy recurrence bit for
             bit, and the profile reaches the Table 1 transmission count
  partition  valid; where the gain_eval kernel gate opens (smooth_1280 at
             16 neurons per core, k >= 64) it equals the numpy partition
  map        the sa_jax placement is injective, with avg_hop within 1.3x of
             host SA at the same proposal budget; vec SA scored by the
             swap_delta kernel places exactly as numpy-scored vec SA
  evaluate   replay stats from the JAX stepper and Pallas screen equal the
             numpy replay's field for field, with packets stepped on device

`--four-chips` runs only `island_sa` on a four-chip mesh and one-chip
`sa_search_jax` on the same seeded traffic; the island placement must be
injective with avg_hop within 1.3x of the one-chip search.

Each phase prints its seconds, XLA compile seconds, device peak bytes and
the device path it took.  The last line is the JSON verdict, printed only
when every check passed.  Without a TPU, or when a check fails, the script
exits non-zero and prints no verdict.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
SEED = 0
NUM_STEPS = 1200
MESH = 5
CAPACITY = 256
HOP_RATIO_MAX = 1.3


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class Meter:
    """Per-phase wall seconds, XLA compile seconds and device peak bytes.

    Each phase runs in a `repro.telemetry` span of its name; its seconds
    and compile counts are that span's, summed over the program's spans
    inside it.  ``span`` is the open phase's span."""

    def __init__(self, jax):
        self.devices = jax.devices()
        self.span = None

    @contextlib.contextmanager
    def phase(self, name: str):
        """Yield a dict the phase fills with what it reports."""
        from repro import telemetry

        info: dict = {}
        with telemetry.span(name) as span:
            self.span = span
            yield info
        peak = [d.memory_stats()["peak_bytes_in_use"] for d in self.devices]
        fields = {"seconds": round(span.seconds, 3),
                  "compile_s": round(span.total("compile_s"), 3),
                  "compiles": int(span.total("compiles")),
                  "cache_loads": int(span.total("cache_loads")),
                  "peak_bytes": peak[0] if len(peak) == 1 else peak, **info}
        print(f"[{name}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
              flush=True)


@contextlib.contextmanager
def counting(owner, name: str):
    """Count the calls through ``owner.name`` while the block runs."""
    orig = getattr(owner, name)
    tally = {"calls": 0}

    def wrapper(*args, **kwargs):
        tally["calls"] += 1
        return orig(*args, **kwargs)

    setattr(owner, name, wrapper)
    try:
        yield tally
    finally:
        setattr(owner, name, orig)


def profile_phase(meter: Meter, snn: str):
    from repro.snn import LIFParams, make_snn, profile_snn
    from repro.snn.lif import lif_run, lif_run_ref
    from repro.snn.simulate import input_drive

    topo = make_snn(snn, seed=SEED)
    with meter.phase("profile") as info:
        prof = profile_snn(topo, num_steps=NUM_STEPS, seed=SEED)
        info.update(snn=snn, neurons=prof.num_neurons, steps=prof.num_steps,
                    transmissions=prof.num_spikes, path="jnp LIF scan")
    check(prof.num_spikes >= topo.target_spikes,
          f"{prof.num_spikes} transmissions < Table 1's {topo.target_spikes}")

    with meter.phase("profile_parity") as info:
        drive = input_drive(topo, NUM_STEPS, SEED)
        params = LIFParams()
        r_jnp = lif_run(topo.weights, drive, params)
        r_pallas = lif_run(topo.weights, drive, params, use_pallas=True)
        r_ref = lif_run_ref(topo.weights, drive, params)
        mismatch = {"jnp_vs_pallas": int((r_jnp != r_pallas).sum()),
                    "jnp_vs_numpy": int((r_jnp != r_ref).sum()),
                    "pallas_vs_numpy": int((r_pallas != r_ref).sum())}
        info.update(spikes=int(r_ref.sum()), **mismatch,
                    path="jnp scan | lif_step kernel | host numpy")
    check(not any(mismatch.values()), f"LIF rasters differ: {mismatch}")
    check(np.array_equal(prof.fire_counts,
                         r_ref[:prof.num_steps].sum(axis=0)),
          "profile fire counts differ from the numpy raster")
    return prof


def toolchain_phase(meter: Meter, prof, kernel: str):
    """The main path: run_toolchain with every phase on its device path."""
    from repro.core import edge_cut, run_toolchain, validate_partition
    from repro.core import refine_vec
    from repro.kernels import link_load

    with meter.phase("toolchain") as info, \
            counting(refine_vec, "_degrees_via_kernel") as gains, \
            counting(link_load, "window_link_loads") as screens:
        res = run_toolchain(
            prof, mesh_w=MESH, mesh_h=MESH, capacity=CAPACITY, seed=SEED,
            partition_impl="vec", mapper="sa_jax",
            noc_kwargs={"stepper": "jax", "screen": kernel})
        # Records stepped on the device: those counted on the evaluate
        # span that holds a stepper span.
        stepped = sum(s.parent.counters["stepped"]
                      for s in meter.span.find("stepper"))
        info.update({f"{k}_s": round(v, 3) for k, v in res.phase_seconds.items()})
        info.update(k=res.partition.k, edge_cut=res.partition.edge_cut,
                    avg_hop=res.mapping.avg_hop,
                    avg_latency=res.noc.avg_latency,
                    congestion=res.noc.congestion_count,
                    gain_eval_calls=gains["calls"],
                    link_load_screens=screens["calls"],
                    packets_stepped_on_device=stepped,
                    path="vec partition | sa_jax scan + swap_delta polish | "
                         f"jax stepper + {kernel} link_load screen")
    part = res.partition
    validate_partition(prof.graph, part.part, part.k, CAPACITY)
    check(edge_cut(prof.graph, part.part) == part.edge_cut,
          "reported edge cut differs from the partition's")
    check(screens["calls"] > 0, "the link_load screen never ran")
    check(stepped > 0, "no packet was stepped on the device")
    return res


def gain_eval_phase(meter: Meter) -> None:
    """Vec partition with the gain_eval kernel gate open vs shut."""
    from repro.core import ToolchainConfig, partition_phase
    from repro.core import refine_vec
    from repro.snn import make_snn, profile_snn

    cfg = ToolchainConfig(mesh_w=10, mesh_h=10, capacity=16, seed=SEED,
                          partition_impl="vec")
    with meter.phase("partition_gain_eval") as info:
        prof = profile_snn(make_snn("smooth_1280", seed=SEED),
                           num_steps=NUM_STEPS, seed=SEED)
        with counting(refine_vec, "_degrees_via_kernel") as gains:
            dev = partition_phase(prof, cfg)
        host = partition_phase(
            prof, dataclasses.replace(cfg, knobs={"_KERNEL_MAX_N": 0}))
        same = bool(np.array_equal(dev.part, host.part))
        info.update(snn="smooth_1280", k=dev.k, gain_eval_calls=gains["calls"],
                    edge_cut_kernel=dev.edge_cut, edge_cut_numpy=host.edge_cut,
                    equal=same, path="gain_eval kernel | numpy bincount")
    check(dev.k >= refine_vec._KERNEL_MIN_K, f"k={dev.k} keeps the gate shut")
    check(gains["calls"] > 0, "the gain_eval kernel never ran")
    check(same, "kernel-gated vec partition differs from the numpy one")


def mapping_phase_checks(meter: Meter, prof, res, kernel: str) -> None:
    from repro.core import ToolchainConfig, mapping_phase
    from repro.kernels import swap_delta

    placement = res.mapping.placement
    check(len(set(placement.tolist())) == placement.shape[0]
          and placement.max() < MESH * MESH, "sa_jax placement not injective")
    cfg = ToolchainConfig(mesh_w=MESH, mesh_h=MESH, capacity=CAPACITY,
                          seed=SEED, mapper="sa")
    with meter.phase("mapping_host_sa") as info:
        budget = res.mapping.evaluations
        host, *_ = mapping_phase(prof, res.partition, dataclasses.replace(
            cfg, mapper_kwargs={"iters": budget}))
        ratio = res.mapping.avg_hop / host.avg_hop
        info.update(proposals=budget, proposals_host=host.evaluations,
                    avg_hop_sa_jax=res.mapping.avg_hop,
                    avg_hop_sa_host=host.avg_hop, ratio=round(ratio, 4),
                    path="host scalar SA")
    check(ratio <= HOP_RATIO_MAX,
          f"sa_jax avg_hop is {ratio:.3f}x host SA's (limit {HOP_RATIO_MAX})")

    with meter.phase("mapping_vec_scorer") as info:
        with counting(swap_delta, "swap_deltas_pairs") as scored:
            dev, *_ = mapping_phase(prof, res.partition, dataclasses.replace(
                cfg, mapper_kwargs={"impl": "vec", "score_backend": kernel}))
        ref, *_ = mapping_phase(prof, res.partition, dataclasses.replace(
            cfg, mapper_kwargs={"impl": "vec", "score_backend": "numpy"}))
        same = bool(np.array_equal(dev.placement, ref.placement))
        info.update(swap_delta_calls=scored["calls"], avg_hop_kernel=dev.avg_hop,
                    avg_hop_numpy=ref.avg_hop, equal=same,
                    path=f"swap_delta {kernel} | numpy batch delta")
    check(scored["calls"] > 0, "the swap_delta kernel never ran")
    check(same, "swap_delta-scored vec SA placement differs from numpy's")


def evaluate_checks(meter: Meter, prof, res) -> None:
    from repro.core import ToolchainConfig, evaluate_phase

    cfg = ToolchainConfig(mesh_w=MESH, mesh_h=MESH, capacity=CAPACITY,
                          seed=SEED,
                          noc_kwargs={"stepper": "numpy", "screen": "numpy"})
    with meter.phase("evaluate_numpy") as info:
        ref = evaluate_phase(prof, res.partition, res.mapping, cfg)
        dev, host = dataclasses.asdict(res.noc), dataclasses.asdict(ref)
        differ = [f for f in dev if not np.array_equal(dev[f], host[f])]
        info.update(noc_packets=ref.num_noc_spikes,
                    differing_fields=",".join(differ) or "none",
                    path="numpy stepper + numpy screen")
    check(not differ, f"device replay differs from numpy in {differ}")


def four_chip_phase(meter: Meter, jax) -> None:
    """Island SA across four chips vs one-chip population SA."""
    from repro.core.mapping_jax import island_sa, sa_search_jax

    check(len(jax.devices()) == 4, f"--four-chips needs 4 devices, "
          f"JAX found {len(jax.devices())}")
    # Seeded SNN-like traffic: each partition feeds a few others, with
    # heavy-tailed spike counts.
    rng = np.random.default_rng(SEED)
    k, mesh_w = 20, MESH
    traffic = np.where(rng.random((k, k)) < 0.25,
                       rng.lognormal(8.0, 1.5, (k, k)).astype(np.int64), 0)
    np.fill_diagonal(traffic, 0)
    trace_len = int(traffic.sum())
    mesh = jax.make_mesh((4,), ("data",))
    with meter.phase("island_sa_4chips") as info:
        isl = island_sa(traffic, mesh_w * mesh_w, mesh_w, trace_len, mesh,
                        seed=SEED)
        info.update(avg_hop=isl.avg_hop, proposals=isl.evaluations,
                    path="shard_map over 4 chips")
    with meter.phase("sa_jax_1chip") as info:
        one = sa_search_jax(traffic, mesh_w * mesh_w, mesh_w, trace_len,
                            seed=SEED)
        ratio = isl.avg_hop / one.avg_hop
        info.update(avg_hop=one.avg_hop, proposals=one.evaluations,
                    island_ratio=round(ratio, 4), path="one chip")
    check(len(set(isl.placement.tolist())) == k, "island placement not injective")
    check(ratio <= HOP_RATIO_MAX,
          f"island avg_hop is {ratio:.3f}x one-chip sa_jax's")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--snn", default="edge_5120",
                    choices=["smooth_320", "smooth_1280", "mlp_2048",
                             "edge_5120", "random_6212"])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only island SA on four chips and its one-chip "
                         "comparison")
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import enable_compile_cache

    print(f"[device] kind={dev.device_kind} count={len(jax.devices())} "
          f"compile_cache={enable_compile_cache()}", flush=True)
    meter = Meter(jax)
    try:
        if args.four_chips:
            four_chip_phase(meter, jax)
        else:
            prof = profile_phase(meter, args.snn)
            res = toolchain_phase(meter, prof, "pallas")
            gain_eval_phase(meter)
            mapping_phase_checks(meter, prof, res, "pallas")
            evaluate_checks(meter, prof, res)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
