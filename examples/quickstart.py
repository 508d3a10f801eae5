"""SNEAP quickstart: profile -> partition -> map -> evaluate, vs baselines.

    PYTHONPATH=src python examples/quickstart.py [--snn smooth_320]

Reproduces the paper's four-phase toolchain on one of the five evaluated
SNNs and prints the Fig. 7 metrics for SNEAP / SpiNeMap / SCO.
"""
import argparse
import sys

sys.path.insert(0, "src")

from repro.compile_cache import enable_compile_cache
from repro.core import run_toolchain
from repro.snn import PAPER_SNNS, make_snn, profile_snn


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--snn", default="smooth_320", choices=PAPER_SNNS)
    ap.add_argument("--steps", type=int, default=400)
    ap.add_argument("--mesh", type=int, default=5, help="mesh side (5 => 5x5)")
    args = ap.parse_args()
    enable_compile_cache()

    print(f"[1/4] profiling {args.snn} ({args.steps} steps of LIF simulation)")
    topo = make_snn(args.snn)
    prof = profile_snn(topo, num_steps=args.steps, seed=0)
    print(f"      {prof.num_neurons} neurons, {prof.graph.num_edges} synapses, "
          f"{prof.num_spikes:,} spike transmissions")

    print("[2-4/4] partition -> map -> NoC-evaluate, three toolchains:")
    header = (f"      {'method':10s} {'k':>3s} {'cut':>9s} {'avg_hop':>8s} "
              f"{'latency':>8s} {'energy_pJ':>12s} {'congest':>8s} {'edge_var':>10s}")
    print(header)
    for method in ("sneap", "spinemap", "sco"):
        budget = {"sneap": {"iters": 20_000}, "spinemap": {"iters": 80},
                  "sco": {}}[method]
        r = run_toolchain(prof, method=method, mesh_w=args.mesh,
                          mesh_h=args.mesh, seed=0, mapper_kwargs=budget)
        print(f"      {method:10s} {r.partition.k:3d} {r.partition.edge_cut:9d} "
              f"{r.mapping.avg_hop:8.4f} {r.noc.avg_latency:8.3f} "
              f"{r.noc.dynamic_energy_pj:12.1f} {r.noc.congestion_count:8d} "
              f"{r.noc.edge_variance:10.1f}   "
              f"[partition {r.phase_seconds['partition']:.2f}s, "
              f"map {r.phase_seconds['mapping']:.2f}s]")
    print("\nLower is better on every column; SNEAP should win each (paper Fig. 7).")


if __name__ == "__main__":
    main()
