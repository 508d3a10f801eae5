"""Batched toolchain sweep with a Pareto report.

    PYTHONPATH=src python examples/sweep_pareto.py [--snn smooth_320]

One `run_toolchain` call answers "how does this SNN behave on this
mesh?". Production asks a different question — "which (k, mesh,
objective, mapper, seed) is *best* for this workload?" — and answering
it one sequential call at a time wastes everything the configs share.
`repro.launch.sweep.run_sweep` runs a whole config grid at once:

  * partition/traffic phases are computed once per unique
    (method, capacity, k, objective, seed) and shared across configs;
  * same-shape `sa_jax` searches run as ONE vmapped device program;
  * `stepper="jax"` replays share pow2-padded compiled programs.

Rows are bitwise-identical to what sequential `run_toolchain` calls
would produce (`tests/test_sweep.py::test_sweep_rows_match_sequential_bitwise`
holds them to it), so only the wall clock differs. This example sweeps
two meshes x two partition objectives x mappers x seeds and prints the
Pareto front over (energy, latency, toolchain seconds).
"""
import argparse
import sys

sys.path.insert(0, "src")

from repro.launch.sweep import config_grid, run_sweep
from repro.snn import PAPER_SNNS, make_snn, profile_snn


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--snn", default="smooth_320", choices=PAPER_SNNS)
    ap.add_argument("--steps", type=int, default=400)
    args = ap.parse_args()

    print(f"[profile] {args.snn} ({args.steps} LIF steps)")
    prof = profile_snn(make_snn(args.snn), num_steps=args.steps, seed=0)

    # 2 meshes x 2 objectives x 2 seeds, device-batched sa_jax half plus
    # a host-SA half — 10 configs, far fewer unique partitions.
    grid = config_grid(
        mesh=[(4, 4), (6, 6)], seed=[0, 1], objective=["cut", "volume"],
        mapper=["sa_jax"], mapper_kwargs=[{"iters": 4000, "chains": 8}],
        stepper=["jax"],
    ) + config_grid(
        mesh=[(4, 4), (6, 6)], seed=[0], objective=["cut"], mapper=["sa"],
        mapper_kwargs=[{"iters": 4000}],
    )
    print(f"[sweep]   {len(grid)} configs")
    res = run_sweep(prof, grid, progress=lambda m: print(f"          {m}"))
    print(f"          done in {res.seconds:.2f}s")

    print(f"\nPareto front over {' x '.join(res.pareto_keys)} "
          f"({len(res.front())} of {len(res.rows)} configs):")
    hdr = (f"  {'mesh':>5s} {'mapper':>7s} {'obj':>7s} {'seed':>4s} {'k':>3s} "
           f"{'energy_pJ':>12s} {'latency':>8s} {'tool_s':>7s}")
    print(hdr)
    for r in res.front():
        print(f"  {r['mesh_w']}x{r['mesh_h']:<3} {r['mapper']:>7s} "
              f"{r['objective']:>7s} {r['seed']:>4} {r['k']:>3} "
              f"{float(r['energy_pj']):12.1f} {float(r['avg_latency']):8.3f} "
              f"{float(r['total_s']):7.2f}")
    print("\nEvery front row is a defensible deployment choice; dominated "
          "rows lose on all three axes at once.")


if __name__ == "__main__":
    main()
