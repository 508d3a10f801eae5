"""Compile each cell's device programs for a described TPU v5e, at the sizes
the cell runs, without a chip.

    JAX_PLATFORMS=cpu python3 bench/compile_v5e.py [--skip-stepper]

Nothing runs, so this says nothing about results or times: it shows that
the chip's compiler takes the programs, and how long it compiles each.
The replay stepper at 2^21 packets takes minutes.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--skip-stepper", action="store_true")
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.core.mapping_jax import _polish_loop, _sa_population
    from repro.kernels.link_load.ops import flatten_link_maps, link_loads
    from repro.nocsim.replay_jax import _run
    from repro.nocsim.xy import link_count
    from repro.snn.lif import LIFParams, _lif_scan

    import run

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def timed(label, lowered):
        t0 = time.perf_counter()
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        print(f"{label}: compiled in {time.perf_counter() - t0:.1f} s, "
              f"temp {getattr(mem, 'temp_size_in_bytes', '?')} B, "
              f"kernel={'tpu_custom_call' in compiled.as_text()}", flush=True)

    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    for cell in bench["workloads"]:
        _, _, config, traffic = run.load_cell(cell["name"])
        n = sum(config["network"]["layers"])
        tc = config["toolchain"]
        w, h = tc["mesh_w"], tc["mesh_h"]
        nc = w * h
        print(f"== {cell['name']}", flush=True)
        timed(f"_lif_scan ({n} neurons, {traffic['num_steps']} steps)",
              _lif_scan.lower(spec((n, n), jnp.float32),
                              spec((traffic["num_steps"], n), jnp.float32),
                              LIFParams(), False, False))
        timed(f"_sa_population ({nc} cores, 16 chains)",
              _sa_population.lower(spec((nc, nc), jnp.float32),
                                   spec((nc, nc), jnp.float32),
                                   spec((16, nc), jnp.int32),
                                   spec((2,), jnp.uint32),
                                   spec((), jnp.float32), 20_000, 64))
        timed(f"_polish_loop ({nc} cores)",
              _polish_loop.lower(spec((nc, nc), jnp.float32),
                                 spec((nc,), jnp.int32),
                                 spec((nc,), jnp.float32),
                                 spec((nc,), jnp.float32), 256, "pallas"))
        x = jnp.arange(nc, dtype=jnp.int32) % w
        y = jnp.arange(nc, dtype=jnp.int32) // w

        def screen(batch):
            def one(c):
                return flatten_link_maps(
                    *link_loads(c, x, y, w, h, backend="pallas"), w, h)
            return jax.lax.map(one, batch)

        timed(f"link_load screen (256 windows of {nc}x{nc})",
              jax.jit(screen).lower(spec((256, nc, nc), jnp.float32)))
        if tc["cast"] == "unicast" and tc["noc_kwargs"]["stepper"] == "jax" \
                and not args.skip_stepper:
            m = 1 << 21  # edge_5120 steps about 1.09M packets
            ints = [spec((m,), jnp.int32) for _ in range(4)]
            timed(f"replay stepper _run ({m} packets)",
                  _run.lower(*ints, spec((m,), np.bool_), w=w, h=h,
                             nl=link_count(w, h), capacity=tc["link_capacity"],
                             max_cycles=100_000))
    return 0


if __name__ == "__main__":
    sys.exit(main())
