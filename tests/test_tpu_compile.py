"""Compile the toolchain's device programs for a described TPU v5e.

No chip is needed: the TPU compiler ships with jaxlib and compiles for a
topology that is described, not attached.  This catches what interpret mode
hides (operand types Mosaic refuses, tiling, VMEM limits) at the widths the
toolchain runs.  Nothing executes, so these tests say nothing about results
or speed.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
every test file.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.mapping_jax import _polish_loop, _sa_population
from repro.kernels.gain_eval.kernel import (connectivity_matmul_pallas,
                                            part_degrees_pallas)
from repro.kernels.lif_step.kernel import lif_step_pallas
from repro.kernels.link_load.kernel import link_loads_pallas
from repro.kernels.swap_delta.kernel import swap_deltas_pallas
from repro.nocsim.replay_jax import _run
from repro.nocsim.xy import link_count
from repro.snn.lif import LIFParams, _lif_scan


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep these compiles out of it.
    from jax.experimental.compilation_cache import compilation_cache

    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", saved)


def spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_gain_eval_degrees_compiles(one_chip):
    # The refiner's kernel gate opens up to n = 4096 at k >= 64.
    c = part_degrees_pallas.lower(
        spec((4096, 4096), jnp.float32, one_chip),
        spec((4096,), jnp.int32, one_chip), 64, interpret=False).compile()
    assert_kernel(c)


def test_gain_eval_connectivity_compiles(one_chip):
    c = connectivity_matmul_pallas.lower(
        spec((4096, 4096), jnp.float32, one_chip),
        spec((4096, 128), jnp.float32, one_chip), interpret=False).compile()
    assert_kernel(c)


def test_swap_delta_compiles(one_chip):
    c = swap_deltas_pallas.lower(
        spec((256, 256), jnp.float32, one_chip),
        spec((256,), jnp.float32, one_chip),
        spec((256,), jnp.float32, one_chip), interpret=False).compile()
    assert_kernel(c)


def test_link_load_compiles(one_chip):
    c = link_loads_pallas.lower(
        spec((256, 256), jnp.float32, one_chip),
        spec((256,), jnp.int32, one_chip), spec((256,), jnp.int32, one_chip),
        mesh_w=16, mesh_h=16, interpret=False).compile()
    assert_kernel(c)


def test_lif_step_compiles(one_chip):
    p = LIFParams()
    c = lif_step_pallas.lower(
        spec((6212,), jnp.float32, one_chip), spec((6212,), jnp.int32, one_chip),
        spec((6212,), jnp.float32, one_chip), decay=p.decay,
        threshold=p.threshold, v_reset=p.v_reset, refractory=p.refractory,
        interpret=False).compile()
    assert_kernel(c)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_lif_scan_compiles_at_random_6212(one_chip, use_pallas):
    n = 6212  # the widest paper SNN: 154 MB of dense f32 weights
    c = _lif_scan.lower(
        spec((n, n), jnp.float32, one_chip),
        spec((1200, n), jnp.float32, one_chip),
        LIFParams(), use_pallas, False).compile()
    if use_pallas:
        assert_kernel(c)


def test_sa_population_compiles(one_chip):
    nc, chains = 256, 16
    c = _sa_population.lower(
        spec((nc, nc), jnp.float32, one_chip),
        spec((nc, nc), jnp.float32, one_chip),
        spec((chains, nc), jnp.int32, one_chip),
        spec((2,), jnp.uint32, one_chip),
        spec((), jnp.float32, one_chip), 20_000, 64).compile()
    assert c.as_text()


def test_polish_loop_compiles(one_chip):
    nc = 256
    c = _polish_loop.lower(
        spec((nc, nc), jnp.float32, one_chip),
        spec((nc,), jnp.int32, one_chip),
        spec((nc,), jnp.float32, one_chip), spec((nc,), jnp.float32, one_chip),
        256, "pallas").compile()
    assert_kernel(c)


def test_replay_stepper_compiles(one_chip):
    # The program is the same at every packet count, but its sorts take the
    # TPU compiler ~15 s at 2^14 packets and minutes from 2^16 on.
    n, w = 1 << 12, 5
    ints = [spec((n,), jnp.int32, one_chip) for _ in range(4)]
    lanes = [spec((n,), np.bool_, one_chip),
             spec((n,), jnp.int32, one_chip), spec((n,), jnp.int32, one_chip)]
    scalars = [spec((), t, one_chip) for t in (jnp.int32, np.bool_, jnp.int32)]
    # A shrinking bucket, with its compaction sort, and the last bucket.
    for out in (n // 2, 0):
        c = _run.lower(*ints, *lanes, *scalars, w=w, h=w,
                       nl=link_count(w, w), capacity=4, max_cycles=100_000,
                       out=out).compile()
        # Lanes are reordered by sorts alone, in the chip's program too: no
        # gather or scatter runs through the sort's permutation.
        text = c.as_text()
        assert re.search(r"\bsort\(", text)
        assert not re.search(r"\b(gather|scatter)\(", text)
