"""Distributed mapping search: shard_map island SA on a multi-device mesh.

Runs in a subprocess so XLA_FLAGS can force 4 host devices without
polluting the single-device test session.  ``island_sa`` must work on any
mesh a caller passes, whatever its axis types.
"""
import os
import subprocess
import sys
from pathlib import Path

SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
import jax
from jax.sharding import AxisType
from repro.core.mapping import sa_search
from repro.core.mapping_jax import island_sa

mesh = jax.make_mesh((4,), ("data",), axis_types=(AxisType.{axis_type},))
rng = np.random.default_rng(0)
k, cores, w = 12, 16, 4
c = rng.integers(0, 100, (k, k)).astype(np.float64)
np.fill_diagonal(c, 0)
trace_len = int(c.sum())
res = island_sa(c, cores, w, trace_len, mesh, rounds=2,
                iters_per_round=1500, chains_per_device=2, seed=0)
assert len(set(res.placement.tolist())) == k, "placement not injective"
ref = sa_search(c, cores, w, trace_len, seed=0, iters=6000)
assert res.avg_hop <= ref.avg_hop * 1.3, (res.avg_hop, ref.avg_hop)
print(f"ISLAND_OK hop={res.avg_hop:.4f} (serial {ref.avg_hop:.4f})")
"""


def run_island(axis_type: str) -> None:
    env = dict(os.environ)
    env["PYTHONPATH"] = str(Path(__file__).resolve().parents[1] / "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c",
                          SCRIPT.replace("{axis_type}", axis_type)],
                         env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "ISLAND_OK" in out.stdout


def test_island_sa_on_four_devices():
    run_island("Auto")


def test_island_sa_on_explicit_mesh():
    run_island("Explicit")
