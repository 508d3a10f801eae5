"""Pallas TPU kernels: dense (n, k) per-partition degree matrices.

Two modes share the (BM, BN, BK) tiled-matmul grid:

* ``part_degrees_pallas`` — edge-cut degrees A[i, kk] @ onehot(p)[kk, j].
  The one-hot factor is never materialized in HBM: each (BK, BN) tile is
  rebuilt on the fly inside the kernel by comparing the (BK, 1)
  partition-id block against a broadcasted column iota.  That keeps HBM
  traffic at the adjacency tiles alone and turns the refiner's per-vertex
  bincount into an MXU-saturating launch scoring every vertex against
  every partition at once.
* ``connectivity_matmul_pallas`` — the communication-volume analog
  B[i, kk] @ P[kk, j], where B is the hfire-weighted vertex×hyperedge
  incidence and P the per-hyperedge partition-presence matrix [Φ(e, p)
  thresholded].  P depends on the whole pin set, so unlike the one-hot it
  is a real (E, k) input rather than an in-kernel rebuild — the kernel is
  a straight tiled f32 matmul on the same block layout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["part_degrees_pallas", "connectivity_matmul_pallas"]

BM = 128
BN = 128
BK = 128
# Degrees are integer spike counts and the refiner needs them exact; the
# MXU's default f32 matmul rounds its operands to bf16 (8 significant bits).
_EXACT = jax.lax.Precision.HIGHEST


def _degrees_kernel(adj_ref, part_ref, out_ref, *, nk: int):
    j = pl.program_id(1)
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    pk = part_ref[...]  # (BK, 1) f32 partition ids (padding rows hold -1)
    cols = (jax.lax.broadcasted_iota(jnp.int32, (BK, BN), 1) + j * BN).astype(jnp.float32)
    onehot = (pk == cols).astype(jnp.float32)  # (BK, BN) tile, built in VMEM
    out_ref[...] += jnp.dot(adj_ref[...], onehot, preferred_element_type=jnp.float32,
                            precision=_EXACT)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def part_degrees_pallas(
    adj: jnp.ndarray,
    part: jnp.ndarray,
    k: int,
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """adj: (n, n) f32 dense adjacency; part: (n,) int. Returns (n, k) f32.

    Rows/columns are zero-padded to the 128-tile grid; padded partition
    entries are set to -1 so their one-hot rows are all zero (and padded
    adjacency columns are zero anyway).
    """
    n = adj.shape[0]
    npad = max(BM, -(-n // BM) * BM)
    kpad = max(BN, -(-k // BN) * BN)
    adj = adj.astype(jnp.float32)
    if npad != n:
        adj = jnp.pad(adj, ((0, npad - n), (0, npad - n)))
    pcol = jnp.full((npad, 1), -1.0, jnp.float32).at[:n, 0].set(
        part.astype(jnp.float32)
    )

    nk = npad // BK
    grid = (npad // BM, kpad // BN, nk)
    out = pl.pallas_call(
        functools.partial(_degrees_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BM, BK), lambda i, j, kk: (i, kk)),  # A[i, kk]
            pl.BlockSpec((BK, 1), lambda i, j, kk: (kk, 0)),  # part[kk]
        ],
        out_specs=pl.BlockSpec((BM, BN), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((npad, kpad), jnp.float32),
        interpret=interpret,
    )(adj, pcol)
    return out[:n, :k]


def _matmul_kernel(a_ref, b_ref, out_ref):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32, precision=_EXACT)


@functools.partial(jax.jit, static_argnames=("interpret",))
def connectivity_matmul_pallas(
    inc: jnp.ndarray,
    pres: jnp.ndarray,
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """inc: (n, E) f32 incidence; pres: (E, k) f32 presence.  Returns (n, k).

    The connectivity-mode degree matrix D* = inc @ pres as a tiled MXU
    matmul; inputs are zero-padded to the 128-tile grid (zero rows/columns
    contribute nothing to the accumulation).
    """
    n, ne = inc.shape
    k = pres.shape[1]
    npad = max(BM, -(-n // BM) * BM)
    epad = max(BK, -(-ne // BK) * BK)
    kpad = max(BN, -(-k // BN) * BN)
    inc = inc.astype(jnp.float32)
    pres = pres.astype(jnp.float32)
    if (npad, epad) != (n, ne):
        inc = jnp.pad(inc, ((0, npad - n), (0, epad - ne)))
    if (epad, kpad) != (ne, k):
        pres = jnp.pad(pres, ((0, epad - ne), (0, kpad - k)))

    grid = (npad // BM, kpad // BN, epad // BK)
    out = pl.pallas_call(
        _matmul_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BM, BK), lambda i, j, kk: (i, kk)),  # inc[i, kk]
            pl.BlockSpec((BK, BN), lambda i, j, kk: (kk, j)),  # pres[kk, j]
        ],
        out_specs=pl.BlockSpec((BM, BN), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((npad, kpad), jnp.float32),
        interpret=interpret,
    )(inc, pres)
    return out[:n, :k]
