"""Pallas TPU kernel: all-pairs SA swap deltas as a fused MXU matmul.

Grid (i, j, kk): classic tiled matmul accumulation over kk for BOTH
products S@D and D@S; the distance tiles D[kk, j], D[i, kk], D[i, j] are
rebuilt on the fly from the (K,) coordinate vectors (D is never stored in
HBM).  The final kk step applies the epilogue

  out = SD + DS - r_i - r_j - (diag_i + diag_j - 2 S_ij) * D_ij

turning the paper's one-swap-at-a-time SA inner loop into a single
MXU-saturating launch that scores the entire O(K^2) neighborhood.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["swap_deltas_pallas"]

BM = 128
BN = 128
BK = 128
# Traffic counts and hop distances are integers and the deltas must come out
# exact; the MXU's default f32 matmul rounds its operands to bf16.
_EXACT = jax.lax.Precision.HIGHEST


def _swap_kernel(
    s_ik_ref, s_kj_ref, s_ij_ref,
    xi_ref, yi_ref, xj_ref, yj_ref, xkr_ref, ykr_ref, xkc_ref, ykc_ref,
    r_i_ref, r_j_ref, diag_i_ref, diag_j_ref,
    out_ref, acc2_ref,
    *, nk: int,
):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)
        acc2_ref[...] = jnp.zeros_like(acc2_ref)

    xi, yi = xi_ref[...], yi_ref[...]  # (BM, 1)
    xj, yj = xj_ref[...], yj_ref[...]  # (1, BN)
    xkr, ykr = xkr_ref[...], ykr_ref[...]  # (BK, 1)
    xkc, ykc = xkc_ref[...], ykc_ref[...]  # (1, BK)

    d_kj = jnp.abs(xkr - xj) + jnp.abs(ykr - yj)  # (BK, BN)
    d_ik = jnp.abs(xi - xkc) + jnp.abs(yi - ykc)  # (BM, BK)

    out_ref[...] += jnp.dot(s_ik_ref[...], d_kj, preferred_element_type=jnp.float32,
                            precision=_EXACT)
    acc2_ref[...] += jnp.dot(d_ik, s_kj_ref[...], preferred_element_type=jnp.float32,
                             precision=_EXACT)

    @pl.when(kk == nk - 1)
    def _epilogue():
        d_ij = jnp.abs(xi - xj) + jnp.abs(yi - yj)  # (BM, BN)
        s_ij = s_ij_ref[...]
        out_ref[...] = (
            out_ref[...]
            + acc2_ref[...]
            - r_i_ref[...]
            - r_j_ref[...]
            - (diag_i_ref[...] + diag_j_ref[...] - 2.0 * s_ij) * d_ij
        )


@functools.partial(jax.jit, static_argnames=("interpret",))
def swap_deltas_pallas(
    sym: jnp.ndarray,
    x: jnp.ndarray,
    y: jnp.ndarray,
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """sym: (K, K) f32 symmetric padded traffic; x, y: (K,) f32 placed coords.

    Returns (K, K) f32 delta matrix.  Padded partitions (zero traffic rows)
    produce deltas that only involve zero traffic, i.e. exact zeros — safe.
    """
    k = sym.shape[0]
    kp = max(BM, -(-k // BM) * BM)
    pad = kp - k
    if pad:
        sym = jnp.pad(sym, ((0, pad), (0, pad)))
        # Padded coords at (0, 0): distance contributions are multiplied by
        # zero traffic everywhere, so the value is irrelevant.
        x = jnp.pad(x, (0, pad))
        y = jnp.pad(y, (0, pad))
    sym = sym.astype(jnp.float32)
    xr = x.astype(jnp.float32).reshape(kp, 1)
    yr = y.astype(jnp.float32).reshape(kp, 1)
    xc = x.astype(jnp.float32).reshape(1, kp)
    yc = y.astype(jnp.float32).reshape(1, kp)

    # Cheap O(K^2) elementwise pre-pass (vs the O(K^3) matmul in-kernel).
    d = jnp.abs(xr - xc) + jnp.abs(yr - yc)
    r = jnp.sum(sym * d, axis=1, keepdims=True)  # (KP, 1)
    diag = jnp.diagonal(sym).reshape(kp, 1)

    nk = kp // BK
    grid = (kp // BM, kp // BN, nk)
    out = pl.pallas_call(
        functools.partial(_swap_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BM, BK), lambda i, j, kk: (i, kk)),  # S[i, kk]
            pl.BlockSpec((BK, BN), lambda i, j, kk: (kk, j)),  # S[kk, j]
            pl.BlockSpec((BM, BN), lambda i, j, kk: (i, j)),  # S[i, j]
            pl.BlockSpec((BM, 1), lambda i, j, kk: (i, 0)),  # x rows
            pl.BlockSpec((BM, 1), lambda i, j, kk: (i, 0)),  # y rows
            pl.BlockSpec((1, BN), lambda i, j, kk: (0, j)),  # x cols
            pl.BlockSpec((1, BN), lambda i, j, kk: (0, j)),  # y cols
            pl.BlockSpec((BK, 1), lambda i, j, kk: (kk, 0)),  # x k-rows
            pl.BlockSpec((BK, 1), lambda i, j, kk: (kk, 0)),  # y k-rows
            pl.BlockSpec((1, BK), lambda i, j, kk: (0, kk)),  # x k-cols
            pl.BlockSpec((1, BK), lambda i, j, kk: (0, kk)),  # y k-cols
            pl.BlockSpec((BM, 1), lambda i, j, kk: (i, 0)),  # r rows
            pl.BlockSpec((1, BN), lambda i, j, kk: (0, j)),  # r cols
            pl.BlockSpec((BM, 1), lambda i, j, kk: (i, 0)),  # diag rows
            pl.BlockSpec((1, BN), lambda i, j, kk: (0, j)),  # diag cols
        ],
        out_specs=pl.BlockSpec((BM, BN), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((kp, kp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((BM, BN), jnp.float32)],
        interpret=interpret,
    )(sym, sym, sym, xr, yr, xc, yc, xr, yr, xc, yc, r, r.reshape(1, kp), diag,
      diag.reshape(1, kp))
    return out[:k, :k]
