"""Pallas TPU kernel: per-link XY load maps via indicator matmuls.

The closed-form link-usage conditions (see ref.py) factor into
indicator-matrix products, turning the route histogram into MXU work:

  east = Y_a^T @ ( [w >= x_a] . (C @ [x_b > w]) )          (H, W-1)
  south = X_b^T @ ( [q < y_b] . (C^T-contract-a over [y_a <= q]) )

The grid walks row-bands of C (BM partitions at a time); every indicator
is rebuilt in VMEM from the coordinate vectors and a broadcasted iota, so
only C itself streams from HBM.  Output maps are (8, 128)-padded and
accumulated across the serial grid.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

__all__ = ["link_loads_pallas"]

BM = 128
LANES = 128
SUB = 8
# Loads are integer packet counts compared against a link capacity; the
# MXU's default f32 matmul rounds its operands to bf16.
_EXACT = lax.Precision.HIGHEST


def _pad_to(n: int, m: int) -> int:
    return max(m, -(-n // m) * m)


def _kernel(c_ref, xa_ref, ya_ref, xb_ref, yb_ref,
            e_ref, w_ref, s_ref, n_ref,
            *, mesh_w: int, mesh_h: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        e_ref[...] = jnp.zeros_like(e_ref)
        w_ref[...] = jnp.zeros_like(w_ref)
        s_ref[...] = jnp.zeros_like(s_ref)
        n_ref[...] = jnp.zeros_like(n_ref)

    c = c_ref[...]  # (BM, K)
    xa = xa_ref[...]  # (BM, 1) f32
    ya = ya_ref[...]
    xb = xb_ref[...]  # (1, K)
    yb = yb_ref[...]
    k = c.shape[1]
    hp = e_ref.shape[0]  # padded H (rows of E/W maps)
    wp = e_ref.shape[1]  # padded W-1 lanes
    wp2 = s_ref.shape[0]  # padded W (rows of S/N maps)
    hq = s_ref.shape[1]  # padded H-1 lanes

    f32 = jnp.float32
    wlink = lax.broadcasted_iota(jnp.int32, (1, wp), 1).astype(f32)  # link index w
    qlink = lax.broadcasted_iota(jnp.int32, (1, hq), 1).astype(f32)  # link index q
    wvalid = wlink < (mesh_w - 1)
    qvalid = qlink < (mesh_h - 1)

    # ---- horizontal (row of a) ----
    u_e = jnp.where((xb.T > wlink) & wvalid, 1.0, 0.0)  # (K, Wp)
    u_w = jnp.where((xb.T <= wlink) & wvalid, 1.0, 0.0)
    t_e = jnp.dot(c, u_e, preferred_element_type=f32, precision=_EXACT)  # (BM, Wp)
    t_w = jnp.dot(c, u_w, preferred_element_type=f32, precision=_EXACT)
    m_ge = jnp.where(wlink >= xa, 1.0, 0.0)  # (BM, Wp) bcast
    m_lt = jnp.where(wlink < xa, 1.0, 0.0)
    hrow = lax.broadcasted_iota(jnp.int32, (BM, hp), 1).astype(f32)
    y_onehot = jnp.where(hrow == ya, 1.0, 0.0)  # (BM, Hp)
    e_ref[...] += lax.dot_general(y_onehot, t_e * m_ge,
                                  (((0,), (0,)), ((), ())),
                                  preferred_element_type=f32, precision=_EXACT)
    w_ref[...] += lax.dot_general(y_onehot, t_w * m_lt,
                                  (((0,), (0,)), ((), ())),
                                  preferred_element_type=f32, precision=_EXACT)

    # ---- vertical (column of b) ----
    v_s = jnp.where((qlink >= ya) & qvalid, 1.0, 0.0)  # (BM, Hq): [y_a <= q]
    v_n = jnp.where((qlink < ya) & qvalid, 1.0, 0.0)  # (BM, Hq): [q < y_a]
    p_s = lax.dot_general(c, v_s, (((0,), (0,)), ((), ())),
                          preferred_element_type=f32, precision=_EXACT)  # (K, Hq)
    p_n = lax.dot_general(c, v_n, (((0,), (0,)), ((), ())),
                          preferred_element_type=f32, precision=_EXACT)
    m_s = jnp.where(qlink < yb.T, 1.0, 0.0)  # (K, Hq): [q < y_b]
    m_n = jnp.where(qlink >= yb.T, 1.0, 0.0)  # (K, Hq): [y_b <= q]
    wcol = lax.broadcasted_iota(jnp.int32, (k, wp2), 1).astype(f32)
    x_onehot = jnp.where(wcol == xb.T, 1.0, 0.0)  # (K, Wp2)
    s_ref[...] += lax.dot_general(x_onehot, p_s * m_s,
                                  (((0,), (0,)), ((), ())),
                                  preferred_element_type=f32, precision=_EXACT)
    n_ref[...] += lax.dot_general(x_onehot, p_n * m_n,
                                  (((0,), (0,)), ((), ())),
                                  preferred_element_type=f32, precision=_EXACT)


@functools.partial(jax.jit, static_argnames=("mesh_w", "mesh_h", "interpret"))
def link_loads_pallas(
    traffic: jnp.ndarray,
    x: jnp.ndarray,
    y: jnp.ndarray,
    *,
    mesh_w: int,
    mesh_h: int,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """traffic: (K, K) f32; x, y: (K,). Returns (E, W, S, N) load maps."""
    kk = traffic.shape[0]
    kp = _pad_to(kk, BM)
    pad = kp - kk
    if pad:
        traffic = jnp.pad(traffic, ((0, pad), (0, pad)))
        # Padded partitions carry zero traffic; coords (0,0) are harmless.
        x = jnp.pad(x, (0, pad))
        y = jnp.pad(y, (0, pad))
    c = traffic.astype(jnp.float32)
    xr = x.astype(jnp.float32).reshape(kp, 1)
    yr = y.astype(jnp.float32).reshape(kp, 1)
    xc = x.astype(jnp.float32).reshape(1, kp)
    yc = y.astype(jnp.float32).reshape(1, kp)

    hp = _pad_to(mesh_h, SUB)
    wp = _pad_to(mesh_w - 1, LANES)
    wp2 = _pad_to(mesh_w, SUB)
    hq = _pad_to(mesh_h - 1, LANES)
    grid = (kp // BM,)
    e, w_, s, n = pl.pallas_call(
        functools.partial(_kernel, mesh_w=mesh_w, mesh_h=mesh_h),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BM, kp), lambda i: (i, 0)),  # C row band
            pl.BlockSpec((BM, 1), lambda i: (i, 0)),  # x_a
            pl.BlockSpec((BM, 1), lambda i: (i, 0)),  # y_a
            pl.BlockSpec((1, kp), lambda i: (0, 0)),  # x_b (full)
            pl.BlockSpec((1, kp), lambda i: (0, 0)),  # y_b (full)
        ],
        out_specs=[
            pl.BlockSpec((hp, wp), lambda i: (0, 0)),
            pl.BlockSpec((hp, wp), lambda i: (0, 0)),
            pl.BlockSpec((wp2, hq), lambda i: (0, 0)),
            pl.BlockSpec((wp2, hq), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((hp, wp), jnp.float32),
            jax.ShapeDtypeStruct((hp, wp), jnp.float32),
            jax.ShapeDtypeStruct((wp2, hq), jnp.float32),
            jax.ShapeDtypeStruct((wp2, hq), jnp.float32),
        ],
        interpret=interpret,
    )(c, xr, yr, xc, yc)
    return (e[:mesh_h, : mesh_w - 1], w_[:mesh_h, : mesh_w - 1],
            s[:mesh_w, : mesh_h - 1], n[:mesh_w, : mesh_h - 1])
