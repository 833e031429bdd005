"""Pallas TPU kernel: Mamba2 SSD intra-chunk block (state-space duality).

Computes, per (batch, chunk, head):

  y_diag[i] = sum_{j<=i} (C_i . B_j) * exp(dAcs_i - dAcs_j) * dt_j * x_j
  state     = sum_j exp(dAcs_last - dAcs_j) * dt_j * B_j (x) x_j

i.e. the quadratic-within-chunk half of SSD; the (cheap) inter-chunk state
recurrence stays a lax.scan in mamba2.py.  The kernel is matmul-dominated
((l,l) x (l,p) on the MXU), which is exactly the SSD paper's point.

Grid = (B, NC, H); blocks carry one chunk of one head.  The wrapper
moves heads ahead of the chunk's positions, so every block's last two
dims are whole array dims, the layout Mosaic tiles: x (l, p), dt and
dA_cs once as a (1, l) row and once as an (l, 1) column, B/C (l, n).
l=256, p<=128, n<=128 fp32 -> ~0.6 MiB VMEM working set.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ssd_kernel(x_ref, dt_row_ref, dt_col_ref, da_row_ref, da_col_ref,
                b_ref, c_ref, y_ref, st_ref):
    x = x_ref[0, 0, 0]                # (l, p)
    dt_row = dt_row_ref[0, 0, 0]      # (1, l)
    dt_col = dt_col_ref[0, 0, 0]      # (l, 1)
    da_row = da_row_ref[0, 0, 0]      # (1, l)
    da_col = da_col_ref[0, 0, 0]      # (l, 1)
    B = b_ref[0, 0]                   # (l, n)
    C = c_ref[0, 0]                   # (l, n)
    l = x.shape[0]

    ii = jax.lax.broadcasted_iota(jnp.int32, (l, l), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (l, l), 1)
    # causal within chunk; exp(dAcs_i - dAcs_j) * dt_j
    decay = jnp.where(jj <= ii, jnp.exp(da_col - da_row), 0.0) * dt_row
    cb = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (l, l)
    y_ref[0, 0, 0] = jax.lax.dot_general(
        cb * decay, x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    w = jnp.exp(da_col[l - 1:l] - da_col) * dt_col    # (l, 1)
    # state (p, n) = x^T @ (B * w)
    st_ref[0, 0, 0] = jax.lax.dot_general(
        x, B * w, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def ssd_intra_chunk(xr, dtr, dA_cs, Br, Cr, *, interpret: bool = False):
    """xr: (b,nc,l,h,p) f32; dtr/dA_cs: (b,nc,l,h); Br/Cr: (b,nc,l,n).

    Returns y_diag (b,nc,l,h,p), states (b,nc,h,p,n) — the same contract as
    ``repro.models.mamba2.ssd_intra_chunk_ref``.
    """
    b, nc, l, h, p = xr.shape
    n = Br.shape[-1]
    xh = xr.transpose(0, 1, 3, 2, 4)          # (b, nc, h, l, p)
    dth = dtr.transpose(0, 1, 3, 2)           # (b, nc, h, l)
    dah = dA_cs.transpose(0, 1, 3, 2)

    def per_head(shape):
        return pl.BlockSpec((1, 1, 1) + shape,
                            lambda ib, ic, ih: (ib, ic, ih, 0, 0))

    shared = pl.BlockSpec((1, 1, l, n), lambda ib, ic, ih: (ib, ic, 0, 0))
    y, st = pl.pallas_call(
        _ssd_kernel,
        grid=(b, nc, h),
        in_specs=[per_head((l, p)),
                  per_head((1, l)), per_head((l, 1)),
                  per_head((1, l)), per_head((l, 1)),
                  shared, shared],
        out_specs=[per_head((l, p)), per_head((p, n))],
        out_shape=[
            jax.ShapeDtypeStruct((b, nc, h, l, p), jnp.float32),
            jax.ShapeDtypeStruct((b, nc, h, p, n), jnp.float32),
        ],
        interpret=interpret,
    )(xh, dth[:, :, :, None, :], dth[..., None],
      dah[:, :, :, None, :], dah[..., None], Br, Cr)
    return y.transpose(0, 1, 3, 2, 4), st
