"""Pallas TPU kernel: 5-point Jacobi stencil (the paper's benchmark app).

TPU adaptation of the paper's manually-tiled CPU/GPU loop: the grid is
row-block tiled into VMEM.  Halo rows come from two extra input views of
the same grid at the 8-row sublane granularity (the smallest block Mosaic
tiles): the 8 rows just above the row block and the 8 just below it, so
the halos cost 16 rows of VMEM instead of two whole row blocks.
Left/right halos are handled in-register by column shifts.  Boundary
conditions (hot top edge = 1.0, others 0.0) are applied via program_id
masking.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

HALO = 8   # rows per halo view: one sublane tile


def _jacobi_kernel(prev_ref, cur_ref, next_ref, out_ref):
    i = pl.program_id(0)
    n_blocks = pl.num_programs(0)
    cur = cur_ref[...]                       # (bh, W)

    # halo rows from the neighbor views (index maps clamp at the edges)
    up_row = prev_ref[HALO - 1:HALO, :]
    down_row = next_ref[0:1, :]
    # global boundary conditions
    up_row = jnp.where(i == 0, jnp.ones_like(up_row), up_row)
    down_row = jnp.where(i == n_blocks - 1, jnp.zeros_like(down_row),
                         down_row)

    up = jnp.concatenate([up_row, cur[:-1]], axis=0)
    down = jnp.concatenate([cur[1:], down_row], axis=0)
    left = jnp.pad(cur[:, :-1], ((0, 0), (1, 0)))
    right = jnp.pad(cur[:, 1:], ((0, 0), (0, 1)))
    out_ref[...] = 0.25 * (up + down + left + right)


def jacobi_step(grid: jax.Array, *, block_rows: int = 128,
                interpret: bool = False) -> jax.Array:
    """One Jacobi sweep over a (H, W) grid.

    Double-buffered VMEM working set = 2 * (2 * block_rows + 2 * 8) * W
    fp32 (cur, out and the two halo views), ~9 MiB at 128 x 4096.
    """
    H, W = grid.shape
    bh = min(block_rows, H)
    assert H % bh == 0 and bh % HALO == 0, (H, bh)
    nb = H // bh
    per_block = bh // HALO
    n_halo = H // HALO

    prev_spec = pl.BlockSpec(
        (HALO, W), lambda i: (jnp.maximum(i * per_block - 1, 0), 0))
    cur_spec = pl.BlockSpec((bh, W), lambda i: (i, 0))
    next_spec = pl.BlockSpec(
        (HALO, W), lambda i: (jnp.minimum((i + 1) * per_block, n_halo - 1), 0))

    return pl.pallas_call(
        _jacobi_kernel,
        grid=(nb,),
        in_specs=[prev_spec, cur_spec, next_spec],
        out_specs=pl.BlockSpec((bh, W), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((H, W), grid.dtype),
        interpret=interpret,
    )(grid, grid, grid)
