"""SPMD overdecomposed stencil — the TPU-production Jacobi2D path.

Each device owns ``odf`` tiles of the global grid (1-D ring decomposition by
row-blocks); halo exchange crosses devices with ``jax.lax.ppermute`` inside
``shard_map`` while intra-device tile boundaries are handled locally.  With
odf > 1 XLA's latency-hiding scheduler can overlap a tile's ppermute with
the other tiles' compute — the Charm++ Fig-1 overlap, TPU-native.

Used by: examples/jacobi_spmd.py, the multi-device elastic test, and the
dry-run (it lowers/compiles on the production meshes).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _tile_step(tile, up_row, down_row):
    """Jacobi update for a (rows, W) tile given exterior halo rows."""
    upper = jnp.concatenate([up_row[None], tile[:-1]], axis=0)
    lower = jnp.concatenate([tile[1:], down_row[None]], axis=0)
    left = jnp.pad(tile[:, :-1], ((0, 0), (1, 0)))
    right = jnp.pad(tile[:, 1:], ((0, 0), (0, 1)))
    return 0.25 * (upper + lower + left + right)


def make_jacobi_spmd_step(mesh: Mesh, *, axis: str = "data", odf: int = 4,
                          n_iters: int = 1):
    """Returns a jitted step: grid (n_dev*odf*rows, W) -> same, n_iters
    Jacobi sweeps with ppermute halo exchange.

    The grid is sharded by row-blocks over ``axis``; each device's block is
    further split into ``odf`` tiles so the boundary exchange of one tile can
    overlap the interior compute of others.
    """
    n_dev = mesh.shape[axis]

    def local_sweep(block, top_halo, bot_halo):
        """block: (odf, rows, W) local tiles; halos: (W,) from neighbors."""
        odf_, rows, W = block.shape
        # stitched view of tile boundary rows
        ups = jnp.concatenate(
            [top_halo[None], block[:-1, -1, :]], axis=0)     # (odf, W)
        downs = jnp.concatenate(
            [block[1:, 0, :], bot_halo[None]], axis=0)       # (odf, W)
        return jax.vmap(_tile_step)(block, ups, downs)

    def step(grid):
        def inner(block):
            # block: (n_dev*odf*rows, W) / n_dev on this device
            rows_total, W = block.shape
            rows = rows_total // odf
            tiles = block.reshape(odf, rows, W)

            def one_iter(tiles, _):
                # exchange device-boundary rows around the ring
                top_edge = tiles[0, 0, :]      # goes to previous device
                bot_edge = tiles[-1, -1, :]    # goes to next device
                fwd = [(i, (i + 1) % n_dev) for i in range(n_dev)]
                bwd = [(i, (i - 1) % n_dev) for i in range(n_dev)]
                top_halo = jax.lax.ppermute(bot_edge, axis, fwd)
                bot_halo = jax.lax.ppermute(top_edge, axis, bwd)
                # fixed boundary conditions at the global top/bottom
                idx = jax.lax.axis_index(axis)
                top_halo = jnp.where(idx == 0,
                                     jnp.ones_like(top_halo), top_halo)
                bot_halo = jnp.where(idx == n_dev - 1,
                                     jnp.zeros_like(bot_halo), bot_halo)
                return local_sweep(tiles, top_halo, bot_halo), ()

            tiles, _ = jax.lax.scan(one_iter, tiles, None, length=n_iters)
            return tiles.reshape(rows_total, W)

        return jax.shard_map(
            inner, mesh=mesh, in_specs=P(axis, None),
            out_specs=P(axis, None))(grid)

    sharding = NamedSharding(mesh, P(axis, None))
    return jax.jit(step, in_shardings=sharding, out_shardings=sharding)


def reference_jacobi(grid, n_iters: int):
    """Single-device oracle with the same boundary conditions."""
    def one(g, _):
        up = jnp.concatenate([jnp.ones((1, g.shape[1]), g.dtype), g[:-1]])
        down = jnp.concatenate([g[1:], jnp.zeros((1, g.shape[1]), g.dtype)])
        left = jnp.pad(g[:, :-1], ((0, 0), (1, 0)))
        right = jnp.pad(g[:, 1:], ((0, 0), (0, 1)))
        return 0.25 * (up + down + left + right), ()
    out, _ = jax.lax.scan(one, grid, None, length=n_iters)
    return out
