"""Oracle for the paged-attention kernel: gather + the model's own math.

The reference gathers each lane's lane-dense ``(bs, KV*D)`` pool rows
(via its block table), reshapes them into the contiguous
``(B, S, KV, D)`` layout the dense cache uses and runs the exact
``full_attention`` call from ``layers.decode_attention``.  Because
positions at or beyond ``kv_len`` are masked to an exact-zero softmax
weight, the gathered garbage in unallocated / sentinel blocks
contributes nothing and the result is *bit-identical* to the dense
decode path — this is the property the serving engine's paged mode
leans on for bit-identical output streams.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.models.layers import full_attention


def gather_pages(pool, block_tables):
    """(num_blocks, bs, ...) + (B, max_blocks) -> (B, max_blocks*bs, ...).

    Sentinel / out-of-range table entries are clamped into the pool (the
    caller masks those positions via ``kv_len``), so a partially filled
    table is safe to gather.
    """
    nb = pool.shape[0]
    bt = jnp.clip(block_tables, 0, nb - 1)
    rows = pool[bt]                       # (B, max_blocks, bs, ...)
    b, mb, bs = rows.shape[:3]
    return rows.reshape((b, mb * bs) + rows.shape[3:])


def paged_attention_ref(q, k_pool, v_pool, block_tables, kv_len):
    """q: (B, H, D) one decode token per lane; pools: (num_rows, bs,
    KV*D); block_tables: (B, max_blocks) int32; kv_len: (B,) valid
    positions per lane.  Returns (B, H, D)."""
    d = q.shape[-1]

    def heads(pool):
        rows = gather_pages(pool, block_tables)      # (B, S, KV*D)
        return rows.reshape(rows.shape[:2] + (-1, d)).astype(q.dtype)

    out = full_attention(q[:, None], heads(k_pool), heads(v_pool),
                         causal=False, kv_len=kv_len)
    return out[:, 0]
