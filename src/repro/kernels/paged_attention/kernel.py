"""Pallas TPU kernel: paged-attention decode over a block-pooled KV cache.

One query token per lane attends over its lane's KV blocks, addressed
through a scalar-prefetched block table (vLLM-style paging).  Grid =
(batch, max_blocks); the block axis is the innermost ("arbitrary")
dimension so the online-softmax state for one lane lives in VMEM scratch
across block iterations.  The block table and the per-lane valid length
ride in as scalar-prefetch operands (``PrefetchScalarGridSpec``): the kv
BlockSpec index map reads ``block_tables[lane, j]`` to pull the j-th
logical block's *physical* pool row into VMEM — no gather
materialization.

Pool rows are lane-dense: ``(bs, KV*D)``, every kv head's D values side
by side on the minor axis, so a row fills whole 128-lane tiles even where
D is 64 and the device stores the pool row-major, unpadded.  Each grid
step moves one whole pool row: the block's last two dims equal the
pool's, which is what Mosaic's tiling rule accepts, and one DMA per block
serves every kv head.  The kernel then walks the kv heads, head ``h`` as
the static lane slice ``[h*D, (h+1)*D)``; GQA lays q out as (B, KV, G, D)
so each head's whole query group is one (G, D) tile.

Blocks wholly past ``kv_len`` are skipped with ``pl.when`` (true block
skipping); the partial tail block masks positions >= kv_len to an
exact-zero softmax weight.

VMEM working set: q(KV,G,d) + k,v(bs,KV*d) + acc(KV,G,d)f32 +
m,l(KV,G,1)f32 — tiny; the pool itself stays in HBM and only
table-addressed blocks move.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(bt_ref, kl_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, block_size: int, scale: float):
    ib = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    klen = kl_ref[ib]
    d = q_ref.shape[-1]

    @pl.when(j * block_size < klen)
    def _body():
        for h in range(q_ref.shape[1]):
            q = q_ref[0, h].astype(jnp.float32)          # (G, d)
            lanes = slice(h * d, (h + 1) * d)
            k = k_ref[0, :, lanes].astype(jnp.float32)   # (bs, d)
            v = v_ref[0, :, lanes].astype(jnp.float32)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = s * scale
            pos = j * block_size + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(pos < klen, s, NEG_INF)
            m_prev = m_ref[h]                            # (G, 1)
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * alpha + p.sum(axis=-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(j == nj - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_attention(q, k_pool, v_pool, block_tables, kv_len, *,
                    interpret: bool = False):
    """q: (B, H, D); pools: (num_rows, bs, KV*D); block_tables:
    (B, max_blocks) int32 physical pool rows (pre-clamped into range);
    kv_len: (B,) int32 valid positions per lane.  Returns (B, H, D).
    """
    b, h, d = q.shape
    _, bs, kvd = k_pool.shape
    kv = kvd // d
    group = h // kv
    max_blocks = block_tables.shape[1]
    scale = d ** -0.5
    qg = q.reshape(b, kv, group, d)

    kernel = functools.partial(_paged_kernel, block_size=bs, scale=scale)
    lane = pl.BlockSpec((1, kv, group, d), lambda ib, j, bt, kl: (ib, 0, 0, 0))
    pool_row = pl.BlockSpec((1, bs, kvd),
                            lambda ib, j, bt, kl: (bt[ib, j], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, max_blocks),
        in_specs=[lane, pool_row, pool_row],
        out_specs=lane,
        scratch_shapes=[
            pltpu.VMEM((kv, group, d), jnp.float32),
            pltpu.VMEM((kv, group, 1), jnp.float32),
            pltpu.VMEM((kv, group, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, group, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), kv_len.astype(jnp.int32),
      qg, k_pool, v_pool)
    return out.reshape(b, h, d)
