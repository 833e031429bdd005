"""Pallas TPU kernel: blockwise causal flash attention with GQA.

Grid = (batch, q_heads, num_q_blocks, num_kv_blocks); the kv axis is the
innermost ("arbitrary") dimension so the online-softmax state for one
(b, h, iq) lives in VMEM scratch across kv iterations.  Causal blocks with
ik > iq are skipped with ``pl.when`` (true block skipping — ~2x fewer FLOPs
than masked-compute).  GQA: the kv BlockSpec index map folds the q-head ->
kv-head mapping (h // group), so no repeated KV materialization.

VMEM working set: q(bq,d) + k,v(bkv,d) + acc(bq,d)f32 + m,l(bq,1)f32.
bq = bkv = 512, d = 128: ~0.9 MiB — well under 16 MiB, MXU-aligned.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  block_q: int, block_kv: int, causal: bool, scale: float):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    should_run = (ik * block_kv <= iq * block_q + block_q - 1) \
        if causal else True

    @pl.when(should_run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)       # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)       # (bkv, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0)
            kpos = ik * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev = m_ref[...]                        # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)            # (bq, 1)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 512,
                    block_kv: int = 512, interpret: bool = False):
    """q: (B, H, S, D); k/v: (B, KV, S, D) — heads-major layout.

    Returns (B, H, S, D).
    """
    b, h, sq, d = q.shape
    kv = k.shape[1]
    sk = k.shape[2]
    group = h // kv
    block_q = min(block_q, sq)
    block_kv = min(block_kv, sk)
    assert sq % block_q == 0 and sk % block_kv == 0
    nq, nk = sq // block_q, sk // block_kv
    scale = d ** -0.5

    kernel = functools.partial(_flash_kernel, block_q=block_q,
                               block_kv=block_kv, causal=causal,
                               scale=scale)
    return pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda ib, ih, iq, ik: (ib, ih // group, ik, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda ib, ih, iq, ik: (ib, ih // group, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q.reshape(b, h, nq * block_q, d),
      k, v)
