"""Shared neural building blocks: norms, rotary, attention, MLPs.

All functions are pure; params are pytrees produced from models/schema.py.
Activation sharding is annotated through ``repro.launch.sharding.constrain``
with *logical* axis names so the same model code runs unsharded on CPU and
sharded on a production mesh.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.launch.sharding import constrain


# ----------------------------------------------------------------------------- norms
def rms_norm(x, weight, eps: float):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32)).astype(dt)


# ----------------------------------------------------------------------------- rotary
def rotary_embedding(positions, head_dim: int, theta: float):
    """positions: (..., S) int -> (cos, sin) of shape (..., S, head_dim//2)."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(angles), jnp.sin(angles)


def apply_rotary(x, cos, sin):
    """x: (B, S, H, D); cos/sin: (B, S, D//2) or (S, D//2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if cos.ndim == 2:
        cos, sin = cos[None], sin[None]
    cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------------------- attention cores
def _gqa_split(q, num_kv: int):
    """(B,S,H,D) -> (B,S,KV,G,D) with G = H // KV query groups."""
    b, s, h, d = q.shape
    return q.reshape(b, s, num_kv, h // num_kv, d)


def full_attention(q, k, v, *, causal: bool, q_offset=0, kv_len=None):
    """Reference full attention with GQA. q:(B,Sq,H,D), k/v:(B,Sk,KV,D).

    ``q_offset`` is the absolute position of q[0] (for decode).
    ``kv_len`` optionally masks out cache positions >= kv_len.
    """
    b, sq, h, d = q.shape
    kv = k.shape[2]
    qg = _gqa_split(q, kv)                      # (B,Sq,KV,G,D)
    scale = d ** -0.5
    # bf16 operands + f32 accumulation (MXU-native): avoids materializing
    # f32 copies of the KV cache (2x cache traffic on decode)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                        preferred_element_type=jnp.float32) * scale
    sk = k.shape[1]
    if causal:
        qpos = jnp.arange(sq) + q_offset
        kpos = jnp.arange(sk)
        mask = kpos[None, :] <= qpos[:, None]   # (Sq, Sk)
        logits = jnp.where(mask[None, None, None], logits, -1e30)
    if kv_len is not None:
        valid = jnp.arange(sk)[None, :] < kv_len[:, None]   # (B, Sk)
        logits = jnp.where(valid[:, None, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, sq, h, d).astype(q.dtype)


def blockwise_attention(q, k, v, *, causal: bool, block_q: int, block_kv: int):
    """Memory-O(S*block) causal attention (online softmax), pure jnp.

    This is the production path for long prefill; it is also the oracle the
    Pallas flash kernel is validated against (kernels/flash_attention/ref.py
    re-exports it).  Causal block skipping: the kv loop for q block i only
    runs over blocks overlapping [0, (i+1)*block_q) — a dynamic fori_loop
    bound, so no 2x masked-compute waste.
    """
    b, sq, h, d = q.shape
    kv_heads = k.shape[2]
    g = h // kv_heads
    sk = k.shape[1]
    block_q = min(block_q, sq)
    block_kv = min(block_kv, sk)
    assert sq % block_q == 0 and sk % block_kv == 0
    nq, nk = sq // block_q, sk // block_kv
    scale = d ** -0.5

    qr = q.reshape(b, nq, block_q, kv_heads, g, d)
    kr = k.reshape(b, nk, block_kv, kv_heads, d)
    vr = v.reshape(b, nk, block_kv, kv_heads, d)

    def q_block(iq):
        qi = jax.lax.dynamic_index_in_dim(qr, iq, 1, keepdims=False)
        qpos = iq * block_q + jnp.arange(block_q)

        def kv_step(ik, carry):
            acc, m, l = carry
            ki = jax.lax.dynamic_index_in_dim(kr, ik, 1, keepdims=False)
            vi = jax.lax.dynamic_index_in_dim(vr, ik, 1, keepdims=False)
            s = jnp.einsum("bqkgd,bskd->bkgqs", qi, ki,
                           preferred_element_type=jnp.float32) * scale
            if causal:
                kpos = ik * block_kv + jnp.arange(block_kv)
                mask = kpos[None, :] <= qpos[:, None]
                s = jnp.where(mask[None, None, None], s, -1e30)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + p.sum(axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bkgqs,bskd->bkgqd", p.astype(vi.dtype), vi,
                preferred_element_type=jnp.float32)
            return (acc_new, m_new, l_new)

        acc0 = jnp.zeros((b, kv_heads, g, block_q, d), jnp.float32)
        m0 = jnp.full((b, kv_heads, g, block_q), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, kv_heads, g, block_q), jnp.float32)
        if causal:
            n_valid = ((iq + 1) * block_q + block_kv - 1) // block_kv
        else:
            n_valid = nk
        acc, m, l = jax.lax.fori_loop(0, n_valid, kv_step, (acc0, m0, l0))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out  # (B,KV,G,bq,D)

    outs = jax.lax.map(q_block, jnp.arange(nq))          # (nq,B,KV,G,bq,D)
    out = jnp.moveaxis(outs, 0, 3)                       # (B,KV,G,nq,bq,D)
    out = out.reshape(b, kv_heads, g, sq, d)
    out = jnp.moveaxis(out, 3, 1).reshape(b, sq, h, d)
    return out.astype(q.dtype)


# ----------------------------------------------------------------------------- attention layer
def attention_block(p, x, cfg, *, causal=True, positions=None,
                    kv_cache=None, cache_len=None, cross_kv=None):
    """Pre-norm attention block with rotary + GQA.

    Modes:
      * training/prefill: kv_cache is None -> attends within x.
      * decode:           kv_cache=(k,v) of shape (B,S,KV,D); x is the new
                          token(s); returns (out, new_kv_entries).
      * cross-attention:  cross_kv=(k,v) precomputed from the encoder.
    """
    from repro.configs.base import ModelConfig  # local to avoid cycles
    dt = jnp.dtype(cfg.compute_dtype)
    b, s, _ = x.shape
    h = rms_norm(x, p["norm"], cfg.norm_eps).astype(dt)

    q = jnp.einsum("bsd,dhk->bshk", h, p["wq"].astype(dt))
    q = constrain(q, "batch", None, "heads", None)
    if cross_kv is None:
        k = jnp.einsum("bsd,dhk->bshk", h, p["wk"].astype(dt))
        v = jnp.einsum("bsd,dhk->bshk", h, p["wv"].astype(dt))
        if positions is None:
            positions = jnp.arange(s)
        cos, sin = rotary_embedding(positions, cfg.head_dim, cfg.rope_theta)
        q = apply_rotary(q, cos, sin)
        k = apply_rotary(k, cos, sin)
    new_kv = None
    if cross_kv is not None:
        k, v = cross_kv
        out = full_attention(q, k, v, causal=False)
    elif kv_cache is not None:
        ck, cv = kv_cache  # (B, S_max, KV, D) seq-sharded on the model axis
        ck = jax.lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype), 0, 1)
        cv = jax.lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype), 0, 1)
        # NOTE: dynamic_update at position cache_len is handled by the caller
        # (serving engine) via roll-free indexed update; here we receive the
        # already-positioned update through `positions`.
        raise RuntimeError("use decode_attention for cached decode")
    else:
        impl = cfg.attn_impl
        if impl == "auto":
            impl = "blockwise" if s > 8192 else "full"
        if impl == "blockwise":
            out = blockwise_attention(q, k, v, causal=causal,
                                      block_q=cfg.flash_block_q,
                                      block_kv=cfg.flash_block_kv)
        else:
            out = full_attention(q, k, v, causal=causal)
        new_kv = (k, v)
    out = constrain(out, "batch", None, "heads", None)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt))
    return x + constrain(out, "batch", None, "embed"), new_kv


def decode_attention(p, x, cfg, *, cache_k, cache_v, cache_len,
                     cross_kv=None, active=None):
    """One-token decode against a KV cache.

    cache_k/v: (B, S_max, KV, D); cache_len: (B,) current lengths.
    Returns (out, (cache_k, cache_v)) with the new token written at cache_len.
    """
    dt = jnp.dtype(cfg.compute_dtype)
    b, s, _ = x.shape  # s == 1
    h = rms_norm(x, p["norm"], cfg.norm_eps).astype(dt)
    q = jnp.einsum("bsd,dhk->bshk", h, p["wq"].astype(dt))
    if cross_kv is not None:
        out = full_attention(q, *cross_kv, causal=False)
        out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt))
        return x + out, (cache_k, cache_v)

    k = jnp.einsum("bsd,dhk->bshk", h, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", h, p["wv"].astype(dt))
    cos, sin = rotary_embedding(cache_len[:, None], cfg.head_dim,
                                cfg.rope_theta)
    q = apply_rotary(q, cos, sin)
    k = apply_rotary(k, cos, sin)
    # scatter the new kv at position cache_len (per batch row); inactive
    # slots keep their old cache contents (continuous-batching mask)
    bidx = jnp.arange(b)
    k_new, v_new = k[:, 0].astype(cache_k.dtype), v[:, 0].astype(cache_v.dtype)
    if active is not None:
        k_new = jnp.where(active[:, None, None], k_new,
                          cache_k[bidx, cache_len])
        v_new = jnp.where(active[:, None, None], v_new,
                          cache_v[bidx, cache_len])
    cache_k = cache_k.at[bidx, cache_len].set(k_new)
    cache_v = cache_v.at[bidx, cache_len].set(v_new)
    out = full_attention(q, cache_k.astype(dt), cache_v.astype(dt),
                         causal=False, kv_len=cache_len + 1)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt))
    return x + out, (cache_k, cache_v)


def _pool_rows(pool):
    """Stacked pool ``(L, num_blocks, bs, KV*D)`` -> ``(L*num_blocks, bs,
    KV*D)`` rows: a free reshape of the row-major pool."""
    return pool.reshape((-1,) + pool.shape[2:])


def _read_rows(table, layer, nb):
    """A layer's block numbers -> rows of the stacked pool, sentinels
    clamped into the layer (their positions are masked by the caller)."""
    return layer * nb + jnp.clip(table, 0, nb - 1)


def _write_rows(table, layer, nb, n_rows):
    """A layer's block numbers -> rows of the stacked pool; a sentinel or
    out-of-range entry goes to ``n_rows``, past the WHOLE stacked pool, so
    its scatter is dropped (never row 0 of the next layer)."""
    valid = (table >= 0) & (table < nb)
    return jnp.where(valid, layer * nb + table, n_rows)


def paged_decode_attention(p, x, cfg, *, pool_k, pool_v, layer,
                           block_tables, cache_len, active=None,
                           impl="auto"):
    """One-token decode against a *paged* KV cache (block pool + tables).

    pool_k/v: the stacked pool of every layer, ``(L, num_blocks, bs,
    KV*D)`` lane-dense rows; ``layer`` (traced) picks this layer's rows.
    Each lane's logical positions map through block_tables (B,
    max_blocks) to physical blocks.  Writes the new kv, in place, at
    logical position ``cache_len`` (physical: block ``bt[b, cache_len //
    bs]`` of ``layer``, offset ``cache_len % bs``); inactive lanes are
    routed past the whole pool and dropped (``mode="drop"``), the paged
    analogue of the dense path's keep-old-value masking.  The attention
    core (``kernels.paged_attention``) is bit-identical to
    ``decode_attention``'s ``full_attention`` on CPU backends and a
    Pallas kernel on TPU.

    Returns (out, (pool_k, pool_v)).
    """
    from repro.kernels.paged_attention import paged_attention
    dt = jnp.dtype(cfg.compute_dtype)
    b, s, _ = x.shape  # s == 1
    with jax.named_scope("qkv"):
        h = rms_norm(x, p["norm"], cfg.norm_eps).astype(dt)
        q = jnp.einsum("bsd,dhk->bshk", h, p["wq"].astype(dt))
        k = jnp.einsum("bsd,dhk->bshk", h, p["wk"].astype(dt))
        v = jnp.einsum("bsd,dhk->bshk", h, p["wv"].astype(dt))
        cos, sin = rotary_embedding(cache_len[:, None], cfg.head_dim,
                                    cfg.rope_theta)
        q = apply_rotary(q, cos, sin)
        k = apply_rotary(k, cos, sin)
    shape, nb, bs = pool_k.shape, pool_k.shape[1], pool_k.shape[2]
    rows_k, rows_v = _pool_rows(pool_k), _pool_rows(pool_v)
    with jax.named_scope("kv_write"):
        blk = block_tables[jnp.arange(b), cache_len // bs]
        if active is not None:
            blk = jnp.where(active, blk, nb)   # sentinel -> write dropped
        row = _write_rows(blk, layer, nb, rows_k.shape[0])
        off = cache_len % bs
        rows_k = rows_k.at[row, off].set(
            k[:, 0].reshape(b, -1).astype(rows_k.dtype), mode="drop")
        rows_v = rows_v.at[row, off].set(
            v[:, 0].reshape(b, -1).astype(rows_v.dtype), mode="drop")
    with jax.named_scope("attention"):
        out = paged_attention(q[:, 0], rows_k, rows_v,
                              _read_rows(block_tables, layer, nb),
                              cache_len + 1, impl=impl)[:, None]
    with jax.named_scope("attn_out"):
        out = x + jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt))
    return out, (rows_k.reshape(shape), rows_v.reshape(shape))


def paged_chunk_attention(p, x, cfg, *, pool_k, pool_v, layer, bt_row, off,
                          history=True):
    """Chunk prefill over one slot's paged KV blocks.

    x: (1, C, d) — C prompt tokens at absolute positions off..off+C-1.
    pool_k/v: the stacked pool ``(L, num_blocks, bs, KV*D)``; ``layer``
    (traced) picks this layer's rows.  bt_row: (max_blocks,) the slot's
    block table.  Gathers the slot's rows into a contiguous (1, S_max,
    KV, D) view, writes the chunk's kv at ``off`` (a block-table append
    in logical terms), attends causally at ``q_offset=off`` over history
    + chunk, and scatters the rows back in place through the table
    (sentinel entries routed past the whole pool and dropped).
    Per-position math matches the streamed decode path bit-for-bit for
    causal families — masked history/pad positions contribute exact
    zeros — which is what lets multi-chunk prefill subsume
    prefill-with-history.

    ``history=False`` is the first-chunk (``off == 0``) specialization:
    with no history every gathered position is masked, so the gather /
    update-slice / full-view attention collapses to causal attention
    within the chunk plus a scatter of only the chunk's own blocks.
    Identical per-position math (masked columns contribute exact zeros
    either way), a fraction of the memory traffic — this is what keeps
    paged admission prefill on par with the dense path's.

    Returns (out, pool_k, pool_v).
    """
    dt = jnp.dtype(cfg.compute_dtype)
    b, c, _ = x.shape  # b == 1
    with jax.named_scope("qkv"):
        h = rms_norm(x, p["norm"], cfg.norm_eps).astype(dt)
        q = jnp.einsum("bsd,dhk->bshk", h, p["wq"].astype(dt))
        k = jnp.einsum("bsd,dhk->bshk", h, p["wk"].astype(dt))
        v = jnp.einsum("bsd,dhk->bshk", h, p["wv"].astype(dt))
        positions = off + jnp.arange(c)
        cos, sin = rotary_embedding(positions, cfg.head_dim, cfg.rope_theta)
        q = apply_rotary(q, cos, sin)
        k = apply_rotary(k, cos, sin)
    shape, nb, bs = pool_k.shape, pool_k.shape[1], pool_k.shape[2]
    rows_k, rows_v = _pool_rows(pool_k), _pool_rows(pool_v)
    n_rows, kvd = rows_k.shape[0], rows_k.shape[2]
    if not history:
        with jax.named_scope("attention"):
            out = full_attention(q, k.astype(dt), v.astype(dt), causal=True)
        with jax.named_scope("attn_out"):
            out = x + jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt))
        with jax.named_scope("kv_write"):
            n_blk = -(-c // bs)
            row = _write_rows(bt_row[:n_blk], layer, nb, n_rows)
            pad = ((0, n_blk * bs - c), (0, 0))

            def blocks(t, dtype):
                t = jnp.pad(t[0].reshape(c, kvd).astype(dtype), pad)
                return t.reshape(n_blk, bs, kvd)

            rows_k = rows_k.at[row].set(blocks(k, rows_k.dtype), mode="drop")
            rows_v = rows_v.at[row].set(blocks(v, rows_v.dtype), mode="drop")
        return out, rows_k.reshape(shape), rows_v.reshape(shape)
    # the slot's history view, with the chunk's kv in place, is what
    # attention reads: its gather and update belong to ``attention``
    with jax.named_scope("attention"):
        mb = bt_row.shape[0]
        read = _read_rows(bt_row, layer, nb)
        hist_k = rows_k[read].reshape(1, mb * bs, -1, cfg.head_dim)
        hist_v = rows_v[read].reshape(1, mb * bs, -1, cfg.head_dim)
        hist_k = jax.lax.dynamic_update_slice(
            hist_k, k.astype(hist_k.dtype), (0, off, 0, 0))
        hist_v = jax.lax.dynamic_update_slice(
            hist_v, v.astype(hist_v.dtype), (0, off, 0, 0))
        out = full_attention(q, hist_k.astype(dt), hist_v.astype(dt),
                             causal=True, q_offset=off)
    with jax.named_scope("attn_out"):
        out = x + jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt))
    with jax.named_scope("kv_write"):
        row = _write_rows(bt_row, layer, nb, n_rows)
        rows_k = rows_k.at[row].set(hist_k.reshape(mb, bs, kvd), mode="drop")
        rows_v = rows_v.at[row].set(hist_v.reshape(mb, bs, kvd), mode="drop")
    return out, rows_k.reshape(shape), rows_v.reshape(shape)


# ----------------------------------------------------------------------------- MLP
def swiglu_block(p, x, cfg):
    dt = jnp.dtype(cfg.compute_dtype)
    h = rms_norm(x, p["norm"], cfg.norm_eps).astype(dt)
    g = jnp.einsum("bsd,df->bsf", h, p["w_gate"].astype(dt))
    u = jnp.einsum("bsd,df->bsf", h, p["w_up"].astype(dt))
    act = constrain(jax.nn.silu(g) * u, "batch", None, "ff")
    out = jnp.einsum("bsf,fd->bsd", act, p["w_down"].astype(dt))
    return x + constrain(out, "batch", None, "embed")
