"""Operations and bytes the algorithm needs, from shapes and live lengths.

Counts follow the model, not the program: a matrix product of (m, k) by
(k, n) is 2mkn operations; attention over a context of c positions costs
4 * heads * head_dim * c per token (scores and values).  Only live
positions count, never the capacity of a cache or the blocks a kernel
happens to fetch, so a share of a peak computed from them cannot pass
100% unless the time leaves out work.
"""

from __future__ import annotations

from weights import Dims

BF16 = 2


def matmul_flops_per_token(m: Dims) -> float:
    """Linear layers of one token through every layer (no head)."""
    d, h, kv, hd, f = m.d, m.heads, m.kv_heads, m.head_dim, m.ff
    return 2.0 * m.layers * (d * h * hd + 2 * d * kv * hd + h * hd * d
                             + 3 * d * f)


def attention_flops(m: Dims, ctx: float) -> float:
    """Attention of one token over ``ctx`` positions, every layer."""
    return 4.0 * m.layers * m.heads * m.head_dim * ctx


def head_flops(m: Dims) -> float:
    return 2.0 * m.d * m.vocab


def prefill_flops(m: Dims, n: int, start: int = 0) -> float:
    """Prompt tokens start .. start+n-1, each attending causally over
    itself and what precedes it; no logits (the engine discards them)."""
    ctx_sum = n * start + n * (n + 1) / 2.0
    return n * matmul_flops_per_token(m) + attention_flops(m, ctx_sum)


def decode_flops(m: Dims, ctx_lens) -> float:
    """One decode token per entry of ``ctx_lens`` (its kv length,
    itself included), with its logits."""
    ctx_lens = list(ctx_lens)
    return (len(ctx_lens) * (matmul_flops_per_token(m) + head_flops(m))
            + attention_flops(m, sum(ctx_lens)))


def paged_attention_cost(m: Dims, ctx_lens):
    """(flops, bytes) of one paged-attention call, one layer: each live
    lane reads its ``ctx`` keys and values once and its query, and
    writes its output."""
    ctx_lens = list(ctx_lens)
    kv = sum(ctx_lens)
    flops = 4.0 * m.heads * m.head_dim * kv
    nbytes = (2.0 * kv * m.kv_heads * m.head_dim * BF16
              + 2.0 * len(ctx_lens) * m.heads * m.head_dim * BF16)
    return flops, nbytes


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """Least time the chip could take: the slower of compute and HBM."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
