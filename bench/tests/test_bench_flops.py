"""Operation and byte counts, worked by hand at a tiny size."""

import pytest

import flops
from weights import Dims

# 2 layers, d 8, 2 heads of 4 (1 kv head), ff 16, vocab 10
M = Dims(layers=2, d=8, heads=2, kv_heads=1, head_dim=4, ff=16, vocab=10,
         tied=False, rope_theta=1e4, eps=1e-5)


def test_matmul_flops_per_token():
    # per layer: q 8*2*4=64, k 8*1*4=32, v 32, o 2*4*8=64, mlp 3*8*16=384
    # -> 576 multiply-adds, 1152 flops; two layers 2304
    assert flops.matmul_flops_per_token(M) == 2304


def test_prefill_counts_causal_context():
    # 3 tokens from position 0 attend over 1, 2, 3 positions: 6 in all;
    # attention 4 * heads * head_dim * 6 * layers = 4*2*4*6*2 = 384
    assert flops.prefill_flops(M, 3) == 3 * 2304 + 384
    # the same 3 tokens after 5 cached ones attend over 6, 7, 8
    assert flops.prefill_flops(M, 3, start=5) == 3 * 2304 + 4 * 2 * 4 * 21 * 2


def test_decode_adds_the_head():
    # two lanes at kv lengths 4 and 6: head 2*8*10 = 160 each
    assert flops.decode_flops(M, [4, 6]) == 2 * (2304 + 160) + 4 * 2 * 4 * 10 * 2


def test_paged_attention_cost_counts_live_positions_only():
    f, b = flops.paged_attention_cost(M, [4, 6])
    assert f == 4 * 2 * 4 * 10                       # one layer
    # keys and values of 10 positions (1 kv head of 4, bf16) + q and out
    assert b == 2 * 10 * 1 * 4 * 2 + 2 * 2 * 2 * 4 * 2


def test_roofline_takes_the_slower_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.roofline_seconds(50, 100, peak) == pytest.approx(10.0)
    assert flops.roofline_seconds(5000, 100, peak) == pytest.approx(50.0)
