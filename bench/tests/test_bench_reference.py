"""The float32 reference against the program, at a small size."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

import reference
import weights
from weights import Dims

DIMS = {
    "untied": Dims(layers=2, d=64, heads=4, kv_heads=2, head_dim=16, ff=128,
                   vocab=250, tied=False, rope_theta=1e4, eps=1e-5),
    "tied": Dims(layers=3, d=64, heads=4, kv_heads=1, head_dim=16, ff=96,
                 vocab=300, tied=True, rope_theta=1e4, eps=1e-5),
    # Granite 3.0's multipliers, folded into the served weights
    "granite": Dims(layers=3, d=64, heads=4, kv_heads=1, head_dim=16,
                    ff=96, vocab=300, tied=True, rope_theta=1e4, eps=1e-6,
                    emb_mult=12.0, attn_mult=0.015625, resid_mult=0.22,
                    logits_scale=8.0),
    "granite_untied": Dims(layers=2, d=64, heads=4, kv_heads=2, head_dim=16,
                           ff=128, vocab=250, tied=False, rope_theta=1e4,
                           eps=1e-6, emb_mult=12.0, attn_mult=0.015625,
                           resid_mult=0.22, logits_scale=8.0),
}


def program_cfg(m: Dims, compute="bfloat16"):
    from repro.configs import get_config
    return get_config("granite-8b").with_(
        num_layers=m.layers, d_model=m.d, num_heads=m.heads,
        num_kv_heads=m.kv_heads, head_dim=m.head_dim, d_ff=m.ff,
        vocab_size=m.vocab, tie_embeddings=m.tied, rope_theta=m.rope_theta,
        norm_eps=m.eps, param_dtype="bfloat16", compute_dtype=compute)


@pytest.mark.parametrize("name", sorted(DIMS))
def test_layers_regenerate_exactly(name):
    m = DIMS[name]
    params = weights.program_params(m, 3_000_000_007)
    key = weights.seed_key(3_000_000_007)
    for layer in range(m.layers):
        w = weights.layer_weights(m, key, layer, served=True)
        for (group, leaf), arr in w.items():
            got = params["layers"][group][leaf][layer]
            assert jnp.array_equal(got, arr), (layer, group, leaf)
    top = weights.top_weights(m, key, served=True)
    assert jnp.array_equal(params["embed"], top["embed"])
    assert ("lm_head" in params) == (not m.tied)


@pytest.mark.parametrize("name", sorted(DIMS))
def test_reference_matches_the_program_forward_in_float32(name):
    """Same weights, same tokens: the program's own forward in float32
    and the reference agree to float32 rounding, so the reference
    computes the program's model (rope, GQA grouping, norms, head)."""
    from repro.models import transformer as T
    m = DIMS[name]
    cfg = program_cfg(m, compute="float32")
    params = weights.program_params(m, 11)
    toks = np.random.default_rng(0).integers(0, m.vocab, 512, np.int32)
    h, _ = T.decoder_forward(params, jnp.asarray(toks)[None], cfg)
    prog = np.asarray(T.lm_logits(params, h, cfg)[0, :, :m.vocab])
    ref = np.asarray(reference.logits(m, weights.seed_key(11),
                                      jnp.asarray(toks)))
    np.testing.assert_allclose(prog, ref, atol=2e-4, rtol=1e-4)


@pytest.mark.parametrize("name", sorted(DIMS))
def test_paged_engine_serves_what_the_reference_ranks_first(name):
    """bf16 paged engine, prompts over several chunks, greedy: every
    served token lies within rounding of the reference's best, and the
    fp8 control lies further off."""
    from repro.serving.engine import Request, ServingEngine
    m = DIMS[name]
    params = weights.program_params(m, 5)
    eng = ServingEngine(program_cfg(m), params, batch_size=3, max_seq=256,
                        cache_mode="paged", block_size=16)
    rng = np.random.default_rng(1)
    reqs = [Request(i, rng.integers(0, m.vocab, n, np.int32), k)
            for i, (n, k) in enumerate([(200, 20), (40, 30), (90, 12)])]
    for r in reqs:
        eng.submit(r)
    eng.run_until_idle()
    gaps = [reference.served_gap(m, 5, r.prompt, r.out_tokens)
            for r in reqs]
    ctl = [reference.served_gap(m, 5, r.prompt, r.out_tokens, control=True)
           for r in reqs]
    assert max(gaps) < 0.05
    assert max(ctl) > 3 * max(gaps)


def test_a_wrong_token_reads_far_off():
    m = DIMS["untied"]
    prompt = np.arange(40, dtype=np.int32) % m.vocab
    ref = np.asarray(reference.logits(
        m, weights.seed_key(5),
        jnp.asarray(np.concatenate([prompt, np.zeros(472, np.int32)]))))
    best = int(np.argmax(ref[39]))
    worst = int(np.argmin(ref[39]))
    assert reference.served_gap(m, 5, prompt, [best]) == 0.0
    assert reference.served_gap(m, 5, prompt, [worst]) > 1.0
    assert reference.served_gap(m, 5, prompt, [m.vocab + 3]) == float("inf")


@pytest.mark.parametrize("name", ["granite", "granite_untied"])
def test_the_reference_sees_the_served_numbers_unfolded(name):
    """Each unfolded leaf times its factor gives back the served
    bfloat16 draw; a power-of-two factor unfolds exactly."""
    m = DIMS[name]
    key = weights.seed_key(21)
    factors = weights.fold_factors(m)
    served = weights.layer_weights(m, key, 1, served=True)
    view = weights.layer_weights(m, key, 1)
    served.update(weights.top_weights(m, key, served=True))
    view.update(weights.top_weights(m, key))
    for leaf, arr in served.items():
        back = (view[leaf] * factors.get(leaf, 1.0)).astype(jnp.bfloat16)
        assert jnp.array_equal(back, arr), leaf
    assert factors["attn", "wq"] == 0.0625      # 1/64 over 16**-0.5
    assert jnp.array_equal(view["attn", "wq"] * 0.0625,
                           served["attn", "wq"].astype(jnp.float32))


@pytest.mark.parametrize("mult,value,expect", [
    ("emb_mult", 12.0, {"embed": 12.0, "final_norm": 1 / 12.0}),
    ("attn_mult", 0.015625, {("attn", "wq"): 0.0625}),
    ("resid_mult", 0.22, {("attn", "wo"): 0.22, ("mlp", "w_down"): 0.22}),
    ("logits_scale", 8.0, {"final_norm": 0.125})])
def test_each_multiplier_folds_into_its_leaves(mult, value, expect):
    """One multiplier alone, tied head: the leaves that carry it, worked
    by hand (the tied head is the embedding, so the final norm undoes
    the embedding multiplier and carries the logits scaling)."""
    m = dataclasses.replace(DIMS["tied"], **{mult: value})
    got = {k: v for k, v in weights.fold_factors(m).items()
           if v != 1.0 and k != "lm_head"}
    assert got == pytest.approx(expect)
