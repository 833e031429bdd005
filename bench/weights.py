"""Seeded weights of a dense Granite/Llama decoder, made by the benchmark.

Every weight is an int8-valued draw times a power of two (norm weights:
1 + a small multiple of 2**-7), so it is exact in bfloat16 and in
float32 alike: the program is served these bfloat16 values, and the
reference regenerates the very same numbers, one layer at a time, from
the seed alone.  Nothing made by the program is ever read back.

Granite's four multipliers (embedding, attention, residual, logits) are
folded into the weights the program is served, since the program's
decoder has none of them: the query projection carries the attention
multiplier over the program's head_dim**-0.5, the output and down
projections the residual multiplier, the embedding the embedding
multiplier, and the head (or, tied, the final norm) the inverse of the
logits scaling.  The served numbers are the draws themselves; the
Granite weights they fold are the draws divided by each leaf's factor,
and that unfolded view is what the reference computes with, applying
the multipliers itself.  Drawing the served numbers (and not the
unfolded ones) keeps every layer's share of the residual stream as in a
plain decoder: drawn unfolded at these scales, the embedding times 12
outweighs what the layers add, and a random model only echoes its input.

``program_params`` builds the program's parameter tree (the layout of
``repro.models.transformer.decoder_lm_schema``) on the device in one
jitted call; ``layer_weights``/``top_weights`` give the reference the
unfolded values in float32.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

# int8 draws are uniform on [-128, 127]: standard deviation ~73.9
_INT8_STD = 73.9

LAYER_LEAVES = (("attn", "norm"), ("attn", "wq"), ("attn", "wk"),
                ("attn", "wv"), ("attn", "wo"), ("mlp", "norm"),
                ("mlp", "w_gate"), ("mlp", "w_up"), ("mlp", "w_down"))
TOP_LEAVES = ("embed", "final_norm", "lm_head")


@dataclasses.dataclass(frozen=True)
class Dims:
    """Sizes of a dense decoder, read from a configuration file."""
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    tied: bool
    rope_theta: float
    eps: float
    emb_mult: float = 1.0
    attn_mult: float = 0.0        # 0: head_dim**-0.5, the program's own
    resid_mult: float = 1.0
    logits_scale: float = 1.0

    @property
    def score_scale(self) -> float:
        return self.attn_mult or self.head_dim ** -0.5

    @property
    def padded_vocab(self) -> int:
        return -(-self.vocab // 256) * 256

    @classmethod
    def from_config(cls, cfg: dict) -> "Dims":
        return cls(layers=cfg["num_hidden_layers"], d=cfg["hidden_size"],
                   heads=cfg["num_attention_heads"],
                   kv_heads=cfg["num_key_value_heads"],
                   head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
                   ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
                   tied=bool(cfg["tie_word_embeddings"]),
                   rope_theta=float(cfg["rope_theta"]),
                   eps=float(cfg["rms_norm_eps"]),
                   emb_mult=float(cfg.get("embedding_multiplier", 1.0)),
                   attn_mult=float(cfg.get("attention_multiplier", 0.0)),
                   resid_mult=float(cfg.get("residual_multiplier", 1.0)),
                   logits_scale=float(cfg.get("logits_scaling", 1.0)))


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number (seeds may exceed 32 bits)."""
    seed = int(seed) % (1 << 62)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, seed >> 31)


def _layer_shapes(m: Dims):
    d, h, kv, hd, f = m.d, m.heads, m.kv_heads, m.head_dim, m.ff
    return {("attn", "norm"): ((d,), 0), ("attn", "wq"): ((d, h, hd), d),
            ("attn", "wk"): ((d, kv, hd), d),
            ("attn", "wv"): ((d, kv, hd), d),
            ("attn", "wo"): ((h, hd, d), h * hd),
            ("mlp", "norm"): ((d,), 0), ("mlp", "w_gate"): ((d, f), d),
            ("mlp", "w_up"): ((d, f), d), ("mlp", "w_down"): ((f, d), f)}


def _top_shapes(m: Dims):
    return {"embed": ((m.padded_vocab, m.d), m.d),
            "final_norm": ((m.d,), 0),
            "lm_head": ((m.d, m.padded_vocab), m.d)}


def _draw(key, shape, fan_in: int, dtype):
    """int8 draw times 2**-e with e set by ``fan_in`` (0: a norm weight,
    1 + j * 2**-7 with j in [-8, 7])."""
    bits = jax.random.bits(key, shape, jnp.uint8).astype(jnp.int32)
    if fan_in == 0:
        return (1.0 + ((bits >> 4) - 8).astype(jnp.float32)
                * 2.0 ** -7).astype(dtype)
    scale = 2.0 ** round(math.log2(1.0 / (math.sqrt(fan_in) * _INT8_STD)))
    return ((bits - 128).astype(jnp.float32) * scale).astype(dtype)


def _leaf_key(key, name: str):
    names = [".".join(p) for p in LAYER_LEAVES] + list(TOP_LEAVES)
    return jax.random.fold_in(key, names.index(name))


def fold_factors(m: Dims) -> dict:
    """Factor each served leaf carries for a multiplier the program
    lacks (leaves not named carry 1)."""
    head = 1.0 / m.logits_scale
    return {("attn", "wq"): m.score_scale * m.head_dim ** 0.5,
            ("attn", "wo"): m.resid_mult, ("mlp", "w_down"): m.resid_mult,
            "embed": m.emb_mult,
            "final_norm": head / m.emb_mult if m.tied else 1.0,
            "lm_head": head}


def _leaf(m: Dims, k, leaf, shape, fan, served: bool):
    """One leaf: the served bfloat16 draw, or (``served=False``) the
    float32 unfolded view the reference computes with."""
    w = _draw(k, shape, fan, jnp.bfloat16)
    if served:
        return w
    return w.astype(jnp.float32) / fold_factors(m).get(leaf, 1.0)


def layer_weights(m: Dims, key, layer, served: bool = False):
    """Layer ``layer`` (may be traced) as {(group, name): array}."""
    out = {}
    for leaf, (shape, fan) in _layer_shapes(m).items():
        k = jax.random.fold_in(_leaf_key(key, ".".join(leaf)), layer)
        out[leaf] = _leaf(m, k, leaf, shape, fan, served)
    return out


def top_weights(m: Dims, key, served: bool = False):
    out = {}
    for name, (shape, fan) in _top_shapes(m).items():
        if name == "lm_head" and m.tied:
            continue
        out[name] = _leaf(m, _leaf_key(key, name), name, shape, fan, served)
    return out


def program_params(m: Dims, seed: int):
    """The program's bfloat16 parameter tree, made on the device in one
    jitted call (the key is an argument, so every seed shares it)."""
    return _program_params(m, seed_key(seed))


@functools.partial(jax.jit, static_argnums=(0,))
def _program_params(m: Dims, key):
    layers = jax.vmap(lambda l: layer_weights(m, key, l, served=True))(
        jnp.arange(m.layers))
    tree = {"layers": {"attn": {}, "mlp": {}}}
    for (group, name), arr in layers.items():
        tree["layers"][group][name] = arr
    tree.update(top_weights(m, key, served=True))
    return tree
