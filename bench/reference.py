"""Plain float32 reference of a dense Granite/Llama decoder.

RMSNorm, rotary position embedding (half-split rotation, as in Llama and
Granite), grouped-query causal attention, SwiGLU, untied or tied head,
and Granite's embedding, attention, residual and logits multipliers.
It imports nothing of the program: the weights are regenerated from the
seed by ``weights.py``, layer by layer inside one loop, and every matrix
product runs at ``Precision.HIGHEST`` (a TPU otherwise multiplies float32
in bfloat16).  Attention is computed in blocks of queries so that a
whole prompt of a few thousand tokens fits beside nothing else.

``fp8=True`` is the control: the same computation with the operands of
every linear layer and of the head rounded to float8 (e4m3, one scale per
weight tensor and one per activation row), the precision below the
bfloat16 the configurations state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from weights import Dims, layer_weights, seed_key, top_weights

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512
E4M3_MAX = 448.0


def _fp8(x, axis):
    """Round to float8 e4m3 with a per-slice scale along ``axis``."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / E4M3_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _linear(spec, x, w, fp8):
    if fp8:
        x = _fp8(x, axis=-1)
        w = _fp8(w, axis=None)
    return jnp.einsum(spec, x, w, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x: (S, heads, D); rotates the two halves of the head dim."""
    half = x.shape[-1] // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, scale):
    """Causal GQA.  q: (S, H, D); k, v: (S, KV, D)."""
    s, h, d = q.shape
    kvh = k.shape[1]
    qg = q.reshape(s // Q_BLOCK, Q_BLOCK, kvh, h // kvh, d)
    kpos = jnp.arange(s)

    def block(args):
        i, qb = args
        sc = jnp.einsum("qkgd,skd->kgqs", qb, k, precision=HIGHEST)
        sc = sc * scale
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.where(kpos[None, :] <= qpos[:, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, (jnp.arange(s // Q_BLOCK), qg))
    return out.reshape(s, h, d)


@functools.partial(jax.jit, static_argnums=(0, 3))
def logits(m: Dims, key, tokens, fp8: bool = False):
    """Logits (S, vocab) at every position of ``tokens`` (S,), S a
    multiple of ``Q_BLOCK``; causal, so right padding changes nothing
    before it."""
    top = top_weights(m, key)
    pos = jnp.arange(tokens.shape[0])
    x = m.emb_mult * top["embed"][tokens]

    def layer(l, x):
        w = layer_weights(m, key, l)
        h = _rms(x, w["attn", "norm"], m.eps)
        q = _rope(_linear("sd,dhk->shk", h, w["attn", "wq"], fp8), pos,
                  m.rope_theta)
        k = _rope(_linear("sd,dhk->shk", h, w["attn", "wk"], fp8), pos,
                  m.rope_theta)
        v = _linear("sd,dhk->shk", h, w["attn", "wv"], fp8)
        a = _attention(q, k, v, m.score_scale).reshape(x.shape[0], -1)
        wo = w["attn", "wo"].reshape(-1, m.d)
        x = x + m.resid_mult * _linear("sf,fd->sd", a, wo, fp8)
        h = _rms(x, w["mlp", "norm"], m.eps)
        g = _linear("sd,df->sf", h, w["mlp", "w_gate"], fp8)
        u = _linear("sd,df->sf", h, w["mlp", "w_up"], fp8)
        return x + m.resid_mult * _linear("sf,fd->sd", jax.nn.silu(g) * u,
                                          w["mlp", "w_down"], fp8)

    x = jax.lax.fori_loop(0, m.layers, layer, x)
    h = _rms(x, top["final_norm"], m.eps)
    head = top["embed"].T if m.tied else top["lm_head"]
    return _linear("sd,dv->sv", h, head[:, :m.vocab], fp8) / m.logits_scale


def _padded(tokens, bucket: int):
    out = np.zeros(-(-len(tokens) // bucket) * bucket, np.int32)
    out[:len(tokens)] = tokens
    return jnp.asarray(out)


@jax.jit
def _gaps(ref, choice):
    """Per position: how far the reference logit of ``choice`` lies
    below the reference's best."""
    picked = jnp.take_along_axis(ref, choice[:, None], axis=-1)[:, 0]
    return jnp.max(ref, axis=-1) - picked


def served_gap(m: Dims, seed: int, prompt, served, *, bucket: int = 1024,
               control: bool = False) -> float:
    """Widest gap, over the served tokens of one request, by which a
    served token's reference logit lies below the reference's best.

    The reference runs once over prompt + served tokens; the token served
    at step i was predicted at position len(prompt) - 1 + i.  With
    ``control`` the tokens judged are instead those the fp8 computation
    puts first at the same positions, over the same context.
    """
    key = seed_key(seed)
    seq = np.concatenate([np.asarray(prompt), np.asarray(served)])
    toks = _padded(seq, max(bucket, Q_BLOCK))
    ref = logits(m, key, toks)
    lo, n = len(prompt) - 1, len(served)
    if control:
        choice = jnp.argmax(logits(m, key, toks, True), axis=-1)
    else:
        if not all(0 <= t < m.vocab for t in served):
            return float("inf")
        choice = np.zeros(toks.shape, np.int32)
        choice[lo:lo + n] = served
    gaps = np.asarray(_gaps(ref, jnp.asarray(choice, jnp.int32)))[lo:lo + n]
    if not np.isfinite(gaps).all():
        return float("inf")
    return float(gaps.max())
