"""Task-level model API: loss / train_step / prefill / serve_step per arch.

Everything here is functional and mesh-agnostic; sharding enters only through
(a) in/out shardings chosen by the launcher and (b) logical-axis constraints
inside the model code.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.launch.sharding import constrain
from repro.models import layers as L
from repro.models import mamba2 as ssm_lib
from repro.models import transformer as T
from repro.models import schema
from repro.optim import adamw


def _scan(body, init, xs, unroll=False):
    """lax.scan, or a fully unrolled Python loop for analysis builds."""
    if not unroll:
        return jax.lax.scan(body, init, xs)
    length = jax.tree.leaves(xs)[0].shape[0]
    carry, ys = init, []
    for i in range(length):
        carry, y = body(carry, jax.tree.map(lambda x: x[i], xs))
        ys.append(y)
    if ys and jax.tree.leaves(ys[0]):
        ys = jax.tree.map(lambda *a: jnp.stack(a), *ys)
    else:
        ys = ()
    return carry, ys


# ============================================================== batches
def batch_spec(cfg: ModelConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """Abstract input batch (ShapeDtypeStructs) for a (arch x shape) cell."""
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    if shape.kind == "train":
        if cfg.family == "enc_dec":
            return {
                "frames": jax.ShapeDtypeStruct((B, S, cfg.d_model),
                                               jnp.bfloat16),
                "tokens": jax.ShapeDtypeStruct((B, S), i32),
                "labels": jax.ShapeDtypeStruct((B, S), i32),
            }
        if cfg.family == "vlm":
            st = S - cfg.frontend_seq
            return {
                "tokens": jax.ShapeDtypeStruct((B, st), i32),
                "labels": jax.ShapeDtypeStruct((B, st), i32),
                "patch_embeds": jax.ShapeDtypeStruct(
                    (B, cfg.frontend_seq, cfg.d_model), jnp.bfloat16),
            }
        return {
            "tokens": jax.ShapeDtypeStruct((B, S), i32),
            "labels": jax.ShapeDtypeStruct((B, S), i32),
        }
    if shape.kind == "prefill":
        if cfg.family == "enc_dec":
            return {
                "frames": jax.ShapeDtypeStruct((B, S, cfg.d_model),
                                               jnp.bfloat16),
                "tokens": jax.ShapeDtypeStruct((B, S), i32),
            }
        if cfg.family == "vlm":
            return {
                "tokens": jax.ShapeDtypeStruct((B, S - cfg.frontend_seq), i32),
                "patch_embeds": jax.ShapeDtypeStruct(
                    (B, cfg.frontend_seq, cfg.d_model), jnp.bfloat16),
            }
        return {"tokens": jax.ShapeDtypeStruct((B, S), i32)}
    if shape.kind == "decode":
        return {"tokens": jax.ShapeDtypeStruct((B, 1), i32),
                "active": jax.ShapeDtypeStruct((B,), i32)}
    raise ValueError(shape.kind)


def make_batch(cfg: ModelConfig, shape: ShapeConfig, rng) -> Dict[str, Any]:
    """Concrete random batch matching ``batch_spec`` (smoke tests)."""
    spec = batch_spec(cfg, shape)
    out = {}
    for k, v in spec.items():
        rng, sub = jax.random.split(rng)
        if k == "active":
            out[k] = jnp.ones(v.shape, jnp.int32)
        elif v.dtype == jnp.int32:
            out[k] = jax.random.randint(sub, v.shape, 0, cfg.vocab_size,
                                        jnp.int32)
        else:
            out[k] = jax.random.normal(sub, v.shape, jnp.float32).astype(
                v.dtype)
    return out


# ============================================================== loss
def lm_loss(params, batch, cfg: ModelConfig, *, unroll=False):
    """Causal-LM cross-entropy (mean over tokens) + MoE aux loss."""
    if cfg.family == "enc_dec":
        h = T.enc_dec_forward(params, batch["frames"], batch["tokens"], cfg,
                              unroll=unroll)
        aux = jnp.zeros((), jnp.float32)
        labels = batch["labels"]
    elif cfg.family == "vlm":
        h, aux = T.decoder_forward(params, batch["tokens"], cfg,
                                   patch_embeds=batch["patch_embeds"],
                                   unroll=unroll)
        h = h[:, cfg.frontend_seq:]           # loss only on text positions
        labels = batch["labels"]
    else:
        h, aux = T.decoder_forward(params, batch["tokens"], cfg,
                                   unroll=unroll)
        labels = batch["labels"]
    logits = T.lm_logits(params, h, cfg)      # (B, S, V) fp32
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    loss = nll.mean() + aux
    return loss, {"nll": nll.mean(), "aux": aux}


# ============================================================== train state
class TrainState(NamedTuple):
    step: jax.Array
    params: Any
    opt: adamw.AdamWState


def init_params(cfg: ModelConfig, rng):
    """Model params only (what serving needs: no optimizer state)."""
    return schema.init_params(T.model_schema(cfg), rng, cfg.param_dtype)


def init_state(cfg: ModelConfig, rng) -> TrainState:
    params = init_params(cfg, rng)
    return TrainState(jnp.zeros((), jnp.int32), params, adamw.init(params))


def abstract_state(cfg: ModelConfig) -> TrainState:
    sch = T.model_schema(cfg)
    params = schema.abstract_params(sch, cfg.param_dtype)
    return TrainState(
        jax.ShapeDtypeStruct((), jnp.int32), params,
        adamw.abstract_init(params))


def num_params(cfg: ModelConfig) -> int:
    return schema.count_params(T.model_schema(cfg))


def active_params(cfg: ModelConfig) -> int:
    """Params touched per token (MoE: routed top_k of num_experts)."""
    total = num_params(cfg)
    if cfg.family != "moe":
        return total
    per_expert = 3 * cfg.d_model * cfg.d_ff
    routed = cfg.num_layers * cfg.num_experts * per_expert
    active = cfg.num_layers * cfg.top_k * per_expert
    return total - routed + active


# ============================================================== train step
def make_train_step(cfg: ModelConfig, hp: Optional[adamw.HParams] = None,
                    unroll: bool = False):
    hp = hp or adamw.HParams()

    def train_step(state: TrainState, batch):
        n_micro = max(cfg.num_microbatches, 1)

        def reshape_micro(x):
            b = x.shape[0]
            assert b % n_micro == 0, (b, n_micro)
            return x.reshape((n_micro, b // n_micro) + x.shape[1:])

        micro = jax.tree.map(reshape_micro, batch)
        loss_grad = jax.value_and_grad(
            lambda p, mb: lm_loss(p, mb, cfg, unroll=unroll), has_aux=True)

        def accum(carry, mb):
            gacc, lacc = carry
            (loss, metrics), grads = loss_grad(state.params, mb)
            if cfg.grad_schedule == "overlapped":
                # C1 analogue: per-microbatch reduce-scatter over the data
                # axis -> XLA overlaps collective i with compute of i+1.
                grads = _scatter_grads(grads, cfg)
            gacc = jax.tree.map(jnp.add, gacc, grads)
            return (gacc, lacc + loss), metrics

        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
        if cfg.grad_schedule == "overlapped":
            zeros = _scatter_grads(zeros, cfg)
        if unroll:
            # analysis builds: straight-line HLO so cost_analysis counts
            # every microbatch (XLA counts a while body once)
            carry = (zeros, jnp.zeros((), jnp.float32))
            ms = []
            for i in range(n_micro):
                carry, mtr = accum(carry,
                                   jax.tree.map(lambda x: x[i], micro))
                ms.append(mtr)
            gsum, lsum = carry
            metrics = jax.tree.map(lambda *x: jnp.stack(x), *ms)
        else:
            (gsum, lsum), metrics = jax.lax.scan(
                accum, (zeros, jnp.zeros((), jnp.float32)), micro)
        grads = jax.tree.map(lambda g: g / n_micro, gsum)
        if cfg.grad_reduce_dtype == "bfloat16":
            # gradient compression: local accumulation stays f32; the
            # cross-data-axis reduction happens on bf16 (half the wire)
            grads = jax.tree.map(lambda g: g.astype(jnp.bfloat16), grads)
        loss = lsum / n_micro

        params, opt = adamw.update(state.params, grads, state.opt,
                                   state.step, hp)
        new_state = TrainState(state.step + 1, params, opt)
        out_metrics = {"loss": loss,
                       "nll": metrics["nll"].mean(),
                       "aux": metrics["aux"].mean(),
                       "grad_norm": adamw.global_norm(grads)}
        return new_state, out_metrics

    return train_step


def _scatter_grads(grads, cfg: ModelConfig):
    """Constrain grad leaves to the ZeRO-1 (data-scattered) shardings so
    GSPMD lowers the per-microbatch reduction as an (overlappable)
    reduce-scatter instead of one fused terminal all-reduce — and the
    scattered accumulation matches the optimizer-state sharding exactly
    (no extra reshard at the update)."""
    from repro.launch.sharding import active_rules, zero1_shardings
    rules = active_rules()
    if rules is None or "data" not in rules.axes:
        return grads
    sch = T.model_schema(cfg)
    zsh = zero1_shardings(rules, sch)
    return jax.tree.map(jax.lax.with_sharding_constraint, grads, zsh)


# ============================================================== serving
class DecodeState(NamedTuple):
    """Per-family decode state; unused fields are empty dicts/arrays."""
    cache: Any            # family-specific pytree
    cache_len: jax.Array  # (B,) filled positions


def abstract_decode_state(cfg: ModelConfig, shape: ShapeConfig):
    B, S = shape.global_batch, shape.seq_len
    bf16 = jnp.bfloat16
    KV, D = cfg.num_kv_heads, cfg.head_dim
    d_inner, nheads, conv_dim, _ = ssm_lib.mamba2_dims(cfg)
    N, P_ = cfg.ssm_state, cfg.ssm_head_dim

    def sds(shp, dt=bf16):
        return jax.ShapeDtypeStruct(shp, dt)

    if cfg.family in ("dense", "vlm", "moe"):
        cache = {"k": sds((cfg.num_layers, B, S, KV, D)),
                 "v": sds((cfg.num_layers, B, S, KV, D))}
    elif cfg.family == "enc_dec":
        cache = {"k": sds((cfg.dec_layers, B, S, KV, D)),
                 "v": sds((cfg.dec_layers, B, S, KV, D)),
                 "xk": sds((cfg.dec_layers, B, S, KV, D)),
                 "xv": sds((cfg.dec_layers, B, S, KV, D))}
    elif cfg.family == "ssm":
        cache = {"ssm": sds((cfg.num_layers, B, nheads, P_, N), jnp.float32),
                 "conv": sds((cfg.num_layers, B, cfg.conv_width - 1,
                              conv_dim))}
    elif cfg.family == "hybrid":
        periods = cfg.num_layers // cfg.attn_every
        cache = {"ssm": sds((periods, cfg.attn_every, B, nheads, P_, N),
                            jnp.float32),
                 "conv": sds((periods, cfg.attn_every, B,
                              cfg.conv_width - 1, conv_dim)),
                 "k": sds((periods, B, S, KV, D)),
                 "v": sds((periods, B, S, KV, D))}
    else:
        raise ValueError(cfg.family)
    return DecodeState(cache, jax.ShapeDtypeStruct((B,), jnp.int32))


def init_decode_state(cfg: ModelConfig, shape: ShapeConfig,
                      fill_len: Optional[int] = None) -> DecodeState:
    ab = abstract_decode_state(cfg, shape)
    cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), ab.cache)
    fl = shape.seq_len - 1 if fill_len is None else fill_len
    return DecodeState(cache, jnp.full((shape.global_batch,), fl, jnp.int32))


def decode_state_logical_axes(cfg: ModelConfig):
    """Logical axes for the decode-state pytree (for shardings)."""
    kv4 = (None, "cache_batch", "cache_seq", "kv_heads", None)
    if cfg.family in ("dense", "vlm", "moe"):
        cache = {"k": kv4, "v": kv4}
    elif cfg.family == "enc_dec":
        cache = {"k": kv4, "v": kv4, "xk": kv4, "xv": kv4}
    elif cfg.family == "ssm":
        cache = {"ssm": (None, "cache_batch", "ssm_heads", None, None),
                 "conv": (None, "cache_batch", None, "conv_dim")}
    elif cfg.family == "hybrid":
        cache = {"ssm": (None, None, "cache_batch", "ssm_heads", None, None),
                 "conv": (None, None, "cache_batch", None, "conv_dim"),
                 "k": (None, "cache_batch", "cache_seq", "kv_heads", None),
                 "v": (None, "cache_batch", "cache_seq", "kv_heads", None)}
    else:
        raise ValueError(cfg.family)
    return DecodeState(cache, ("cache_batch",))


# -------------------------------------------------------------- prefill
def make_prefill(cfg: ModelConfig, shape: ShapeConfig, unroll: bool = False):
    """Returns fn(params, batch) -> (last_logits, DecodeState)."""

    def prefill(params, batch):
        B = shape.global_batch
        if cfg.family in ("dense", "vlm", "moe"):
            h, caches = _decoder_prefill(params, batch, cfg, unroll)
            cache = caches
        elif cfg.family == "enc_dec":
            h, cache = _encdec_prefill(params, batch, cfg, unroll)
        elif cfg.family in ("ssm", "hybrid"):
            h, cache = _ssm_prefill(params, batch, cfg, unroll)
        else:
            raise ValueError(cfg.family)
        logits = T.lm_logits(params, h[:, -1:], cfg)
        cache_len = jnp.full((B,), _prefill_len(cfg, shape), jnp.int32)
        return logits, DecodeState(cache, cache_len)

    return prefill


def _prefill_len(cfg, shape):
    return shape.seq_len


def _decoder_prefill(params, batch, cfg, unroll):
    tokens = batch["tokens"]
    h = T.embed_tokens(params, tokens, cfg)
    if cfg.family == "vlm":
        pe = constrain(batch["patch_embeds"].astype(h.dtype),
                       "batch", None, "embed")
        h = jnp.concatenate([pe, h], axis=1)
    S = h.shape[1]

    def body(carry, lp):
        x = carry
        x, kv = L.attention_block(lp["attn"], x, cfg, causal=True)
        if cfg.family == "moe":
            from repro.models import moe as moe_lib
            x, _ = moe_lib.moe_block(lp["moe"], x, cfg)
        else:
            x = L.swiglu_block(lp["mlp"], x, cfg)
        k, v = kv
        return x, (k.astype(jnp.bfloat16), v.astype(jnp.bfloat16))

    if unroll:
        ks, vs = [], []
        for i in range(cfg.num_layers):
            h, (k, v) = body(h, jax.tree.map(lambda x: x[i], params["layers"]))
            ks.append(k); vs.append(v)
        cache = {"k": jnp.stack(ks), "v": jnp.stack(vs)}
    else:
        h, (ks, vs) = jax.lax.scan(body, h, params["layers"])
        cache = {"k": ks, "v": vs}
    return h, cache


def _encdec_prefill(params, batch, cfg, unroll):
    enc_out = T.encoder_forward(params, batch["frames"], cfg, unroll=unroll)
    dt = jnp.dtype(cfg.compute_dtype)
    h = T.embed_tokens(params, batch["tokens"], cfg)

    def body(carry, lp):
        x = carry
        x, kv = L.attention_block(lp["self_attn"], x, cfg, causal=True)
        ca = lp["cross_attn"]
        hn = L.rms_norm(x, ca["norm"], cfg.norm_eps).astype(dt)
        q = jnp.einsum("bsd,dhk->bshk", hn, ca["wq"].astype(dt))
        xk = jnp.einsum("bsd,dhk->bshk", enc_out.astype(dt),
                        ca["wk"].astype(dt))
        xv = jnp.einsum("bsd,dhk->bshk", enc_out.astype(dt),
                        ca["wv"].astype(dt))
        att = L.full_attention(q, xk, xv, causal=False)
        x = x + jnp.einsum("bshk,hkd->bsd", att, ca["wo"].astype(dt))
        x = L.swiglu_block(lp["mlp"], x, cfg)
        k, v = kv
        return x, (k.astype(jnp.bfloat16), v.astype(jnp.bfloat16),
                   xk.astype(jnp.bfloat16), xv.astype(jnp.bfloat16))

    h, (ks, vs, xks, xvs) = _scan(body, h, params["dec_layers"],
                                  unroll=unroll)
    return h, {"k": ks, "v": vs, "xk": xks, "xv": xvs}


def _ssm_prefill(params, batch, cfg, unroll):
    h = T.embed_tokens(params, batch["tokens"], cfg)
    W = cfg.conv_width
    if cfg.family == "ssm":
        def body(carry, lp):
            x = carry
            x, (st, conv_tail) = ssm_lib.mamba2_block(lp, x, cfg)
            return x, (st, conv_tail)
        h, (ssm_states, convs) = _scan(body, h, params["layers"],
                                       unroll=unroll)
        return h, {"ssm": ssm_states.astype(jnp.float32),
                   "conv": convs.astype(jnp.bfloat16)}
    else:  # hybrid
        periods = cfg.num_layers // cfg.attn_every
        shared = params["shared"]

        def period_body(carry, pp):
            x = carry
            def inner(c, lp):
                c, (st, conv_tail) = ssm_lib.mamba2_block(lp, c, cfg)
                return c, (st, conv_tail)
            x, (sts, convs) = _scan(inner, x, pp, unroll=unroll)
            x, kv = L.attention_block(shared["attn"], x, cfg, causal=True)
            x = L.swiglu_block(shared["mlp"], x, cfg)
            k, v = kv
            return x, (sts, convs, k.astype(jnp.bfloat16),
                       v.astype(jnp.bfloat16))

        h, (sts, convs, ks, vs) = _scan(period_body, h, params["mamba"],
                                        unroll=unroll)
        return h, {"ssm": sts.astype(jnp.float32),
                   "conv": convs.astype(jnp.bfloat16),
                   "k": ks, "v": vs}


# ------------------------------------------------- chunked bulk prefill
# Families whose prefill needs only ``tokens`` (no frames / patch embeds)
# and can therefore be bulk-prefilled by a serving engine.
BULK_PREFILL_FAMILIES = ("dense", "moe", "ssm", "hybrid")
# Causal-attention families ignore a padded tail (position i never attends
# to j > i), so a prompt chunk may be right-padded to a bucket size.
# Recurrent families (ssm/hybrid) must never feed pad tokens through the
# state recurrence; their chunks are always fully real.
PAD_SAFE_FAMILIES = ("dense", "moe")


def make_bulk_prefill(cfg: ModelConfig, shape: ShapeConfig, chunk: int):
    """Chunked bulk prefill into one slot of a batched decode cache.

    Returns ``fn(params, state, tokens, slot, n_real) -> DecodeState``:
    runs ``make_prefill`` over a ``(1, chunk)`` token buffer and scatters
    the resulting cache columns into row ``slot`` of ``state`` (positions
    ``[0, chunk)`` on every ``cache_seq`` axis; whole-row replacement for
    recurrent-state leaves), then sets ``cache_len[slot] = n_real``.

    ``slot`` and ``n_real`` are traced, so one compiled function per
    (cfg, engine shape, chunk bucket) serves every slot and prompt length
    — the bucket list bounds the number of recompiles.

    Bit-exactness: the prefill forward computes the same per-position
    math as the streamed decode path (verified by the engine equivalence
    tests), so a bulk-prefilled slot continues identically to one that
    streamed its prompt one token per step.
    """
    pshape = ShapeConfig(f"prefill_chunk{chunk}", chunk, 1, "prefill")
    prefill = make_prefill(cfg, pshape)
    batch_axes = {k: ax.index("cache_batch")
                  for k, ax in decode_state_logical_axes(cfg).cache.items()}

    def bulk_prefill(params, state: DecodeState, tokens, slot, n_real):
        _, pstate = prefill(params, {"tokens": tokens})
        new_cache = {}
        for key, leaf in state.cache.items():
            upd = pstate.cache[key].astype(leaf.dtype)
            starts = [0] * leaf.ndim
            starts[batch_axes[key]] = slot
            new_cache[key] = jax.lax.dynamic_update_slice(
                leaf, upd, tuple(starts))
        cache_len = state.cache_len.at[slot].set(
            jnp.asarray(n_real, jnp.int32))
        return DecodeState(new_cache, cache_len)

    return bulk_prefill


# ------------------------------------------------- sync-free decode loop
class SampleState(NamedTuple):
    """Device-resident continuous-batching state for the decode hot loop.

    Everything the per-step control flow needs lives on device, so a
    multi-step decode window performs zero device->host transfers; the
    host reconciles progress from its own exact projection and fetches
    ``out_buf`` only at completion/drain boundaries.
    """
    next_tok: jax.Array   # (B, 1) int32 — token each slot feeds next step
    active: jax.Array     # (B,)  int32 — slot occupied and not finished
    fed: jax.Array        # (B,)  int32 — prompt+generated tokens fed so far
    plen: jax.Array       # (B,)  int32 — prompt length
    maxfed: jax.Array     # (B,)  int32 — fed value at which the slot is done
    out_buf: jax.Array    # (B, S) int32 — generated tokens at index fed-plen
    rng: jax.Array        # PRNG key for device-side temperature sampling


def init_sample_state(cfg: ModelConfig, shape: ShapeConfig,
                      seed: int = 0) -> SampleState:
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    return SampleState(
        next_tok=jnp.zeros((B, 1), i32),
        active=jnp.zeros((B,), i32),
        fed=jnp.zeros((B,), i32),
        plen=jnp.ones((B,), i32),
        maxfed=jnp.zeros((B,), i32),
        out_buf=jnp.zeros((B, S), i32),
        rng=jax.random.PRNGKey(seed),
    )


def make_decode_loop(cfg: ModelConfig, shape: ShapeConfig, n_steps: int,
                     temperature: float = 0.0, unroll: bool = False,
                     eos_token: Optional[int] = None, serve_step=None):
    """Fused sample-and-advance decode: ``n_steps`` serve_steps in ONE
    dispatch, sampling and continuous-batching bookkeeping on device.

    ``serve_step`` injects an alternative per-token step with the same
    calling convention (paged-cache engines pass
    ``make_paged_serve_step``'s); the sampling/bookkeeping body treats
    the cache state opaquely, so dense and paged loops share it —
    which is what makes their token streams bit-identical by
    construction.

    Returns ``fn(params, DecodeState, SampleState, prompt_buf) ->
    (DecodeState, SampleState)``.  Per inner step, each active slot feeds
    ``next_tok``; mid-prefill slots pull their next token from
    ``prompt_buf`` (B, S) while finished-prefill slots take the sampled
    token, write it into ``out_buf`` and self-deactivate once ``fed``
    reaches ``maxfed`` — no host round-trip anywhere in the loop.

    ``eos_token`` enables device-side early exit: a slot that samples the
    EOS token writes it into ``out_buf`` and clears its own active flag,
    so the remaining fused steps of the window skip it entirely.  Tokens
    emitted before (and including) EOS are bit-identical to the
    non-early-exit loop — the extra done condition only fires on the step
    that produced the EOS sample.
    """
    if serve_step is None:
        serve_step = make_serve_step(cfg, shape, unroll=unroll)
    B, S = shape.global_batch, shape.seq_len

    def decode_loop(params, state: DecodeState, sample: SampleState,
                    prompt_buf):
        bidx = jnp.arange(B)

        def body(carry, _):
            state, s = carry
            logits, state = serve_step(
                params, state, {"tokens": s.next_tok, "active": s.active})
            with jax.named_scope("sample"):
                last = logits[:, -1, :]
                rng = s.rng
                if temperature > 0:
                    rng, sub = jax.random.split(rng)
                    sampled = jax.random.categorical(
                        sub, last.astype(jnp.float32) / temperature, axis=-1)
                else:
                    sampled = jnp.argmax(last, axis=-1)
                sampled = sampled.astype(jnp.int32)
                act = s.active > 0
                fed2 = s.fed + s.active
                generating = act & (fed2 >= s.plen)
                oi = jnp.clip(fed2 - s.plen, 0, S - 1)
                out_buf = s.out_buf.at[bidx, oi].set(
                    jnp.where(generating, sampled,
                              s.out_buf[bidx, oi]))
                nxt = jnp.where(fed2 < s.plen,
                                prompt_buf[bidx, jnp.clip(fed2, 0, S - 1)],
                                sampled)
                next_tok = jnp.where(act[:, None], nxt[:, None],
                                     s.next_tok)
                done = generating & (fed2 >= s.maxfed)
                if eos_token is not None:
                    done = done | (generating & (sampled == eos_token))
                active = s.active * (1 - done.astype(jnp.int32))
            return (state, SampleState(next_tok, active, fed2, s.plen,
                                       s.maxfed, out_buf, rng)), ()

        (state, sample), _ = jax.lax.scan(body, (state, sample), None,
                                          length=n_steps)
        return state, sample

    return decode_loop


# -------------------------------------------------------------- decode
def make_serve_step(cfg: ModelConfig, shape: ShapeConfig,
                    unroll: bool = False):
    """Returns fn(params, DecodeState, batch) -> (logits, DecodeState).

    One new token per sequence against a cache of ``shape.seq_len``.
    """

    def serve_step(params, state: DecodeState, batch):
        tokens = batch["tokens"]            # (B, 1)
        active = batch.get("active")
        if active is None:
            active = jnp.ones((tokens.shape[0],), jnp.int32)
        act = active.astype(jnp.bool_)
        h = T.embed_tokens(params, tokens, cfg)
        cache, clen = state.cache, state.cache_len

        if cfg.family in ("dense", "vlm", "moe"):
            def body(carry, xs):
                x = carry
                lp, ck, cv = xs
                x, (ck, cv) = L.decode_attention(
                    lp["attn"], x, cfg, cache_k=ck, cache_v=cv,
                    cache_len=clen, active=act)
                if cfg.family == "moe":
                    from repro.models import moe as moe_lib
                    x, _ = moe_lib.moe_block(lp["moe"], x, cfg)
                else:
                    x = L.swiglu_block(lp["mlp"], x, cfg)
                return x, (ck, cv)
            h, (ks, vs) = _scan(
                body, h, (params["layers"], cache["k"], cache["v"]),
                unroll=unroll)
            new_cache = {"k": ks, "v": vs}
        elif cfg.family == "enc_dec":
            dt = jnp.dtype(cfg.compute_dtype)
            def body(carry, xs):
                x = carry
                lp, ck, cv, xk, xv = xs
                x, (ck, cv) = L.decode_attention(
                    lp["self_attn"], x, cfg, cache_k=ck, cache_v=cv,
                    cache_len=clen, active=act)
                ca = lp["cross_attn"]
                hn = L.rms_norm(x, ca["norm"], cfg.norm_eps).astype(dt)
                q = jnp.einsum("bsd,dhk->bshk", hn, ca["wq"].astype(dt))
                att = L.full_attention(q, xk.astype(dt), xv.astype(dt),
                                       causal=False)
                x = x + jnp.einsum("bshk,hkd->bsd", att,
                                   ca["wo"].astype(dt))
                x = L.swiglu_block(lp["mlp"], x, cfg)
                return x, (ck, cv)
            h, (ks, vs) = _scan(
                body, h, (params["dec_layers"], cache["k"], cache["v"],
                          cache["xk"], cache["xv"]), unroll=unroll)
            new_cache = dict(cache, k=ks, v=vs)
        elif cfg.family == "ssm":
            def body(carry, xs):
                x = carry
                lp, st, cs = xs
                x, (st, cs) = ssm_lib.mamba2_block(
                    lp, x, cfg, ssm_state=st, conv_state=cs, active=act)
                return x, (st, cs)
            h, (ssm, conv) = _scan(
                body, h, (params["layers"], cache["ssm"], cache["conv"]),
                unroll=unroll)
            new_cache = {"ssm": ssm, "conv": conv}
        elif cfg.family == "hybrid":
            shared = params["shared"]
            def period_body(carry, xs):
                x = carry
                pp, st, cs, ck, cv = xs
                def inner(c, ys):
                    lp, s1, c1 = ys
                    c, (s1, c1) = ssm_lib.mamba2_block(
                        lp, c, cfg, ssm_state=s1, conv_state=c1, active=act)
                    return c, (s1, c1)
                x, (st, cs) = _scan(inner, x, (pp, st, cs), unroll=unroll)
                x, (ck, cv) = L.decode_attention(
                    shared["attn"], x, cfg, cache_k=ck, cache_v=cv,
                    cache_len=clen, active=act)
                x = L.swiglu_block(shared["mlp"], x, cfg)
                return x, (st, cs, ck, cv)
            h, (ssm, conv, ks, vs) = _scan(
                period_body, h,
                (params["mamba"], cache["ssm"], cache["conv"],
                 cache["k"], cache["v"]), unroll=unroll)
            new_cache = {"ssm": ssm, "conv": conv, "k": ks, "v": vs}
        else:
            raise ValueError(cfg.family)

        logits = T.lm_logits(params, h, cfg)
        return logits, DecodeState(new_cache, clen + active)

    return serve_step


# ============================================================ paged cache
class PagedDecodeState(NamedTuple):
    """Decode state over a *paged* KV cache (vLLM-style block pool).

    KV leaves are one shared pool ``(L, num_blocks, block_size, KV*D)``
    (``periods`` in place of ``L`` for the hybrid family) instead of
    dense per-lane columns.  Rows are lane-dense: every kv head's D values
    side by side on the minor axis, so the device stores the pool
    row-major with no padding and no layout conversion, and the layer
    scans carry the whole pool and update it in place.  Each lane
    addresses its logical positions through ``block_tables`` (B,
    max_blocks) of physical pool rows.  Unallocated table entries hold
    the sentinel ``num_blocks`` (out of range): gathers clamp it into the
    layer (garbage always masked by kv_len / causality), scatters send it
    past the whole stacked pool and drop it (``mode="drop"``) — so stale
    tables can never corrupt live blocks, in this layer or the next.
    Recurrent leaves (ssm/conv) are O(1) per
    lane and stay lane-indexed.
    """
    cache: Any
    cache_len: jax.Array     # (B,) filled positions
    block_tables: jax.Array  # (B, max_blocks) int32 physical pool rows


def paged_kv_keys(cfg: ModelConfig) -> Tuple[str, ...]:
    """Cache keys stored in the block pool (vs. per-lane recurrent)."""
    if cfg.family in ("dense", "moe"):
        return ("k", "v")
    if cfg.family == "ssm":
        return ()
    if cfg.family == "hybrid":
        return ("k", "v")
    raise ValueError(cfg.family)


def abstract_paged_decode_state(cfg: ModelConfig, shape: ShapeConfig,
                                block_size: int, num_blocks: int):
    """Paged analogue of ``abstract_decode_state``.

    ``shape.global_batch`` is the number of decode *lanes* (concurrent
    slots); pool memory is ``num_blocks`` x ``block_size`` kv columns,
    decoupled from lanes x seq_len — the whole point of paging.  Only
    ``BULK_PREFILL_FAMILIES`` minus enc_dec/vlm are supported (the
    serving engine's admission path).
    """
    B, S = shape.global_batch, shape.seq_len
    assert S % block_size == 0, (S, block_size)
    mb = S // block_size
    bf16 = jnp.bfloat16
    kv_row = (num_blocks, block_size, cfg.num_kv_heads * cfg.head_dim)
    d_inner, nheads, conv_dim, _ = ssm_lib.mamba2_dims(cfg)
    N, P_ = cfg.ssm_state, cfg.ssm_head_dim

    def sds(shp, dt=bf16):
        return jax.ShapeDtypeStruct(shp, dt)

    if cfg.family in ("dense", "moe"):
        cache = {"k": sds((cfg.num_layers,) + kv_row),
                 "v": sds((cfg.num_layers,) + kv_row)}
    elif cfg.family == "ssm":
        cache = {"ssm": sds((cfg.num_layers, B, nheads, P_, N), jnp.float32),
                 "conv": sds((cfg.num_layers, B, cfg.conv_width - 1,
                              conv_dim))}
    elif cfg.family == "hybrid":
        periods = cfg.num_layers // cfg.attn_every
        cache = {"ssm": sds((periods, cfg.attn_every, B, nheads, P_, N),
                            jnp.float32),
                 "conv": sds((periods, cfg.attn_every, B,
                              cfg.conv_width - 1, conv_dim)),
                 "k": sds((periods,) + kv_row),
                 "v": sds((periods,) + kv_row)}
    else:
        raise ValueError(f"paged cache unsupported for {cfg.family}")
    return PagedDecodeState(cache,
                            jax.ShapeDtypeStruct((B,), jnp.int32),
                            jax.ShapeDtypeStruct((B, mb), jnp.int32))


def init_paged_decode_state(cfg: ModelConfig, shape: ShapeConfig,
                            block_size: int,
                            num_blocks: int) -> PagedDecodeState:
    ab = abstract_paged_decode_state(cfg, shape, block_size, num_blocks)
    cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), ab.cache)
    B, mb = ab.block_tables.shape
    return PagedDecodeState(
        cache, jnp.zeros((B,), jnp.int32),
        jnp.full((B, mb), num_blocks, jnp.int32))   # all-sentinel tables


def make_paged_serve_step(cfg: ModelConfig, shape: ShapeConfig,
                          block_size: int, num_blocks: int,
                          unroll: bool = False, impl: str = "auto"):
    """Paged ``make_serve_step``: fn(params, PagedDecodeState, batch) ->
    (logits, PagedDecodeState).  Same sampling-visible math as the dense
    step — the attention core is bit-identical on CPU backends and a
    Pallas paged-attention kernel on TPU.

    The layer scans carry the whole KV pool and scan over layer indices:
    each layer writes its rows in place and the kernel reads them inside
    the stacked pool, so no layer's pool is sliced, stacked or copied.
    """

    def serve_step(params, state: PagedDecodeState, batch):
        tokens = batch["tokens"]            # (B, 1)
        active = batch.get("active")
        if active is None:
            active = jnp.ones((tokens.shape[0],), jnp.int32)
        act = active.astype(jnp.bool_)
        h = T.embed_tokens(params, tokens, cfg)
        cache, clen, bt = state.cache, state.cache_len, state.block_tables

        if cfg.family in ("dense", "moe"):
            def body(carry, xs):
                x, pk, pv = carry
                lp, layer = xs
                x, (pk, pv) = L.paged_decode_attention(
                    lp["attn"], x, cfg, pool_k=pk, pool_v=pv, layer=layer,
                    block_tables=bt, cache_len=clen, active=act, impl=impl)
                if cfg.family == "moe":
                    from repro.models import moe as moe_lib
                    x, _ = moe_lib.moe_block(lp["moe"], x, cfg)
                else:
                    with jax.named_scope("mlp"):
                        x = L.swiglu_block(lp["mlp"], x, cfg)
                return (x, pk, pv), ()
            (h, ks, vs), _ = _scan(
                body, (h, cache["k"], cache["v"]),
                (params["layers"], jnp.arange(cfg.num_layers)),
                unroll=unroll)
            new_cache = {"k": ks, "v": vs}
        elif cfg.family == "ssm":
            def body(carry, xs):
                x = carry
                lp, st, cs = xs
                x, (st, cs) = ssm_lib.mamba2_block(
                    lp, x, cfg, ssm_state=st, conv_state=cs, active=act)
                return x, (st, cs)
            h, (ssm, conv) = _scan(
                body, h, (params["layers"], cache["ssm"], cache["conv"]),
                unroll=unroll)
            new_cache = {"ssm": ssm, "conv": conv}
        elif cfg.family == "hybrid":
            shared = params["shared"]
            def period_body(carry, xs):
                x, pk, pv = carry
                pp, st, cs, period = xs
                def inner(c, ys):
                    lp, s1, c1 = ys
                    c, (s1, c1) = ssm_lib.mamba2_block(
                        lp, c, cfg, ssm_state=s1, conv_state=c1, active=act)
                    return c, (s1, c1)
                x, (st, cs) = _scan(inner, x, (pp, st, cs), unroll=unroll)
                x, (pk, pv) = L.paged_decode_attention(
                    shared["attn"], x, cfg, pool_k=pk, pool_v=pv,
                    layer=period, block_tables=bt, cache_len=clen,
                    active=act, impl=impl)
                with jax.named_scope("mlp"):
                    x = L.swiglu_block(shared["mlp"], x, cfg)
                return (x, pk, pv), (st, cs)
            (h, ks, vs), (ssm, conv) = _scan(
                period_body, (h, cache["k"], cache["v"]),
                (params["mamba"], cache["ssm"], cache["conv"],
                 jnp.arange(cache["k"].shape[0])), unroll=unroll)
            new_cache = {"ssm": ssm, "conv": conv, "k": ks, "v": vs}
        else:
            raise ValueError(cfg.family)

        with jax.named_scope("logits"):
            logits = T.lm_logits(params, h, cfg)
        return logits, PagedDecodeState(new_cache, clen + active, bt)

    return serve_step


def make_paged_decode_loop(cfg: ModelConfig, shape: ShapeConfig,
                           n_steps: int, block_size: int, num_blocks: int,
                           temperature: float = 0.0,
                           eos_token: Optional[int] = None,
                           impl: str = "auto"):
    """``make_decode_loop`` over a paged cache — shares the exact
    sampling/bookkeeping body, so token streams match dense decode
    bit-for-bit."""
    step = make_paged_serve_step(cfg, shape, block_size, num_blocks,
                                 impl=impl)
    return make_decode_loop(cfg, shape, n_steps, temperature=temperature,
                            eos_token=eos_token, serve_step=step)


def make_paged_bulk_prefill(cfg: ModelConfig, shape: ShapeConfig,
                            chunk: int, block_size: int, num_blocks: int,
                            first_chunk: bool = False):
    """State-continued chunk prefill into one slot of a paged cache.

    Returns ``fn(params, state, tokens, slot, off, n_real) ->
    PagedDecodeState``: prefills a ``(1, chunk)`` token buffer whose
    first token sits at absolute position ``off`` of slot ``slot``.
    Attention kv lands in the slot's blocks through its table (a
    block-table append); attention reads causally over history + chunk
    (prefill-with-history).  The layer scan carries the whole pool and
    each layer scatters its rows in place, as in the decode step.
    Recurrent (ssm/conv) leaves continue from the slot's carried state —
    zeros when ``off == 0`` — via the SSD ``init_state`` threading, which
    is exactly equivalent to one long prefill over the concatenated
    chunks.  Sets
    ``cache_len[slot] = off + n_real``.

    ``slot``/``off``/``n_real`` are traced: one compiled function per
    (cfg, shape, chunk bucket, block geometry) covers every slot,
    chunk index, and real length.  ``first_chunk=True`` specializes the
    compiled function for ``off == 0`` (fresh admission, the hot case
    under churn): the kv attention skips the history gather — every
    gathered position would be masked — and recurrent leaves start from
    literal zeros instead of a gather-and-select.
    """
    assert cfg.family in BULK_PREFILL_FAMILIES, cfg.family
    mb = shape.seq_len // block_size

    def paged_prefill(params, state: PagedDecodeState, tokens, slot, off,
                      n_real):
        def take_lane(leaf, ax):
            return jax.lax.dynamic_slice_in_dim(leaf, slot, 1, axis=ax)

        cache, bt = state.cache, state.block_tables
        bt_row = jax.lax.dynamic_slice(bt, (slot, 0), (1, mb))[0]
        first = off == 0
        h = T.embed_tokens(params, tokens, cfg)
        new_cache = dict(cache)

        if cfg.family in ("dense", "moe"):
            def body(carry, xs):
                x, pk, pv = carry
                lp, layer = xs
                x, pk, pv = L.paged_chunk_attention(
                    lp["attn"], x, cfg, pool_k=pk, pool_v=pv, layer=layer,
                    bt_row=bt_row, off=off, history=not first_chunk)
                if cfg.family == "moe":
                    from repro.models import moe as moe_lib
                    x, _ = moe_lib.moe_block(lp["moe"], x, cfg)
                else:
                    with jax.named_scope("mlp"):
                        x = L.swiglu_block(lp["mlp"], x, cfg)
                return (x, pk, pv), ()
            (_, ks, vs), _ = jax.lax.scan(
                body, (h, cache["k"], cache["v"]),
                (params["layers"], jnp.arange(cfg.num_layers)))
            new_cache = {"k": ks, "v": vs}
        elif cfg.family == "ssm":
            if first_chunk:
                ssm0 = jnp.zeros_like(take_lane(cache["ssm"], 1))
                conv0 = jnp.zeros_like(take_lane(cache["conv"], 1))
            else:
                ssm0 = jnp.where(first, 0.0, take_lane(cache["ssm"], 1))
                conv0 = jnp.where(first, 0, take_lane(cache["conv"], 1))
            def body(carry, xs):
                x = carry
                lp, s0, c0 = xs
                x, (st, cv) = ssm_lib.mamba2_block(
                    lp, x, cfg, init_ssm=s0, init_conv=c0)
                return x, (st, cv)
            _, (ssm, conv) = jax.lax.scan(
                body, h, (params["layers"], ssm0, conv0))
            new_cache = {
                "ssm": jax.lax.dynamic_update_slice(
                    cache["ssm"], ssm.astype(cache["ssm"].dtype),
                    (0, slot, 0, 0, 0)),
                "conv": jax.lax.dynamic_update_slice(
                    cache["conv"], conv.astype(cache["conv"].dtype),
                    (0, slot, 0, 0))}
        elif cfg.family == "hybrid":
            if first_chunk:
                ssm0 = jnp.zeros_like(take_lane(cache["ssm"], 2))
                conv0 = jnp.zeros_like(take_lane(cache["conv"], 2))
            else:
                ssm0 = jnp.where(first, 0.0, take_lane(cache["ssm"], 2))
                conv0 = jnp.where(first, 0, take_lane(cache["conv"], 2))
            shared = params["shared"]
            def period_body(carry, xs):
                x, pk, pv = carry
                pp, s0, c0, period = xs
                def inner(c, ys):
                    lp, s1, c1 = ys
                    c, (st, cv) = ssm_lib.mamba2_block(
                        lp, c, cfg, init_ssm=s1, init_conv=c1)
                    return c, (st, cv)
                x, (sts, cvs) = jax.lax.scan(inner, x, (pp, s0, c0))
                x, pk, pv = L.paged_chunk_attention(
                    shared["attn"], x, cfg, pool_k=pk, pool_v=pv,
                    layer=period, bt_row=bt_row, off=off,
                    history=not first_chunk)
                with jax.named_scope("mlp"):
                    x = L.swiglu_block(shared["mlp"], x, cfg)
                return (x, pk, pv), (sts, cvs)
            (_, ks, vs), (ssm, conv) = jax.lax.scan(
                period_body, (h, cache["k"], cache["v"]),
                (params["mamba"], ssm0, conv0,
                 jnp.arange(cache["k"].shape[0])))
            new_cache = {
                "ssm": jax.lax.dynamic_update_slice(
                    cache["ssm"], ssm.astype(cache["ssm"].dtype),
                    (0, 0, slot, 0, 0, 0)),
                "conv": jax.lax.dynamic_update_slice(
                    cache["conv"], conv.astype(cache["conv"].dtype),
                    (0, 0, slot, 0, 0)),
                "k": ks, "v": vs}
        else:
            raise ValueError(cfg.family)

        cache_len = state.cache_len.at[slot].set(
            jnp.asarray(off + n_real, jnp.int32))
        return PagedDecodeState(new_cache, cache_len, bt)

    return paged_prefill
