"""GShard/Switch-style top-k MoE with capacity-bounded scatter dispatch.

Dispatch is sort-free: for each of the k routing choices we compute the
token's position-in-expert with a cumulative sum over the one-hot expert
assignment, drop tokens past ``capacity``, and scatter token activations into
a per-expert buffer of shape (E, C, d).  Expert FFNs then run as one batched
einsum with the expert dim sharded over the 'model' mesh axis (EP); GSPMD
materializes the token redistribution as all-to-all / collective traffic,
which the roofline analysis measures.

qwen2-moe's 60 experts do not divide the 16-way model axis; the sharding
rules fall back to sharding each expert's d_ff (see launch/sharding.py), so
the layer keeps a TP dimension without uneven input shardings.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.launch.sharding import constrain
from repro.models import layers as L
from repro.models.schema import Spec


def moe_schema(cfg: ModelConfig, stacked=None, prefix="layers"):
    st = (stacked,) if stacked is not None else ()
    sa = (prefix,) if stacked is not None else ()
    d, f, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    sch = {
        "norm": Spec(st + (d,), sa + (None,), "ones"),
        "router": Spec(st + (d, E), sa + ("embed", None)),
        "we_gate": Spec(st + (E, d, f), sa + ("experts", "embed", "expert_ff")),
        "we_up": Spec(st + (E, d, f), sa + ("experts", "embed", "expert_ff")),
        "we_down": Spec(st + (E, f, d), sa + ("experts", "expert_ff", "embed")),
    }
    if cfg.num_shared_experts:
        fs = cfg.num_shared_experts * f
        sch.update({
            "ws_gate": Spec(st + (d, fs), sa + ("embed", "ff")),
            "ws_up": Spec(st + (d, fs), sa + ("embed", "ff")),
            "ws_down": Spec(st + (fs, d), sa + ("ff", "embed")),
        })
    return sch


def expert_capacity(cfg: ModelConfig, num_tokens: int) -> int:
    cap = int(cfg.capacity_factor * num_tokens * cfg.top_k / cfg.num_experts)
    return max(8, min(cap, num_tokens))


def route(router_logits, cfg: ModelConfig):
    """top-k routing. router_logits: (T, E) fp32.

    Returns (expert_idx (T,k), weights (T,k), aux_loss scalar).
    """
    probs = jax.nn.softmax(router_logits, axis=-1)
    weights, expert_idx = jax.lax.top_k(probs, cfg.top_k)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balancing auxiliary loss
    E = cfg.num_experts
    density = jnp.mean(
        jax.nn.one_hot(expert_idx[:, 0], E, dtype=jnp.float32), axis=0)
    density_proxy = jnp.mean(probs, axis=0)
    aux = jnp.sum(density * density_proxy) * (E ** 2) / E
    return expert_idx, weights, aux * cfg.router_aux_weight


def moe_block(p, x, cfg: ModelConfig):
    """x: (B, S, d) -> (B, S, d), aux_loss.  Dispatches to the best
    available implementation:

    1. ``_moe_explicit_ep`` — partial-manual shard_map over the 'model'
       axis: activations are already replicated over 'model', so each
       expert shard gathers its own experts' tokens LOCALLY and the only
       communication is one psum of the combined output per layer.
       Requires an active mesh with E % model_size == 0.
    2. ``_moe_grouped`` — pure-pjit sort-based grouped dispatch (GShard
       capacity sharding).  Fallback for CPU smoke tests and for archs
       whose expert count does not divide the model axis (qwen2's 60).

    The O(kT*E) one-hot/cumsum form is kept as ``moe_block_onehot`` (the
    paper-era baseline; see EXPERIMENTS.md §Perf for the measured ladder).
    """
    from repro.launch.sharding import active_rules
    if cfg.moe_impl == "onehot":
        return moe_block_onehot(p, x, cfg)
    rules = active_rules()
    E = cfg.num_experts
    if cfg.moe_impl != "grouped" and rules is not None \
            and "model" in rules.axes:
        msize = rules.mesh.shape["model"]
        if msize > 1 and E % msize == 0:
            return _moe_explicit_ep(p, x, cfg, rules, msize)
    return _moe_grouped(p, x, cfg)


def _routing_tables(p, ht, cfg: ModelConfig, G: int, Tg: int):
    """Shared routing math: slot->token / slot->weight tables per group.

    ht: (G, Tg, d).  Returns (tok_of_slot, w_of_slot) with shape
    (G, E*C) plus (aux, C)."""
    E, k = cfg.num_experts, cfg.top_k
    C = expert_capacity(cfg, Tg)
    kTg = k * Tg
    router_logits = jnp.einsum(
        "gtd,de->gte", ht.astype(jnp.float32),
        p["router"].astype(jnp.float32))
    expert_idx, weights, aux = route(router_logits.reshape(G * Tg, E), cfg)
    expert_idx = expert_idx.reshape(G, Tg, k)
    weights = weights.reshape(G, Tg, k)

    flat_e = jnp.swapaxes(expert_idx, 1, 2).reshape(G, kTg)
    flat_tok = jnp.tile(jnp.arange(Tg, dtype=jnp.int32), (G, k))
    flat_w = jnp.swapaxes(weights, 1, 2).reshape(G, kTg)

    order = jnp.argsort(flat_e, axis=1, stable=True)
    sorted_e = jnp.take_along_axis(flat_e, order, axis=1)
    counts = jax.vmap(lambda fe: jnp.bincount(fe, length=E))(flat_e)
    starts = jnp.cumsum(counts, axis=1) - counts
    rank = (jnp.arange(kTg, dtype=jnp.int32)[None]
            - jnp.take_along_axis(starts, sorted_e, axis=1))
    keep = rank < C
    slot = sorted_e * C + jnp.clip(rank, 0, C - 1)
    slot_or_oob = jnp.where(keep, slot, E * C)

    gidx = jnp.arange(G)[:, None]
    tok_of_slot = jnp.full((G, E * C + 1), Tg, jnp.int32).at[
        gidx, slot_or_oob].set(jnp.take_along_axis(flat_tok, order, axis=1),
                               mode="drop")[:, :E * C]
    w_of_slot = jnp.zeros((G, E * C + 1), jnp.float32).at[
        gidx, slot_or_oob].set(jnp.take_along_axis(flat_w, order, axis=1),
                               mode="drop")[:, :E * C]
    return tok_of_slot, w_of_slot, aux, C


def _moe_explicit_ep(p, x, cfg: ModelConfig, rules, msize: int):
    """Explicit expert parallelism: FULLY manual shard_map.

    Batch is sharded over the non-'model' axes and replicated over 'model';
    expert weights are sharded over 'model'.  Each device routes its local
    tokens, gathers its own experts' tokens locally (zero-communication
    dispatch), runs the expert FFNs, and the ONLY collective is one f32
    psum of the combined output over 'model' per layer.  (Fully-manual
    shard_map avoids two XLA-CPU partial-manual/all-reduce-promotion
    compiler bugs hit along the way — see EXPERIMENTS.md §Perf.)
    """
    from jax.sharding import PartitionSpec as P
    dt = jnp.dtype(cfg.compute_dtype)
    b, s, d = x.shape
    E, k = cfg.num_experts, cfg.top_k
    E_loc = E // msize
    mesh = rules.mesh
    batch_axes = tuple(a for a in mesh.axis_names if a != "model")
    dp = 1
    for a in batch_axes:
        dp *= mesh.shape[a]
    if b % dp != 0:
        return _moe_grouped(p, x, cfg)

    h = L.rms_norm(x, p["norm"], cfg.norm_eps).astype(dt)

    def body(ht, router_w, we_gate, we_up, we_down):
        # ht: LOCAL (b/dp, s, d) f32 (f32 boundary: AD's psum of a bf16
        # cotangent crashes XLA-CPU's AllReducePromotion pass)
        ht = ht.astype(dt)
        b_loc = ht.shape[0]
        T_loc = b_loc * s
        m = jax.lax.axis_index("model")
        htg = ht.reshape(1, T_loc, d)
        tok_of_slot, w_of_slot, aux, C = _routing_tables(
            {"router": router_w}, htg, cfg, 1, T_loc)
        # slice this shard's experts' slots: dispatch is fully local
        tok_local = jax.lax.dynamic_slice_in_dim(
            tok_of_slot.reshape(E, C), m * E_loc, E_loc, axis=0)
        w_local = jax.lax.dynamic_slice_in_dim(
            w_of_slot.reshape(E, C), m * E_loc, E_loc, axis=0)
        tok_local = tok_local.reshape(E_loc * C)
        # local dispatch gather (pad row = dropped/empty slots)
        ht_pad = jnp.concatenate(
            [ht.reshape(T_loc, d), jnp.zeros((1, d), dt)], axis=0)
        buf = ht_pad[tok_local].reshape(E_loc, C, d)
        # local expert FFNs
        g = jnp.einsum("ecd,edf->ecf", buf, we_gate.astype(dt))
        u = jnp.einsum("ecd,edf->ecf", buf, we_up.astype(dt))
        out_buf = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u,
                             we_down.astype(dt))
        # local combine (f32 partial sums) + THE one collective
        out_flat = out_buf.reshape(E_loc * C, d).astype(jnp.float32) * \
            w_local.reshape(E_loc * C, 1)
        partial = jnp.zeros((T_loc, d), jnp.float32).at[tok_local].add(
            out_flat, mode="drop")
        out = jax.lax.psum(partial, "model").astype(dt)
        if batch_axes:
            aux = jax.lax.pmean(aux, batch_axes)
        return out.reshape(b_loc, s, d), aux

    wspec = P("model", None, None)
    sm = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(batch_axes, None, None), P(), wspec, wspec, wspec),
        out_specs=(P(batch_axes, None, None), P()),
        check_vma=False)
    out, aux = sm(h.astype(jnp.float32), p["router"], p["we_gate"],
                  p["we_up"], p["we_down"])
    out = out.astype(dt)

    if cfg.num_shared_experts:
        gs = jnp.einsum("bsd,df->bsf", h, p["ws_gate"].astype(dt))
        us = jnp.einsum("bsd,df->bsf", h, p["ws_up"].astype(dt))
        out = out + jnp.einsum("bsf,fd->bsd", jax.nn.silu(gs) * us,
                               p["ws_down"].astype(dt))
    return x + constrain(out, "batch", None, "embed"), aux


def _moe_grouped(p, x, cfg: ModelConfig):
    """Pure-pjit sort-based grouped dispatch (GShard capacity sharding)."""
    dt = jnp.dtype(cfg.compute_dtype)
    b, s, d = x.shape
    T = b * s
    E, k = cfg.num_experts, cfg.top_k
    G = cfg.moe_groups if T % max(cfg.moe_groups, 1) == 0 else 1
    Tg = T // G
    C = expert_capacity(cfg, Tg)    # per-group capacity (GShard sharding)
    kTg = k * Tg

    h = L.rms_norm(x, p["norm"], cfg.norm_eps).astype(dt)
    ht = h.reshape(G, Tg, d)
    ht = constrain(ht, "batch", None, "embed")
    router_logits = jnp.einsum(
        "gtd,de->gte", ht.astype(jnp.float32),
        p["router"].astype(jnp.float32))
    expert_idx, weights, aux = route(router_logits.reshape(G * Tg, E), cfg)
    expert_idx = expert_idx.reshape(G, Tg, k)
    weights = weights.reshape(G, Tg, k)

    # choice-major flattening per group: first choices precede second
    # choices, so the stable sort preserves Switch-style drop priority.
    flat_e = jnp.swapaxes(expert_idx, 1, 2).reshape(G, kTg)    # (G, kTg)
    flat_tok = jnp.tile(jnp.arange(Tg, dtype=jnp.int32), (G, k))
    flat_w = jnp.swapaxes(weights, 1, 2).reshape(G, kTg)

    order = jnp.argsort(flat_e, axis=1, stable=True)           # (G, kTg)
    sorted_e = jnp.take_along_axis(flat_e, order, axis=1)
    counts = jax.vmap(lambda fe: jnp.bincount(fe, length=E))(flat_e)
    starts = jnp.cumsum(counts, axis=1) - counts               # (G, E)
    rank = (jnp.arange(kTg, dtype=jnp.int32)[None]
            - jnp.take_along_axis(starts, sorted_e, axis=1))
    keep = rank < C                                            # capacity drop
    slot = sorted_e * C + jnp.clip(rank, 0, C - 1)             # (G, kTg)
    slot_or_oob = jnp.where(keep, slot, E * C)                 # OOB -> drop

    # slot -> (token, weight) per group; empty slots hit a zero pad row
    gidx = jnp.arange(G)[:, None]
    tok_of_slot = jnp.full((G, E * C + 1), Tg, jnp.int32).at[
        gidx, slot_or_oob].set(jnp.take_along_axis(flat_tok, order, axis=1),
                               mode="drop")[:, :E * C]
    w_of_slot = jnp.zeros((G, E * C + 1), jnp.float32).at[
        gidx, slot_or_oob].set(jnp.take_along_axis(flat_w, order, axis=1),
                               mode="drop")[:, :E * C]
    tok_of_slot = constrain(tok_of_slot, "batch", None)
    w_of_slot = constrain(w_of_slot, "batch", None)

    # dispatch: a group-local batched gather, then reshard (G,data)x(E,model)
    # -> the MoE all-to-all
    ht_pad = jnp.concatenate([ht, jnp.zeros((G, 1, d), dt)], axis=1)
    buf = jnp.take_along_axis(
        ht_pad, tok_of_slot[:, :, None], axis=1)               # (G, E*C, d)
    buf = buf.reshape(G, E, C, d)
    buf = constrain(buf, "batch", "experts", None, "embed")

    # expert FFNs as batched einsums (EP over 'model')
    g = jnp.einsum("gecd,edf->gecf", buf, p["we_gate"].astype(dt))
    u = jnp.einsum("gecd,edf->gecf", buf, p["we_up"].astype(dt))
    act = constrain(jax.nn.silu(g) * u, "batch", "experts", None,
                    "expert_ff")
    out_buf = jnp.einsum("gecf,efd->gecd", act, p["we_down"].astype(dt))
    out_buf = constrain(out_buf, "batch", "experts", None, "embed")

    # combine: all-to-all back, then a group-local weighted scatter-add.
    # f32 scatter: partial-sum all-reduces of bf16 crash XLA-CPU's
    # AllReducePromotion pass (and f32 is better combine numerics anyway)
    out_flat = out_buf.reshape(G, E * C, d).astype(jnp.float32) * \
        w_of_slot[:, :, None]
    out_flat = constrain(out_flat, "batch", None, "embed")
    # batched scatter-add with a d-wide window; empty slots carry tok=Tg
    # (out of bounds) and are dropped
    combined = jnp.zeros((G, Tg, d), jnp.float32).at[gidx, tok_of_slot].add(
        out_flat, mode="drop").astype(dt)
    out = combined.reshape(b, s, d)

    if cfg.num_shared_experts:
        gs = jnp.einsum("bsd,df->bsf", h, p["ws_gate"].astype(dt))
        us = jnp.einsum("bsd,df->bsf", h, p["ws_up"].astype(dt))
        out = out + jnp.einsum("bsf,fd->bsd", jax.nn.silu(gs) * us,
                               p["ws_down"].astype(dt))
    return x + constrain(out, "batch", None, "embed"), aux


def moe_block_onehot(p, x, cfg: ModelConfig):
    """Paper-era one-hot/cumsum dispatch (GShard formulation).

    Kept as the §Perf baseline and as a second oracle for the sort-based
    path; O(kT*E) dispatch temporaries."""
    dt = jnp.dtype(cfg.compute_dtype)
    b, s, d = x.shape
    T = b * s
    E, k = cfg.num_experts, cfg.top_k
    C = expert_capacity(cfg, T)

    h = L.rms_norm(x, p["norm"], cfg.norm_eps).astype(dt)
    ht = h.reshape(T, d)
    router_logits = jnp.einsum(
        "td,de->te", ht.astype(jnp.float32), p["router"].astype(jnp.float32))
    expert_idx, weights, aux = route(router_logits, cfg)

    flat_e = expert_idx.T.reshape(-1)              # (k*T,) choice-major
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)       # (kT, E)
    pos = jnp.cumsum(onehot, axis=0) - 1                       # (kT, E)
    pos_in_e = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
    keep = pos_in_e < C

    tok_idx = jnp.tile(jnp.arange(T), k)
    buf = jnp.zeros((E, C, d), dt)
    src = ht[tok_idx] * keep[:, None].astype(dt)
    buf = buf.at[flat_e, jnp.clip(pos_in_e, 0, C - 1)].add(
        src, mode="drop")
    buf = constrain(buf, "experts", None, "embed")

    g = jnp.einsum("ecd,edf->ecf", buf, p["we_gate"].astype(dt))
    u = jnp.einsum("ecd,edf->ecf", buf, p["we_up"].astype(dt))
    act = constrain(jax.nn.silu(g) * u, "experts", None, "expert_ff")
    out_buf = jnp.einsum("ecf,efd->ecd", act, p["we_down"].astype(dt))
    out_buf = constrain(out_buf, "experts", None, "embed")

    flat_w = weights.T.reshape(-1).astype(dt) * keep.astype(dt)
    gathered = out_buf[flat_e, jnp.clip(pos_in_e, 0, C - 1)]   # (kT, d)
    combined = jnp.zeros((T, d), dt).at[tok_idx].add(
        gathered * flat_w[:, None])
    out = combined.reshape(b, s, d)

    if cfg.num_shared_experts:
        gs = jnp.einsum("bsd,df->bsf", h, p["ws_gate"].astype(dt))
        us = jnp.einsum("bsd,df->bsf", h, p["ws_up"].astype(dt))
        out = out + jnp.einsum("bsf,fd->bsd", jax.nn.silu(gs) * us,
                               p["ws_down"].astype(dt))
    return x + constrain(out, "batch", None, "embed"), aux
