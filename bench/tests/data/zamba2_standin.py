"""A stand-in model module for the harness's CPU tests: the program's
own zamba2-2.7b (Mamba-2 layers, one shared attention block after every
``attn_every`` of them) at its ``reduced()`` size, served through the
paged engine.

It gives the interface of ``bench/models/<model>.py`` from files under
``bench/tests/`` alone.  Its weights are the program's
``model_zoo.init_params`` from the seed, and ``served_gap`` judges the
served tokens against the program's own unpaged forward in float32: a
check that the harness carries a model of another family end to end,
not a reference.  Its control rounds every weight to float8.  Counts
take two operations per weight outside the embedding for each token,
the head for each decoded token, and attention in each shared block.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

BF16 = 2
BUCKET = 128
# the program's named scopes on the hybrid paged path: those of the
# shared attention block, its MLP, the head and the sampling body
SCOPES = ("qkv", "kv_write", "attention", "attn_out", "mlp", "logits",
          "sample")


@dataclasses.dataclass(frozen=True)
class Dims:
    cfg: object                     # the program's ModelConfig
    vocab: int


def program_config(cfg_file: dict):
    from repro.configs import get_config
    prog = cfg_file["program"]
    cfg = get_config(prog["arch"])
    if prog.get("reduced"):
        cfg = cfg.reduced()
    cfg = cfg.with_(**prog["overrides"])
    want = {"family": cfg_file["family"],
            "num_layers": cfg_file["num_hidden_layers"],
            "d_model": cfg_file["hidden_size"],
            "attn_every": cfg_file["attn_every"],
            "vocab_size": cfg_file["vocab_size"],
            "param_dtype": cfg_file["deployment"]["dtype"]}
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        raise SystemExit(f"program config {got} differs from file {want}")
    return cfg


def dims(cfg_file: dict) -> Dims:
    return Dims(program_config(cfg_file), cfg_file["vocab_size"])


def program_params(m: Dims, seed: int):
    from repro.models import model_zoo
    return model_zoo.init_params(m.cfg, jax.random.PRNGKey(seed % (1 << 31)))


@functools.partial(jax.jit, static_argnums=(0,))
def _logits(cfg, params, tokens):
    from repro.models import transformer as T
    h, _ = T.decoder_forward(params, tokens[None], cfg)
    return T.lm_logits(params, h, cfg)[0]


def _fp8(params):
    return jax.tree.map(
        lambda w: w.astype(jnp.float8_e4m3fn).astype(w.dtype)
        if jnp.issubdtype(w.dtype, jnp.floating) else w, params)


def served_gap(m: Dims, seed: int, prompt, served,
               control: bool = False) -> float:
    """Widest gap, over the served tokens, by which a served token's
    logit in the program's unpaged float32 forward lies below that
    forward's best (``control``: the tokens the float8 weights put
    first at the same positions)."""
    cfg = m.cfg.with_(compute_dtype="float32")
    params = program_params(m, seed)
    seq = np.concatenate([np.asarray(prompt), np.asarray(served)])
    toks = np.zeros(-(-len(seq) // BUCKET) * BUCKET, np.int32)
    toks[:len(seq)] = seq
    ref = np.asarray(_logits(cfg, params, jnp.asarray(toks)))[:, :m.vocab]
    lo, n = len(prompt) - 1, len(served)
    if control:
        choice = np.asarray(jnp.argmax(
            _logits(cfg, _fp8(params), jnp.asarray(toks))[:, :m.vocab], -1))
    else:
        if not all(0 <= t < m.vocab for t in served):
            return float("inf")
        choice = np.zeros(len(toks), np.int64)
        choice[lo:lo + n] = served
    rows = np.arange(lo, lo + n)
    gaps = ref[rows].max(-1) - ref[rows, choice[lo:lo + n]]
    return float(gaps.max()) if np.isfinite(gaps).all() else float("inf")


def attention_layers(m: Dims) -> int:
    """Paged-attention calls per fused step: one per shared block."""
    return m.cfg.num_layers // m.cfg.attn_every


def _per_token(m: Dims) -> float:
    from repro.models import model_zoo
    c = m.cfg
    return 2.0 * (model_zoo.num_params(c) - c.padded_vocab * c.d_model)


def _attention(m: Dims, ctx: float) -> float:
    c = m.cfg
    return 4.0 * attention_layers(m) * c.num_heads * c.head_dim * ctx


def prefill_flops(m: Dims, n: int) -> float:
    return n * _per_token(m) + _attention(m, n * (n + 1) / 2.0)


def decode_flops(m: Dims, ctx_lens) -> float:
    ctx_lens = list(ctx_lens)
    head = 2.0 * m.cfg.d_model * m.vocab
    return len(ctx_lens) * (_per_token(m) + head) + _attention(
        m, sum(ctx_lens))


def paged_attention_cost(m: Dims, ctx_lens):
    c, ctx_lens = m.cfg, list(ctx_lens)
    kv = sum(ctx_lens)
    flops = 4.0 * c.num_heads * c.head_dim * kv
    nbytes = (2.0 * kv * c.num_kv_heads * c.head_dim * BF16
              + 2.0 * len(ctx_lens) * c.num_heads * c.head_dim * BF16)
    return flops, nbytes
