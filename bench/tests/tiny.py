"""A tiny configuration and mixes for CPU dry runs of the harness."""

import time

import core
import serve_cell


def cfg_file(tied: bool) -> dict:
    return {
        "model": "dense",
        "num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "intermediate_size": 128,
        "vocab_size": 256, "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
        "tie_word_embeddings": tied, "embedding_multiplier": 12.0,
        "attention_multiplier": 0.015625, "residual_multiplier": 0.22,
        "logits_scaling": 8.0,
        "program": {"arch": "granite-8b", "overrides": {
            "num_layers": 2, "d_model": 64, "num_heads": 4,
            "num_kv_heads": 2, "head_dim": 16, "d_ff": 128,
            "vocab_size": 256, "param_dtype": "bfloat16",
            "tie_embeddings": tied}},
        "deployment": {"dtype": "bfloat16", "lanes": 4, "max_seq": 384,
                       "block_size": 16, "kv_pool_blocks": 64}}


MIXES = {
    "batch": {"kind": "offline", "backlog": 64,
              "prompt": {"median": 60, "sigma": 0.8, "min": 4, "max": 280},
              "output": {"median": 12, "sigma": 0.9, "min": 2, "max": 60},
              "round": 4, "check": {"served_tokens": 40, "min_lanes": 3,
                                    "max_requests": 6}},
}
PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
LIMIT = {"max_logit_gap": 0.05}


def run(kind: str, seed: int = 3_000_000_011, seconds: float = 3.0,
        cfg: dict = None, limits: dict = LIMIT, **kw):
    """One run of the tiny cell ``kind``, of the dense tiny configuration
    or of ``cfg``; returns serve_cell.run's triple and the counter."""
    cell = {"name": f"tiny.{kind}", "config": "tiny", "traffic": kind,
            "chips": 1}
    counter = core.CompileCounter()
    cfg = cfg or cfg_file(kind == "batch")
    model = core.load_model(cfg, f"configuration of {cell['name']}")
    return serve_cell.run(cell, cfg, model, seed,
                          seconds, time.perf_counter(), counter,
                          mix=MIXES[kind], limits=limits, peak=PEAK,
                          **kw) + (counter,)
