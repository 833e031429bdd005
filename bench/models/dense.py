"""A dense Granite/Llama decoder, as the harness sees a model.

The interface every ``bench/models/<model>.py`` gives, bound here to
``weights.py`` (seeded weights), ``reference.py`` (the float32
reference and its fp8 control) and ``flops.py`` (operations and
bytes).  A configuration file selects this module with
``"model": "dense"``.
"""
import flops
import reference
import weights

# the program's ``jax.named_scope`` names on this model's paged path
# (models/layers.py, models/model_zoo.py): readers split device time by
# the innermost of them in each instruction's ``op_name``
SCOPES = ("qkv", "kv_write", "attention", "attn_out", "mlp", "logits",
          "sample")


def dims(cfg_file: dict) -> weights.Dims:
    """The frozen sizes the weights, the reference and the counts use."""
    return weights.Dims.from_config(cfg_file)


def program_config(cfg_file: dict):
    """The program's ModelConfig for a configuration file, checked
    against the file's sizes so that the file is what runs."""
    from repro.configs import get_config
    prog = cfg_file["program"]
    cfg = get_config(prog["arch"]).with_(**prog["overrides"])
    want = {"num_layers": cfg_file["num_hidden_layers"],
            "d_model": cfg_file["hidden_size"],
            "num_heads": cfg_file["num_attention_heads"],
            "num_kv_heads": cfg_file["num_key_value_heads"],
            "d_ff": cfg_file["intermediate_size"],
            "vocab_size": cfg_file["vocab_size"],
            "tie_embeddings": cfg_file["tie_word_embeddings"],
            "rope_theta": cfg_file["rope_theta"],
            "norm_eps": cfg_file["rms_norm_eps"],
            "param_dtype": cfg_file["deployment"]["dtype"]}
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        raise SystemExit(f"program config {got} differs from file {want}")
    return cfg


# seeded bfloat16 parameters on the device, made in one jitted call
program_params = weights.program_params
# served_gap(dims, seed, prompt, served, control=False): the widest gap
# below the float32 reference's best; ``control``: the fp8 control's
served_gap = reference.served_gap
prefill_flops = flops.prefill_flops
decode_flops = flops.decode_flops
paged_attention_cost = flops.paged_attention_cost


def attention_layers(m: weights.Dims) -> int:
    """Paged-attention kernel calls per fused step: every layer."""
    return m.layers
