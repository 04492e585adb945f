"""Architecture adapter: GPT-2-shaped decoders (Cerebras-GPT).

The one place that knows how a published GPT-2 ``config.json`` maps onto the
system's ``GPTForCausalLM``, which leaves it has, and what its work costs.
The counting functions are the yardstick: operations and bytes the algorithm
needs, from shapes alone, whatever implements it.
"""
from __future__ import annotations

REFERENCE = "bench.reference.gpt2_block"
# leaves that pack several projections along their last axis
FUSED = {"attn.qkv_proj.weight": 3, "attn.qkv_proj.bias": 3}


def n_layers(cfg):
    return cfg["n_layer"]


def layer_prefix(i):
    return f"transformer.h.{i}."


def top_specs(cfg):
    h, std = cfg["n_embd"], cfg["initializer_range"]
    return [("transformer.wte.weight", (cfg["vocab_size"], h), ("normal", std)),
            ("transformer.wpe.weight", (cfg["n_positions"], h), ("normal", std)),
            ("transformer.ln_f.weight", (h,), ("near_one", 0.05)),
            ("transformer.ln_f.bias", (h,), ("normal", std))]


def layer_specs(cfg):
    h, inner, std = cfg["n_embd"], cfg["n_inner"], cfg["initializer_range"]
    n, o = ("normal", std), ("near_one", 0.05)
    return [("ln_1.weight", (h,), o), ("ln_1.bias", (h,), n),
            ("attn.qkv_proj.weight", (h, 3 * h), n),
            ("attn.qkv_proj.bias", (3 * h,), n),
            ("attn.out_proj.weight", (h, h), n), ("attn.out_proj.bias", (h,), n),
            ("ln_2.weight", (h,), o), ("ln_2.bias", (h,), n),
            ("mlp.fc_in.weight", (h, inner), n), ("mlp.fc_in.bias", (inner,), n),
            ("mlp.fc_out.weight", (inner, h), n), ("mlp.fc_out.bias", (h,), n)]


def build(cfg):
    """The system's model for this configuration (parameters in the default
    dtype the caller has set)."""
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    if cfg["activation_function"] != "gelu" or not cfg["tie_word_embeddings"]:
        raise ValueError("the GPT-2 adapter runs erf GELU with a tied head")
    return GPTForCausalLM(GPTConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["n_embd"],
        intermediate_size=cfg["n_inner"], num_hidden_layers=cfg["n_layer"],
        num_attention_heads=cfg["n_head"],
        max_position_embeddings=cfg["n_positions"],
        layer_norm_epsilon=cfg["layer_norm_epsilon"],
        tie_word_embeddings=True))


def blocks(model):
    return list(model.transformer.h)


def loss(model, ids, labels):
    return model.compute_loss(model(ids), labels)


# -- what the work costs, from shapes ---------------------------------------
def block_matmul_params(cfg):
    h, inner = cfg["n_embd"], cfg["n_inner"]
    return cfg["n_layer"] * (4 * h * h + 2 * h * inner)


def head_params(cfg):
    return cfg["n_embd"] * cfg["vocab_size"]


def n_params(cfg):
    h, inner = cfg["n_embd"], cfg["n_inner"]
    per_layer = 4 * h * h + 2 * h * inner + 9 * h + inner + 4 * h
    return (cfg["n_layer"] * per_layer + cfg["vocab_size"] * h
            + cfg["n_positions"] * h + 2 * h)


def attention_flops(cfg, context):
    """QK^T and PV of ONE query token over ``context`` keys, all layers."""
    return 4.0 * context * cfg["n_embd"] * cfg["n_layer"]


def kv_bytes_per_token(cfg, itemsize=2):
    return 2 * cfg["n_layer"] * cfg["n_embd"] * itemsize


def attention_geometry(cfg):
    return {"layers": cfg["n_layer"], "heads": cfg["n_head"],
            "head_dim": cfg["n_embd"] // cfg["n_head"]}
