"""Architecture adapter: Llama-shaped decoders (Mistral-7B).

The mapping of a published ``config.json`` onto the system's
``LlamaForCausalLM``, the leaves, the walk and the cost of the work;
``gpt2.py``'s docstring has the interface.
"""
from __future__ import annotations

REFERENCE = "bench.reference.llama_block"


def n_layers(cfg):
    return cfg["num_hidden_layers"]


def layer_prefix(i):
    return f"model.layers.{i}."


def _dims(cfg):
    h = cfg["hidden_size"]
    d = h // cfg["num_attention_heads"]
    return h, cfg["num_key_value_heads"] * d, cfg["intermediate_size"]


def top_specs(cfg):
    h, std = cfg["hidden_size"], cfg["initializer_range"]
    return [("model.embed_tokens.weight", (cfg["vocab_size"], h), ("normal", std)),
            ("model.norm.weight", (h,), ("near_one", 0.05)),
            ("lm_head.weight", (h, cfg["vocab_size"]), ("normal", std))]


def layer_specs(cfg, i):
    """Every layer alike."""
    h, kvw, inter = _dims(cfg)
    n, o = ("normal", cfg["initializer_range"]), ("near_one", 0.05)
    return [("self_attn.q_proj.weight", (h, h), n),
            ("self_attn.k_proj.weight", (h, kvw), n),
            ("self_attn.v_proj.weight", (h, kvw), n),
            ("self_attn.o_proj.weight", (h, h), n),
            ("mlp.gate_proj.weight", (h, inter), n),
            ("mlp.up_proj.weight", (h, inter), n),
            ("mlp.down_proj.weight", (inter, h), n),
            ("input_layernorm.weight", (h,), o),
            ("post_attention_layernorm.weight", (h,), o)]


def walk(cfg):
    """Every block once, in order."""
    return [("block", i) for i in range(cfg["num_hidden_layers"])]


def build(cfg):
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    if cfg["hidden_act"] != "silu" or cfg["tie_word_embeddings"] \
            or cfg.get("sliding_window") is not None:
        raise ValueError("the Llama adapter runs SwiGLU, an untied head and "
                         "no sliding window")
    return LlamaForCausalLM(LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        tie_word_embeddings=False))


def blocks(model):
    return list(model.model.layers)


def loss(model, ids, labels):
    return model.forward_loss(ids, labels, loss_chunk_size=256)


def block_matmul_params(cfg):
    h, kvw, inter = _dims(cfg)
    return cfg["num_hidden_layers"] * (2 * h * h + 2 * h * kvw + 3 * h * inter)


def head_params(cfg):
    return cfg["hidden_size"] * cfg["vocab_size"]


def n_params(cfg):
    h = cfg["hidden_size"]
    return (block_matmul_params(cfg) + cfg["num_hidden_layers"] * 2 * h
            + 2 * cfg["vocab_size"] * h + h)


def attention_flops(cfg, context):
    return 4.0 * context * cfg["hidden_size"] * cfg["num_hidden_layers"]


def kv_bytes_per_token(cfg, itemsize=2):
    _, kvw, _ = _dims(cfg)
    return 2 * cfg["num_hidden_layers"] * kvw * itemsize


def attention_geometry(cfg):
    """Query heads: the system repeats K/V to them before the kernel, and the
    products the mathematics needs are per query head either way."""
    return {"layers": cfg["num_hidden_layers"],
            "heads": cfg["num_attention_heads"],
            "head_dim": cfg["hidden_size"] // cfg["num_attention_heads"]}
