"""Architecture adapter: Ouro, the looped decoder (Ouro-2.6B).

The mapping of the published ``config.json`` onto the system's
``OuroForCausalLM``, the leaves, the walk and the cost of the work;
``gpt2.py``'s docstring has the interface. ``num_hidden_layers`` layers hold
weights; a forward pass visits each of them ``total_ut_steps`` times, with a
stop on the model's own leaves (the norm, the gate) at every pass's end, and
the counts are per token over that whole walk.
"""
from __future__ import annotations

REFERENCE = "bench.reference.ouro_block"


def n_layers(cfg):
    return cfg["num_hidden_layers"]


def layer_prefix(i):
    return f"model.layers.{i}."


def _dims(cfg):
    h = cfg["hidden_size"]
    return h, cfg["num_key_value_heads"] * cfg["head_dim"], \
        cfg["intermediate_size"]


def _visits(cfg):
    """Layer visits of one forward pass: every layer once a pass."""
    return cfg["total_ut_steps"] * cfg["num_hidden_layers"]


def top_specs(cfg):
    h, std = cfg["hidden_size"], cfg["initializer_range"]
    return [("model.embed_tokens.weight", (cfg["vocab_size"], h), ("normal", std)),
            ("model.norm.weight", (h,), ("near_one", 0.05)),
            ("model.early_exit_gate.weight", (h, 1), ("normal", std)),
            ("model.early_exit_gate.bias", (1,), ("normal", std)),
            ("lm_head.weight", (h, cfg["vocab_size"]), ("normal", std))]


def layer_specs(cfg, i):
    """Every layer alike: seven matrices, four norms."""
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
            ("input_layernorm_2.weight", (h,), o),
            ("post_attention_layernorm.weight", (h,), o),
            ("post_attention_layernorm_2.weight", (h,), o)]


def walk(cfg):
    """``total_ut_steps`` passes: every block in order, then the pass's end
    (the model's norm and gate, leaves outside the layers)."""
    one_pass = [("block", i) for i in range(cfg["num_hidden_layers"])] \
        + [("pass_end", None)]
    return one_pass * cfg["total_ut_steps"]


def build(cfg):
    from paddle_tpu.models.ouro import OuroConfig, OuroForCausalLM
    if cfg["hidden_act"] != "silu" or cfg["tie_word_embeddings"] \
            or cfg["sliding_window"] is not None or cfg["use_sliding_window"] \
            or cfg["rope_scaling"] is not None \
            or cfg["head_dim"] * cfg["num_attention_heads"] != cfg["hidden_size"]:
        raise ValueError("the Ouro adapter runs SwiGLU, an untied head, full "
                         "attention, plain rope and heads that fill the width")
    return OuroForCausalLM(OuroConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        tie_word_embeddings=False, total_ut_steps=cfg["total_ut_steps"],
        early_exit_threshold=cfg["early_exit_threshold"]))


def blocks(model):
    return list(model.model.layers)


def loss(model, ids, labels):
    return model.forward_loss(ids, labels, loss_chunk_size=256)


# -- what the work costs, from shapes: per token, over the whole walk --------
def _layer_matmul_params(cfg):
    h, kvw, inter = _dims(cfg)
    return 2 * h * h + 2 * h * kvw + 3 * h * inter


def block_matmul_params(cfg):
    return _visits(cfg) * _layer_matmul_params(cfg)


def head_params(cfg):
    return cfg["hidden_size"] * cfg["vocab_size"]


def n_params(cfg):
    """Stored parameters: every layer once."""
    h = cfg["hidden_size"]
    return (cfg["num_hidden_layers"] * (_layer_matmul_params(cfg) + 4 * h)
            + 2 * cfg["vocab_size"] * h + 2 * h + 1)


def attention_flops(cfg, context):
    """QK^T and PV of ONE query token over ``context`` keys, every visit."""
    return 4.0 * context * cfg["hidden_size"] * _visits(cfg)


def kv_bytes_per_token(cfg, itemsize=2):
    _, kvw, _ = _dims(cfg)
    return 2 * _visits(cfg) * kvw * itemsize


def attention_geometry(cfg):
    return {"layers": _visits(cfg), "heads": cfg["num_attention_heads"],
            "head_dim": cfg["head_dim"]}
