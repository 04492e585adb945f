"""Architecture adapter: LongCat-Flash, the language model of
LongCat-Flash-Omni, as ONE CHIP's share of an expert-parallel deployment.

The mapping of the published ``config.json`` onto the system's
``LongcatFlashForCausalLM``, the leaves, the walk and the cost of the work;
``gpt2.py``'s docstring has the interface. ``num_layers`` DOUBLE layers hold
weights (two latent-attention blocks, two dense FFNs, one expert layer each)
and a forward pass visits each once. The configuration's ``n_routed_experts``
counts the routed experts HELD here (ids ``first_expert ..``); the router
keeps its published width, ``published.n_routed_experts + zero_expert_num``
outputs, and its ``moe_topk`` a token.

The counts are what one token passes through and keeps HERE over the walk:
the dense path whole (both attention blocks, both FFNs, the router), the
routed experts at the share of a token's ``moe_topk`` choices that falls on
a held expert when routing is even (``moe_topk * held / router width``
expert FFNs a token and layer), the zero experts at nothing, and one latent
row (``kv_lora_rank + qk_rope_head_dim`` numbers) for each of the ``2 *
num_layers`` attention blocks.
"""
from __future__ import annotations

REFERENCE = "bench.reference.longcat_flash_block"

MLA = ("q_a_proj.weight", "q_a_layernorm.weight", "q_b_proj.weight",
       "kv_a_proj_with_mqa.weight", "kv_a_layernorm.weight",
       "kv_b_proj.weight", "o_proj.weight")


def n_layers(cfg):
    return cfg["num_layers"]


def layer_prefix(i):
    return f"model.layers.{i}."


def router_width(cfg):
    return cfg["published"]["n_routed_experts"] + cfg["zero_expert_num"]


def top_specs(cfg):
    h, std = cfg["hidden_size"], cfg["initializer_range"]
    return [("model.embed_tokens.weight", (cfg["vocab_size"], h), ("normal", std)),
            ("model.norm.weight", (h,), ("near_one", 0.05)),
            ("lm_head.weight", (cfg["vocab_size"], h), ("normal", std))]


def _mla_shapes(cfg):
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    ql, kl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, v = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    return {"q_a_proj.weight": (h, ql), "q_a_layernorm.weight": (ql,),
            "q_b_proj.weight": (ql, heads * (nope + rope)),
            "kv_a_proj_with_mqa.weight": (h, kl + rope),
            "kv_a_layernorm.weight": (kl,),
            "kv_b_proj.weight": (kl, heads * (nope + v)),
            "o_proj.weight": (heads * v, h)}


def layer_specs(cfg, i):
    """Every double layer alike: two attention blocks of five matrices and
    two norms, two FFNs of three, four norms, the router and its bias, three
    banks of the held experts."""
    h, inter = cfg["hidden_size"], cfg["ffn_hidden_size"]
    held, width = cfg["n_routed_experts"], cfg["expert_ffn_hidden_size"]
    n, o = ("normal", cfg["initializer_range"]), ("near_one", 0.05)
    out = []
    for j in (0, 1):
        out += [(f"self_attn.{j}.{name}", shape,
                 o if name.endswith("layernorm.weight") else n)
                for name, shape in _mla_shapes(cfg).items()]
        out += [(f"mlps.{j}.gate_proj.weight", (h, inter), n),
                (f"mlps.{j}.up_proj.weight", (h, inter), n),
                (f"mlps.{j}.down_proj.weight", (inter, h), n),
                (f"input_layernorm.{j}.weight", (h,), o),
                (f"post_attention_layernorm.{j}.weight", (h,), o)]
    return out + [
        ("mlp.router.classifier.weight", (h, router_width(cfg)),
         ("normal", cfg["router_init_std"])),
        # 1 + std * normal: the choice is blind to the bias's common part,
        # the weight is not, so a bias used in the weight is a wrong logit
        ("mlp.router.e_score_correction_bias", (router_width(cfg),),
         ("near_one", cfg["router_bias_std"])),
        ("mlp.experts.gate_proj", (held, h, width), n),
        ("mlp.experts.up_proj", (held, h, width), n),
        ("mlp.experts.down_proj", (held, width, h), n)]


def walk(cfg):
    """Every double layer once, in order: the shortcut leaves and joins
    inside a stop."""
    return [("block", i) for i in range(cfg["num_layers"])]


def build(cfg):
    from paddle_tpu.models.longcat_flash import (LongcatFlashConfig,
                                                 LongcatFlashForCausalLM)
    if cfg["attention_bias"] or cfg["attention_method"] != "MLA" \
            or cfg["zero_expert_type"] != "identity":
        raise ValueError("the LongCat-Flash adapter runs MLA without biases "
                         "and identity zero experts")
    return LongcatFlashForCausalLM(LongcatFlashConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        ffn_hidden_size=cfg["ffn_hidden_size"],
        expert_ffn_hidden_size=cfg["expert_ffn_hidden_size"],
        num_layers=cfg["num_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        kv_lora_rank=cfg["kv_lora_rank"], q_lora_rank=cfg["q_lora_rank"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        mla_scale_q_lora=cfg["mla_scale_q_lora"],
        mla_scale_kv_lora=cfg["mla_scale_kv_lora"],
        n_routed_experts=cfg["published"]["n_routed_experts"],
        zero_expert_num=cfg["zero_expert_num"], moe_topk=cfg["moe_topk"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        experts_held=cfg["n_routed_experts"],
        first_expert=cfg["first_expert"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"]))


def blocks(model):
    return list(model.model.layers)


def loss(model, ids, labels):
    raise NotImplementedError("no training cell: 16 bytes a parameter of "
                              "this share do not fit one chip")


# -- what the work costs, from shapes: per token, over the whole walk --------
def mla_params(cfg):
    """The five matrices of one attention block."""
    return sum(a * b for a, b in (s for s in _mla_shapes(cfg).values()
                                  if len(s) == 2))


def dense_layer_params(cfg):
    """A double layer outside its experts: two attention blocks, two FFNs,
    the router."""
    h = cfg["hidden_size"]
    return 2 * mla_params(cfg) + 2 * 3 * h * cfg["ffn_hidden_size"] \
        + h * router_width(cfg)


def expert_params(cfg):
    """One routed expert: three matrices."""
    return 3 * cfg["hidden_size"] * cfg["expert_ffn_hidden_size"]


def held_share(cfg):
    """The share of a token's choices that falls on a held expert when the
    routing is even."""
    return cfg["n_routed_experts"] / router_width(cfg)


def zero_share(cfg):
    return cfg["zero_expert_num"] / router_width(cfg)


def block_matmul_params(cfg):
    return cfg["num_layers"] * (
        dense_layer_params(cfg)
        + cfg["moe_topk"] * held_share(cfg) * expert_params(cfg))


def head_params(cfg):
    return cfg["hidden_size"] * cfg["vocab_size"]


def n_params(cfg):
    """Stored parameters of this share."""
    h = cfg["hidden_size"]
    norms = 4 * h + 2 * (cfg["q_lora_rank"] + cfg["kv_lora_rank"])
    return (cfg["num_layers"] * (
        dense_layer_params(cfg) + router_width(cfg) + norms
        + cfg["n_routed_experts"] * expert_params(cfg))
        + 2 * cfg["vocab_size"] * h + h)


def latent_row(cfg):
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def attention_flops(cfg, context):
    """Absorbed latent attention of ONE query token over ``context`` cached
    rows, every block: each head's score over the row (latent and roped key)
    and its weighted sum of latents, 2 operations a multiply-add."""
    return 2.0 * cfg["num_attention_heads"] * context \
        * (latent_row(cfg) + cfg["kv_lora_rank"]) * 2 * cfg["num_layers"]


def kv_bytes_per_token(cfg, itemsize=2):
    """One latent row for each attention block, no V."""
    return 2 * cfg["num_layers"] * latent_row(cfg) * itemsize


def attention_geometry(cfg):
    return {"layers": 2 * cfg["num_layers"],
            "heads": cfg["num_attention_heads"],
            "head_dim": cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            "latent_row": latent_row(cfg), "value_row": cfg["kv_lora_rank"]}
