"""Architecture adapter: Nemotron-H, the hybrid decoder of
NVIDIA-Nemotron-3-Super-120B-A12B, as ONE CHIP's share of a deployment in
which four chips share each layer.

The mapping of the published ``config.json`` onto the system's
``NemotronHForCausalLM``, the leaves, the walk and the cost of the work;
``gpt2.py``'s docstring has the interface. The first ``num_hidden_layers``
letters of ``hybrid_override_pattern`` name the layers that hold weights:
``M`` a Mamba-2 mixer, ``*`` an attention layer, ``E`` an expert layer in a
latent width; each is one stop of the walk, and a forward pass visits each
once. The configuration's ``n_routed_experts`` counts the routed experts HELD
here (ids ``first_expert ..``) and ``vocab_size`` the rows of the vocabulary
held here; the router keeps its published width
(``published.n_routed_experts``) and its ``num_experts_per_tok`` a token.

The counts are what one token passes through and keeps HERE over the walk:
the Mamba layers' and the attention layer's projections whole, the expert
layers' router, latent projections and shared expert whole, the routed
experts at the share of a token's choices that falls on a held expert when
routing is even, K and V for the attention layers alone; and, new with this
architecture, what a SEQUENCE keeps whatever its length
(``state_bytes_per_sequence``) and what the recurrence costs a row
(``scan_flops_per_row``).
"""
from __future__ import annotations

REFERENCE = "bench.reference.nemotron_h_block"
STOPS = {"M": "mamba", "*": "attention", "E": "moe"}


def pattern(cfg):
    return cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]


def n_layers(cfg):
    return cfg["num_hidden_layers"]


def layer_prefix(i):
    return f"backbone.layers.{i}."


def router_width(cfg):
    return cfg["published"]["n_routed_experts"]


def d_inner(cfg):
    return cfg["mamba_num_heads"] * cfg["mamba_head_dim"]


def conv_dim(cfg):
    return d_inner(cfg) + 2 * cfg["n_groups"] * cfg["ssm_state_size"]


def top_specs(cfg):
    h, std = cfg["hidden_size"], cfg["initializer_range"]
    return [("backbone.embeddings.weight", (cfg["vocab_size"], h),
             ("normal", std)),
            ("backbone.norm_f.weight", (h,), ("near_one", 0.05)),
            ("lm_head.weight", (cfg["vocab_size"], h), ("normal", std))]


def layer_specs(cfg, i):
    """A layer's norm and its one part's leaves, by the pattern's letter."""
    h = cfg["hidden_size"]
    n, o = ("normal", cfg["initializer_range"]), ("near_one", 0.05)
    norm = [("norm.weight", (h,), o)]
    kind = pattern(cfg)[i]
    if kind == "M":
        heads, d, c = cfg["mamba_num_heads"], d_inner(cfg), conv_dim(cfg)
        return norm + [
            ("mixer.A_log", (heads,), ("near_one", cfg["a_log_std"])),
            ("mixer.D", (heads,), ("near_one", cfg["d_std"])),
            ("mixer.dt_bias", (heads,), ("normal", cfg["dt_bias_std"])),
            ("mixer.in_proj.weight", (h, d + c + heads), n),
            ("mixer.conv1d.weight", (cfg["conv_kernel"], c),
             ("normal", cfg["conv_init_std"])),
            ("mixer.conv1d.bias", (c,), ("normal", cfg["conv_init_std"])),
            ("mixer.norm.weight", (d,), o),
            ("mixer.out_proj.weight", (d, h), n)]
    if kind == "*":
        qw = cfg["num_attention_heads"] * cfg["head_dim"]
        kvw = cfg["num_key_value_heads"] * cfg["head_dim"]
        return norm + [("mixer.q_proj.weight", (h, qw), n),
                       ("mixer.k_proj.weight", (h, kvw), n),
                       ("mixer.v_proj.weight", (h, kvw), n),
                       ("mixer.o_proj.weight", (qw, h), n)]
    held, lat = cfg["n_routed_experts"], cfg["moe_latent_size"]
    width, shared = cfg["moe_intermediate_size"], \
        cfg["moe_shared_expert_intermediate_size"]
    return norm + [
        ("mixer.gate.weight", (h, router_width(cfg)),
         ("normal", cfg["router_init_std"])),
        # 1 + std * normal: the choice is blind to the bias's common part,
        # the weight is not
        ("mixer.gate.e_score_correction_bias", (router_width(cfg),),
         ("near_one", cfg["router_bias_std"])),
        ("mixer.fc1_latent_proj.weight", (h, lat), n),
        ("mixer.fc2_latent_proj.weight", (lat, h), n),
        ("mixer.experts.up_proj", (held, lat, width), n),
        ("mixer.experts.down_proj", (held, width, lat), n),
        ("mixer.shared_experts.up_proj.weight", (h, shared), n),
        ("mixer.shared_experts.down_proj.weight", (shared, h), n)]


def walk(cfg):
    """Every layer once, in order, by its kind."""
    return [(STOPS[kind], i) for i, kind in enumerate(pattern(cfg))]


def build(cfg):
    from paddle_tpu.models.nemotron_h import (NemotronHConfig,
                                              NemotronHForCausalLM)
    if cfg["mlp_hidden_act"] != "relu2" or cfg["mamba_hidden_act"] != "silu" \
            or cfg["n_group"] != 1 or cfg["n_shared_experts"] != 1 \
            or cfg["mamba_proj_bias"] or cfg["attention_bias"] \
            or not cfg["use_conv_bias"]:
        raise ValueError("the Nemotron-H adapter runs relu2 experts, a silu "
                         "convolution with bias, one shared expert, one "
                         "routing group and no projection bias")
    return NemotronHForCausalLM(NemotronHConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        hybrid_override_pattern=cfg["hybrid_override_pattern"],
        layer_norm_epsilon=cfg["layer_norm_epsilon"],
        max_position_embeddings=cfg["max_position_embeddings"],
        mamba_num_heads=cfg["mamba_num_heads"],
        mamba_head_dim=cfg["mamba_head_dim"],
        ssm_state_size=cfg["ssm_state_size"], n_groups=cfg["n_groups"],
        conv_kernel=cfg["conv_kernel"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        n_routed_experts=router_width(cfg),
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_latent_size=cfg["moe_latent_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        moe_shared_expert_intermediate_size=cfg[
            "moe_shared_expert_intermediate_size"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        norm_topk_prob=cfg["norm_topk_prob"],
        experts_held=cfg["n_routed_experts"],
        first_expert=cfg["first_expert"]))


def blocks(model):
    return list(model.backbone.layers)


def loss(model, ids, labels):
    raise NotImplementedError("no training cell: 12 bytes a parameter of "
                              "this share do not fit one chip, and the "
                              "recurrence has no tested backward pass")


# -- what the work costs, from shapes: per token, over the whole walk --------
def kinds(cfg):
    p = pattern(cfg)
    return {k: p.count(k) for k in "M*E"}


def mamba_params(cfg):
    """The two projections of one Mamba layer."""
    h, d = cfg["hidden_size"], d_inner(cfg)
    return h * (d + conv_dim(cfg) + cfg["mamba_num_heads"]) + d * h


def attention_params(cfg):
    h = cfg["hidden_size"]
    qw = cfg["num_attention_heads"] * cfg["head_dim"]
    kvw = cfg["num_key_value_heads"] * cfg["head_dim"]
    return 2 * h * qw + 2 * h * kvw


def moe_dense_params(cfg):
    """An expert layer outside its routed experts: router, both latent
    projections, the shared expert."""
    h = cfg["hidden_size"]
    return h * router_width(cfg) + 2 * h * cfg["moe_latent_size"] \
        + 2 * h * cfg["moe_shared_expert_intermediate_size"]


def dense_params(cfg):
    """Every matrix a token passes through whatever its routing."""
    k = kinds(cfg)
    return k["M"] * mamba_params(cfg) + k["*"] * attention_params(cfg) \
        + k["E"] * moe_dense_params(cfg)


def expert_params(cfg):
    """One routed expert: two matrices in the latent width."""
    return 2 * cfg["moe_latent_size"] * cfg["moe_intermediate_size"]


def held_share(cfg):
    """The share of a token's choices that falls on a held expert when the
    routing is even."""
    return cfg["n_routed_experts"] / router_width(cfg)


def block_matmul_params(cfg):
    return dense_params(cfg) + kinds(cfg)["E"] * cfg["num_experts_per_tok"] \
        * held_share(cfg) * expert_params(cfg)


def head_params(cfg):
    return cfg["hidden_size"] * cfg["vocab_size"]


def n_params(cfg):
    """Stored parameters of this share."""
    k, h = kinds(cfg), cfg["hidden_size"]
    c, heads = conv_dim(cfg), cfg["mamba_num_heads"]
    small = k["M"] * ((cfg["conv_kernel"] + 1) * c + 3 * heads + d_inner(cfg)) \
        + k["E"] * router_width(cfg) + (n_layers(cfg) + 1) * h
    return dense_params(cfg) + small \
        + k["E"] * cfg["n_routed_experts"] * expert_params(cfg) \
        + 2 * cfg["vocab_size"] * h


def attention_flops(cfg, context):
    """ONE query token over ``context`` cached tokens in every attention
    layer: scores and weighted sum for each query head, 2 operations a
    multiply-add."""
    return 4.0 * context * cfg["num_attention_heads"] * cfg["head_dim"] \
        * kinds(cfg)["*"]


def kv_bytes_per_token(cfg, itemsize=2):
    """K and V of the attention layers; a Mamba layer keeps nothing a
    token."""
    return 2 * kinds(cfg)["*"] * cfg["num_key_value_heads"] \
        * cfg["head_dim"] * itemsize


def attention_geometry(cfg):
    return {"layers": kinds(cfg)["*"], "heads": cfg["num_attention_heads"],
            "head_dim": cfg["head_dim"]}


def state_bytes_per_sequence(cfg, tails=True, itemsize=2):
    """What a sequence keeps beside its pages, whatever its length: in each
    Mamba layer the recurrent state, heads x head size x state size in
    float32, and with ``tails`` the convolution's last ``conv_kernel - 1``
    inputs in the activations' type."""
    state = cfg["mamba_num_heads"] * cfg["mamba_head_dim"] \
        * cfg["ssm_state_size"] * 4
    tail = (cfg["conv_kernel"] - 1) * conv_dim(cfg) * itemsize
    return kinds(cfg)["M"] * (state + (tail if tails else 0))


def scan_flops_per_row(cfg):
    """The recurrence on one row in every Mamba layer: for each element of
    each head's state the decay, the outer product's multiply-add and the
    read-out's multiply-add, and ``dt x`` once a channel."""
    per = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    return kinds(cfg)["M"] * (5.0 * per * cfg["ssm_state_size"] + per)


def scan_row_bytes(cfg, itemsize=2):
    """What the recurrence reads and writes a row in every Mamba layer
    beside the state: x, B and C in, dt in float32, y out in float32."""
    per = d_inner(cfg)
    return kinds(cfg)["M"] * (
        (per + 2 * cfg["n_groups"] * cfg["ssm_state_size"]) * itemsize
        + 4 * cfg["mamba_num_heads"] + 4 * per)
