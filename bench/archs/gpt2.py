"""Architecture adapter: GPT-2-shaped decoders (Cerebras-GPT).

The one place that knows how a published GPT-2 ``config.json`` maps onto the
system's ``GPTForCausalLM``, which leaves it has, in what order a forward
pass visits its layers, and what its work costs.

The adapter's interface (``bench/archs/<architecture>.py``; every adapter has
every name, the harness asks for none of them with a default). ``cfg`` is the
configuration's file as a dict.

``REFERENCE``
    Module name of the plain reference. It has ``embed(top, ids, cfg)``,
    ``head(top, x, cfg, q)`` and one function for every step name of the
    walk, each ``step(top, lw, x, cfg, q) -> x``: ``top`` the leaves outside
    the layers, ``lw`` the visited layer's leaves (``None`` at a stop between
    layers), ``x`` one sequence's ``[width, hidden]`` float32 array, ``q``
    the control's rounding of matmul operands or ``None``.
``n_layers(cfg)``
    How many layers HOLD WEIGHTS (not how many stops the walk makes).
``layer_prefix(i)``
    What the system's state dict puts before layer ``i``'s leaf names.
``top_specs(cfg)``, ``layer_specs(cfg, i)``
    The leaves outside the layers, and the leaves of layer ``i``, as
    ``(name, shape, init)`` (``lib/weights.py``). Layers may differ: layers
    that return equal lists share one compiled weight program.
``walk(cfg)``
    The forward pass between ``embed`` and ``head`` as a sequence of stops
    ``(step name, layer index or None)``, in order. The serving reference
    makes layer ``i``'s leaves again at each visit and calls the reference
    module's function of that name; a layer may be visited several times,
    and a stop with ``None`` works on ``top`` alone (a norm between passes).
    The training reference follows a walk of ``"block"`` stops that visits
    every layer once, and refuses any other.
``build(cfg)``
    The system's model (parameters in the default dtype the caller has set).
``blocks(model)``, ``loss(model, ids, labels)``
    Its rematerialised layers and its training loss: read by the training
    kind alone.
``block_matmul_params``, ``head_params``, ``attention_flops``,
``kv_bytes_per_token``, ``attention_geometry``
    The yardstick: operations and bytes the algorithm needs, from shapes
    alone, whatever implements it. They count what ONE TOKEN PASSES THROUGH
    AND KEEPS on the whole walk: a layer visited ``T`` times counts ``T``
    times in matmul parameters (so in operations and, a serving step, in
    weight bytes: the visits depend on each other and the weights do not
    stay on the chip between them), in attention operations and in cache
    bytes, and ``attention_geometry``'s ``layers`` is the attention calls of
    one forward pass. ``n_params`` alone counts stored parameters.
``FUSED`` (optional)
    Leaves that pack several projections along their last axis.
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


def layer_specs(cfg, i):
    """Every layer alike."""
    h, inner, std = cfg["n_embd"], cfg["n_inner"], cfg["initializer_range"]
    n, o = ("normal", std), ("near_one", 0.05)
    return [("ln_1.weight", (h,), o), ("ln_1.bias", (h,), n),
            ("attn.qkv_proj.weight", (h, 3 * h), n),
            ("attn.qkv_proj.bias", (3 * h,), n),
            ("attn.out_proj.weight", (h, h), n), ("attn.out_proj.bias", (h,), n),
            ("ln_2.weight", (h,), o), ("ln_2.bias", (h,), n),
            ("mlp.fc_in.weight", (h, inner), n), ("mlp.fc_in.bias", (inner,), n),
            ("mlp.fc_out.weight", (inner, h), n), ("mlp.fc_out.bias", (h,), n)]


def walk(cfg):
    """Every block once, in order."""
    return [("block", i) for i in range(cfg["n_layer"])]


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


# -- what the work costs, from shapes: per token, over the whole walk --------
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
    """QK^T and PV of ONE query token over ``context`` keys, every visit."""
    return 4.0 * context * cfg["n_embd"] * cfg["n_layer"]


def kv_bytes_per_token(cfg, itemsize=2):
    return 2 * cfg["n_layer"] * cfg["n_embd"] * itemsize


def attention_geometry(cfg):
    return {"layers": cfg["n_layer"], "heads": cfg["n_head"],
            "head_dim": cfg["n_embd"] // cfg["n_head"]}
