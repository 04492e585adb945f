"""LongCat-Flash: the language model of LongCat-Flash-Omni (Meituan,
huggingface.co/meituan-longcat/LongCat-Flash-Omni; LongCat-Flash Technical
Report, arXiv:2509.01322, and the ``longcat_flash`` modelling code published
with it).

A DOUBLE layer holds two multi-head latent attention (MLA) blocks, two dense
SwiGLU FFNs and one expert layer whose output skips over the layer's second
half (shortcut-connected MoE)::

    for i in (0, 1):
        a   = x + MLA_i(RMSNorm(x))
        h_i = RMSNorm(a)
        if i == 0:  m = MoE(h_0)          # leaves after the FIRST half
        x   = a + FFN_i(h_i)
    x = x + m                             # joins after the SECOND half

MLA caches 576 numbers a token and block: the normed, rescaled latent ``c``
(``kv_lora_rank``) and the roped key ``k_rope`` all heads share. Both low-rank
paths are rescaled (``s_q = sqrt(hidden / q_lora_rank)`` on the query,
``s_kv = sqrt(hidden / kv_lora_rank)`` on the latent); rope turns neighbouring
pairs; scores are scaled by ``(qk_nope_head_dim + qk_rope_head_dim) ** -0.5``.
The expert layer routes over ``n_routed_experts + zero_expert_num`` outputs:
softmax scores in float32, the ``moe_topk`` largest of score + bias chosen,
each weighted by ``routed_scaling_factor`` times its unbiased score (no
renormalisation); a zero expert returns its input. One chip of an
expert-parallel deployment holds ``experts_held`` routed experts, a contiguous
range of ids from ``first_expert``: the sum over routed experts runs over
those, and what the others would add is left out. The zero experts hold no
weights and are all here.

The functions below are the model's mathematics over plain arrays;
``forward`` runs them unabsorbed and with every held expert on every token
(weights of nought where not chosen). Serving and ``generate()`` go through
``generation._LongcatDecoder`` (absorbed attention over the latent cache,
routed dispatch), which ``_decoder_for`` picks by this class.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .. import nn
from ..distributed.fleet.meta_parallel import VocabParallelEmbedding
from ..ops.dispatch import dispatch
from ..tensor import Tensor
from .llama import build_rope_cache

NEG_INF = -1e30


@dataclass
class LongcatFlashConfig:
    vocab_size: int = 131072
    hidden_size: int = 6144
    ffn_hidden_size: int = 12288
    expert_ffn_hidden_size: int = 2048
    num_layers: int = 28
    num_attention_heads: int = 64
    kv_lora_rank: int = 512
    q_lora_rank: int = 1536
    qk_rope_head_dim: int = 64
    qk_nope_head_dim: int = 128
    v_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    n_routed_experts: int = 512        # the router's routed outputs
    zero_expert_num: int = 256
    moe_topk: int = 12
    routed_scaling_factor: float = 6.0
    experts_held: int = 512            # routed experts this chip holds
    first_expert: int = 0              # id of the first of them
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e7

    @property
    def num_hidden_layers(self):       # what the decoders call it
        return self.num_layers

    @property
    def router_width(self):
        return self.n_routed_experts + self.zero_expert_num

    @property
    def q_scale(self):
        return (self.hidden_size / self.q_lora_rank) ** 0.5 \
            if self.mla_scale_q_lora else 1.0

    @property
    def kv_scale(self):
        return (self.hidden_size / self.kv_lora_rank) ** 0.5 \
            if self.mla_scale_kv_lora else 1.0

    @property
    def attn_scale(self):
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5

    @staticmethod
    def tiny(vocab_size=256, layers=2, experts_held=16, first_expert=0,
             seq=128):
        """Widths in the published ratios (hidden : q rank : kv rank 12 : 3
        : 1, heads of 2 : 1 : 2 nope, rope, value; two thirds of the
        router's outputs routed)."""
        return LongcatFlashConfig(
            vocab_size=vocab_size, hidden_size=96, ffn_hidden_size=192,
            expert_ffn_hidden_size=32, num_layers=layers,
            num_attention_heads=4, kv_lora_rank=8, q_lora_rank=24,
            qk_rope_head_dim=4, qk_nope_head_dim=8, v_head_dim=8,
            n_routed_experts=16, zero_expert_num=8, moe_topk=3,
            experts_held=experts_held, first_expert=first_expert,
            max_position_embeddings=seq, rope_theta=10000.0)


# -- the mathematics, over plain arrays ---------------------------------------
def rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(ms + eps) * w.astype(jnp.float32)) \
        .astype(x.dtype)


def rope_pairs(x, cos, sin):
    """Turn the neighbouring pairs (2i, 2i+1) of x's last axis; cos, sin:
    broadcastable to x's shape with the last axis halved."""
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape).astype(x.dtype)


def mla_project(p, x, cos, sin, cfg):
    """The projections of one MLA block. x: [..., hidden]; cos, sin: [...,
    rope / 2] at each row's position; p: the block's leaves by their short
    names. Returns (q_nope [..., heads, nope], q_rope [..., heads, rope],
    roped and both rescaled by ``s_q``; c [..., kv_lora], normed and
    rescaled by ``s_kv``; k_rope [..., rope], roped): ``(c, k_rope)`` is what
    a token keeps."""
    heads, nope, rope = cfg.num_attention_heads, cfg.qk_nope_head_dim, \
        cfg.qk_rope_head_dim
    c_q = rms(x @ p["q_a_proj.weight"], p["q_a_layernorm.weight"],
              cfg.rms_norm_eps)
    q = (c_q @ p["q_b_proj.weight"]).reshape(
        x.shape[:-1] + (heads, nope + rope))
    q = q * jnp.asarray(cfg.q_scale, q.dtype)
    kv = x @ p["kv_a_proj_with_mqa.weight"]
    c = rms(kv[..., :cfg.kv_lora_rank], p["kv_a_layernorm.weight"],
            cfg.rms_norm_eps)
    c = c * jnp.asarray(cfg.kv_scale, c.dtype)
    q_rope = rope_pairs(q[..., nope:], cos[..., None, :], sin[..., None, :])
    k_rope = rope_pairs(kv[..., cfg.kv_lora_rank:], cos, sin)
    return q[..., :nope], q_rope, c, k_rope


def kv_b_parts(w_kvb, cfg):
    """``kv_b_proj`` [kv_lora, heads * (nope + v)] as its key part [kv_lora,
    heads, nope] and its value part [kv_lora, heads, v]."""
    w = w_kvb.reshape(cfg.kv_lora_rank, cfg.num_attention_heads,
                      cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def mla_unabsorbed(p, x, cos, sin, cfg):
    """One MLA block over whole sequences, as published: keys and values of
    every head are formed from the latent, full causal softmax. x: [B, S,
    hidden]; cos, sin: [S, rope / 2]. Returns [B, S, hidden]."""
    b, s, _ = x.shape
    q_nope, q_rope, c, k_rope = mla_project(p, x, cos, sin, cfg)
    w_k, w_v = kv_b_parts(p["kv_b_proj.weight"], cfg)
    f32 = jnp.float32
    k_nope = jnp.einsum("bsc,chn->bshn", c, w_k)
    v = jnp.einsum("bsc,chv->bshv", c, w_v)
    scores = (jnp.einsum("bshn,bthn->bhst", q_nope.astype(f32),
                         k_nope.astype(f32))
              + jnp.einsum("bshr,btr->bhst", q_rope.astype(f32),
                           k_rope.astype(f32))) * cfg.attn_scale
    causal = jnp.tril(jnp.ones((s, s), bool))
    prob = jax.nn.softmax(jnp.where(causal, scores, NEG_INF), axis=-1)
    out = jnp.einsum("bhst,bthv->bshv", prob, v.astype(f32)).astype(x.dtype)
    return out.reshape(b, s, -1) @ p["o_proj.weight"]


def swiglu(x, w_gate, w_up, w_down):
    gate = x @ w_gate
    return (jax.nn.silu(gate.astype(jnp.float32)).astype(gate.dtype)
            * (x @ w_up)) @ w_down


def route(h, w_router, bias, cfg):
    """h: [T, hidden]. Returns (chosen [T, k] int32, the router outputs
    chosen; weights [T, k] float32): softmax over all the router's outputs
    in float32, the k largest of score + bias, each weighted by the scaling
    factor times its UNBIASED score."""
    logits = jnp.matmul(h, w_router, preferred_element_type=jnp.float32)
    scores = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    _, chosen = jax.lax.top_k(scores + bias.astype(jnp.float32), cfg.moe_topk)
    weights = cfg.routed_scaling_factor * jnp.take_along_axis(
        scores, chosen, axis=-1)
    return chosen.astype(jnp.int32), weights


def expert_weights(chosen, weights, cfg):
    """The routing as dense weights: (held [T, experts_held], the weight of
    each held expert for each token, nought where not chosen; zero [T], the
    summed weight of a token's zero experts)."""
    local = chosen - cfg.first_expert
    held = jnp.sum(
        jnp.where(local[..., None] == jnp.arange(cfg.experts_held), 1.0, 0.0)
        * weights[..., None], axis=1)
    zero = jnp.sum(jnp.where(chosen >= cfg.n_routed_experts, weights, 0.0),
                   axis=-1)
    return held, zero


def moe_dense(h, w_router, bias, w_gate, w_up, w_down, cfg):
    """The expert layer with every held expert on every token, weights of
    nought where not chosen: the plain form the routed dispatch has to
    equal. h: [T, hidden]; w_gate, w_up: [held, hidden, width]; w_down:
    [held, width, hidden]."""
    held, zero = expert_weights(*route(h, w_router, bias, cfg), cfg)

    def one(acc, ew):
        gate, up, down, weight = ew
        return acc + weight[:, None] * swiglu(h, gate, up, down) \
            .astype(jnp.float32), None

    y, _ = jax.lax.scan(one, jnp.zeros(h.shape, jnp.float32),
                        (w_gate, w_up, w_down, held.T))
    return (y + zero[:, None] * h.astype(jnp.float32)).astype(h.dtype)


# -- the layers ----------------------------------------------------------------
def _matrix(layer, shape):
    return layer.create_parameter(
        shape=list(shape), default_initializer=nn.initializer.Normal(0.0, 0.02))


class LongcatFlashMLA(nn.Layer):
    NAMES = ("q_a_proj.weight", "q_a_layernorm.weight", "q_b_proj.weight",
             "kv_a_proj_with_mqa.weight", "kv_a_layernorm.weight",
             "kv_b_proj.weight", "o_proj.weight")

    def __init__(self, config: LongcatFlashConfig):
        super().__init__()
        c = self.config = config
        h, heads = c.hidden_size, c.num_attention_heads
        eps = c.rms_norm_eps
        self.q_a_proj = nn.Linear(h, c.q_lora_rank, bias_attr=False)
        self.q_a_layernorm = nn.RMSNorm(c.q_lora_rank, epsilon=eps)
        self.q_b_proj = nn.Linear(
            c.q_lora_rank, heads * (c.qk_nope_head_dim + c.qk_rope_head_dim),
            bias_attr=False)
        self.kv_a_proj_with_mqa = nn.Linear(
            h, c.kv_lora_rank + c.qk_rope_head_dim, bias_attr=False)
        self.kv_a_layernorm = nn.RMSNorm(c.kv_lora_rank, epsilon=eps)
        self.kv_b_proj = nn.Linear(
            c.kv_lora_rank, heads * (c.qk_nope_head_dim + c.v_head_dim),
            bias_attr=False)
        self.o_proj = nn.Linear(heads * c.v_head_dim, h, bias_attr=False)

    def leaves(self):
        own = dict(self.named_parameters())
        return [own[n] for n in self.NAMES]

    @jax.named_scope("attention")
    def forward(self, x, rope_cache):
        cfg = self.config
        return dispatch(
            "longcat_mla",
            lambda x, cos, sin, *ws: mla_unabsorbed(
                dict(zip(self.NAMES, ws)), x, cos, sin, cfg),
            x, *rope_cache, *self.leaves())


class LongcatFlashMLP(nn.Layer):
    def __init__(self, config: LongcatFlashConfig):
        super().__init__()
        h, inter = config.hidden_size, config.ffn_hidden_size
        self.gate_proj = nn.Linear(h, inter, bias_attr=False)
        self.up_proj = nn.Linear(h, inter, bias_attr=False)
        self.down_proj = nn.Linear(inter, h, bias_attr=False)

    @jax.named_scope("mlp")
    def forward(self, x):
        return dispatch("longcat_swiglu", swiglu, x, self.gate_proj.weight,
                        self.up_proj.weight, self.down_proj.weight)


class LongcatFlashRouter(nn.Layer):
    def __init__(self, config: LongcatFlashConfig):
        super().__init__()
        self.classifier = nn.Linear(config.hidden_size, config.router_width,
                                    bias_attr=False)
        self.e_score_correction_bias = self.create_parameter(
            shape=[config.router_width], is_bias=True)


class LongcatFlashExperts(nn.Layer):
    """The held experts' weights as three banks."""

    def __init__(self, config: LongcatFlashConfig):
        super().__init__()
        e, h, f = config.experts_held, config.hidden_size, \
            config.expert_ffn_hidden_size
        self.gate_proj = _matrix(self, (e, h, f))
        self.up_proj = _matrix(self, (e, h, f))
        self.down_proj = _matrix(self, (e, f, h))


class LongcatFlashMoE(nn.Layer):
    def __init__(self, config: LongcatFlashConfig):
        super().__init__()
        self.config = config
        self.router = LongcatFlashRouter(config)
        self.experts = LongcatFlashExperts(config)

    @jax.named_scope("moe")
    def forward(self, x):
        cfg = self.config
        shape = x.shape
        return dispatch(
            "longcat_moe",
            lambda x, *ws: moe_dense(x.reshape(-1, shape[-1]), *ws, cfg)
            .reshape(shape),
            x, self.router.classifier.weight,
            self.router.e_score_correction_bias, self.experts.gate_proj,
            self.experts.up_proj, self.experts.down_proj)


class LongcatFlashDecoderLayer(nn.Layer):
    def __init__(self, config: LongcatFlashConfig):
        super().__init__()
        eps = config.rms_norm_eps

        def norms():
            return nn.LayerList([nn.RMSNorm(config.hidden_size, epsilon=eps)
                                 for _ in range(2)])

        self.self_attn = nn.LayerList([LongcatFlashMLA(config)
                                       for _ in range(2)])
        self.mlps = nn.LayerList([LongcatFlashMLP(config) for _ in range(2)])
        self.input_layernorm = norms()
        self.post_attention_layernorm = norms()
        self.mlp = LongcatFlashMoE(config)

    def forward(self, x, rope_cache):
        shortcut = None
        for i in range(2):
            a = x + self.self_attn[i](self.input_layernorm[i](x), rope_cache)
            h = self.post_attention_layernorm[i](a)
            if i == 0:
                shortcut = self.mlp(h)
            x = a + self.mlps[i](h)
        return x + shortcut


class LongcatFlashModel(nn.Layer):
    def __init__(self, config: LongcatFlashConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = VocabParallelEmbedding(config.vocab_size,
                                                   config.hidden_size)
        self.layers = nn.LayerList([LongcatFlashDecoderLayer(config)
                                    for _ in range(config.num_layers)])
        self.norm = nn.RMSNorm(config.hidden_size,
                               epsilon=config.rms_norm_eps)
        cos, sin = build_rope_cache(config.max_position_embeddings,
                                    config.qk_rope_head_dim,
                                    config.rope_theta)
        self.register_buffer("rope_cos", Tensor(cos), persistable=False)
        self.register_buffer("rope_sin", Tensor(sin), persistable=False)

    def forward(self, input_ids):
        with jax.named_scope("embed"):
            h = self.embed_tokens(input_ids)
        s = input_ids.shape[1]
        rope = (Tensor(self.rope_cos._data[:s]),
                Tensor(self.rope_sin._data[:s]))
        for layer in self.layers:
            h = layer(h, rope)
        return self.norm(h)


class LongcatFlashForCausalLM(nn.Layer):
    """The trunk and an untied head, ``lm_head.weight`` [vocab, hidden] as
    published. Training through the expert layer is the plain dense form
    (``moe_dense``); no pipeline or tensor-parallel protocol is offered."""

    def __init__(self, config: LongcatFlashConfig):
        super().__init__()
        self.config = config
        self.model = LongcatFlashModel(config)
        self.lm_head = nn.Layer()
        self.lm_head.weight = _matrix(
            self.lm_head, (config.vocab_size, config.hidden_size))

    def forward(self, input_ids, attention_mask=None):
        if attention_mask is not None:
            raise NotImplementedError(
                "LongcatFlashForCausalLM.forward is causal over whole rows; "
                "generate() and the serving engine take ragged batches")
        h = self.model(input_ids)
        with jax.named_scope("head"):
            return dispatch("longcat_head", lambda h, w: h @ w.T, h,
                            self.lm_head.weight)

    def generate(self, input_ids, attention_mask=None, **kwargs):
        from ..generation import generate
        return generate(self, input_ids, attention_mask=attention_mask,
                        **kwargs)
