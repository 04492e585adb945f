"""Ouro: a looped decoder (ByteDance, huggingface.co/ByteDance/Ouro-2.6B).

The Llama block with a second RMSNorm on each branch's output, and a model
that runs its ONE stack of layers ``total_ut_steps`` times a token: the
model's single final norm closes every pass, the normed state starts the
next one, and a gate on it gives each pass an exit probability. The head
reads the state of the first pass at which the cumulative exit probability
reaches ``early_exit_threshold`` (the last pass otherwise; at the published
threshold 1 that is the last pass unless a gate saturates). All passes always
run: pass ``t`` of a later token attends to this token's pass-``t`` keys.

Built from ``llama.py``'s attention, MLP and rope cache; parameters carry the
published state dict's names. Serving and ``generate()`` go through
``generation._OuroDecoder``, which ``_decoder_for`` picks by this class.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .. import nn
from ..distributed.fleet.meta_parallel import VocabParallelEmbedding
from ..ops.dispatch import dispatch
from ..tensor import Tensor
from .llama import (LlamaAttention, LlamaConfig, LlamaForCausalLM, LlamaMLP,
                    _tp_linears, build_rope_cache)


@dataclass
class OuroConfig(LlamaConfig):
    vocab_size: int = 49152
    hidden_size: int = 2048
    intermediate_size: int = 5632
    num_hidden_layers: int = 48
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    max_position_embeddings: int = 65536
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    total_ut_steps: int = 4            # passes over the layers a token
    early_exit_threshold: float = 1.0

    @staticmethod
    def tiny(vocab_size=256, hidden_size=64, layers=2, heads=4, passes=3,
             seq=128, threshold=1.0):
        return OuroConfig(vocab_size=vocab_size, hidden_size=hidden_size,
                          intermediate_size=hidden_size * 2,
                          num_hidden_layers=layers, num_attention_heads=heads,
                          num_key_value_heads=heads,
                          max_position_embeddings=seq, total_ut_steps=passes,
                          early_exit_threshold=threshold)


def exit_select(states, gate_w, gate_b, threshold):
    """Which pass each row leaves after, and the state it leaves with.

    states: [T, ..., H], the normed output of every pass; gate_w: [H, 1];
    gate_b: [1]. ``lambda_t = sigmoid(w . h_t + b)``; pass ``t`` has exit
    probability ``lambda_t prod_{j<t}(1 - lambda_j)`` and the last pass the
    remainder; a row leaves after the first pass whose cumulative
    probability reaches ``threshold``, else after the last. Returns
    (state [..., H], pass [...] int32 in 1..T). The gate is computed in
    float32 as a sum of products, so no matrix unit rounds it."""
    lam = jax.nn.sigmoid(
        jnp.sum(states.astype(jnp.float32)
                * gate_w.astype(jnp.float32)[:, 0], axis=-1)
        + gate_b.astype(jnp.float32)[0])                     # [T, ...]
    stay = jnp.cumprod(1.0 - lam, axis=0)
    stay_before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]])
    reached = jnp.cumsum(lam * stay_before, axis=0) >= threshold
    reached = reached.at[-1].set(True)
    first = jnp.argmax(reached, axis=0)                      # first True
    state = jnp.take_along_axis(states, first[None, ..., None], axis=0)[0]
    return state, (first + 1).astype(jnp.int32)


class OuroDecoderLayer(nn.Layer):
    """``a = x + N2(Attn(N1(x)))``, ``y = a + N4(MLP(N3(a)))``."""

    def __init__(self, config: OuroConfig):
        super().__init__()
        eps = config.rms_norm_eps
        self.self_attn = LlamaAttention(config)
        self.mlp = LlamaMLP(config)
        self.input_layernorm = nn.RMSNorm(config.hidden_size, epsilon=eps)
        self.input_layernorm_2 = nn.RMSNorm(config.hidden_size, epsilon=eps)
        self.post_attention_layernorm = nn.RMSNorm(config.hidden_size,
                                                   epsilon=eps)
        self.post_attention_layernorm_2 = nn.RMSNorm(config.hidden_size,
                                                     epsilon=eps)

    def forward(self, hidden_states, rope_cache, attention_mask=None):
        h = self.self_attn(self.input_layernorm(hidden_states), rope_cache,
                           attention_mask)
        a = hidden_states + self.input_layernorm_2(h)
        m = self.mlp(self.post_attention_layernorm(a))
        return a + self.post_attention_layernorm_2(m)


class OuroModel(nn.Layer):
    def __init__(self, config: OuroConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = VocabParallelEmbedding(config.vocab_size,
                                                   config.hidden_size)
        self.layers = nn.LayerList(
            [OuroDecoderLayer(config)
             for _ in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)
        self.early_exit_gate = nn.Linear(config.hidden_size, 1)
        head_dim = config.hidden_size // config.num_attention_heads
        cos, sin = build_rope_cache(config.max_position_embeddings, head_dim,
                                    config.rope_theta)
        self.register_buffer("rope_cos", Tensor(cos), persistable=False)
        self.register_buffer("rope_sin", Tensor(sin), persistable=False)

    def forward(self, input_ids, attention_mask=None,
                attn_startend_row_indices=None):
        """The exit state of every position (normed: the head reads it as
        it is)."""
        if attn_startend_row_indices is not None:
            raise NotImplementedError("OuroModel takes attention_mask only")
        with jax.named_scope("embed"):
            h = self.embed_tokens(input_ids)
        s = input_ids.shape[1]
        rope = (Tensor(self.rope_cos._data[:s]),
                Tensor(self.rope_sin._data[:s]))
        states = []
        for _ in range(self.config.total_ut_steps):
            for layer in self.layers:
                h = layer(h, rope, attention_mask)
            h = self.norm(h)
            states.append(h)
        threshold = self.config.early_exit_threshold
        with jax.named_scope("loop_exit"):
            return dispatch(
                "ouro_exit_select",
                lambda gate_w, gate_b, *hs: exit_select(
                    jnp.stack(hs), gate_w, gate_b, threshold)[0],
                self.early_exit_gate.weight, self.early_exit_gate.bias,
                *states)


class OuroForCausalLM(LlamaForCausalLM):
    """``LlamaForCausalLM``'s head, losses and ``generate()`` over the looped
    trunk. The pipeline protocol is not offered: its region runs a block
    list once."""

    def __init__(self, config: OuroConfig):
        nn.Layer.__init__(self)
        self.config = config
        self.model = OuroModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            Col, _ = _tp_linears(config)
            self.lm_head = Col(config.hidden_size, config.vocab_size,
                               has_bias=False)

    def pp_block_layers(self):
        raise NotImplementedError(
            "OuroForCausalLM runs its layers total_ut_steps times a token; "
            "the pipeline region runs a block list once")
