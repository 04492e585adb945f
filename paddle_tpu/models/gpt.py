"""GPT model family, dense and MoE (BASELINE.json configs #3/#4).

Reference parity: the GPT/ERNIE-style decoder stacks the reference's fleet
hybrid-parallel and MoE paths train (incubate/distributed/models/moe/,
fused_multi_transformer kernels). TPU-native: TP layers carry mp-axis
annotations, MoE FFN blocks carry ep-axis annotations; under the SPMD
trainer GSPMD emits the Megatron collectives and the expert all-to-all.

Pre-LN GPT-2 architecture: learned position embeddings, GELU MLP (or
MoELayer every `moe_every` blocks), causal attention, weight-tied LM head
optional.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from .. import nn
from ..distributed.fleet.meta_parallel import (ColumnParallelLinear,
                                               RowParallelLinear,
                                               VocabParallelEmbedding)
from ..incubate.distributed.models.moe import MoELayer
from ..nn import functional as F
from ..tensor import Tensor


@dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    intermediate_size: Optional[int] = None  # None = 4 * hidden
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    max_position_embeddings: int = 1024
    layer_norm_epsilon: float = 1e-5
    tie_word_embeddings: bool = True
    # MoE (num_experts == 0 -> dense GPT)
    num_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_every: int = 2          # MoE FFN every N-th block (GShard style)
    moe_gate: str = "gshard"
    aux_loss_weight: float = 0.01
    dtype: str = "float32"

    @property
    def ffn_size(self) -> int:
        return self.intermediate_size or 4 * self.hidden_size

    @staticmethod
    def gpt2_small():
        return GPTConfig()

    @staticmethod
    def gpt_moe(experts: int = 8, **kw):
        return GPTConfig(num_experts=experts, **kw)

    @staticmethod
    def tiny(vocab_size=256, hidden_size=64, layers=2, heads=4, seq=64,
             num_experts=0, **kw):
        return GPTConfig(vocab_size=vocab_size, hidden_size=hidden_size,
                         intermediate_size=hidden_size * 2,
                         num_hidden_layers=layers, num_attention_heads=heads,
                         max_position_embeddings=seq, num_experts=num_experts,
                         **kw)


class GPTAttention(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        h = config.hidden_size
        self.num_heads = config.num_attention_heads
        self.head_dim = h // self.num_heads
        self.qkv_proj = ColumnParallelLinear(h, 3 * h, has_bias=True)
        self.out_proj = RowParallelLinear(h, h, has_bias=True)

    @jax.named_scope("attention")
    def forward(self, x, attention_mask=None):
        b, s, h = x.shape
        qkv = self.qkv_proj(x).reshape([b, s, 3, self.num_heads,
                                        self.head_dim])
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        out = F.scaled_dot_product_attention(q, k, v,
                                             attn_mask=attention_mask,
                                             is_causal=True)
        return self.out_proj(out.reshape([b, s, h]))


class GPTMLP(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.fc_in = ColumnParallelLinear(config.hidden_size, config.ffn_size,
                                          has_bias=True)
        self.fc_out = RowParallelLinear(config.ffn_size, config.hidden_size,
                                        has_bias=True)

    @jax.named_scope("mlp")
    def forward(self, x):
        return self.fc_out(F.gelu(self.fc_in(x)))


class GPTBlock(nn.Layer):
    def __init__(self, config: GPTConfig, layer_idx: int):
        super().__init__()
        self.ln_1 = nn.LayerNorm(config.hidden_size,
                                 epsilon=config.layer_norm_epsilon)
        self.attn = GPTAttention(config)
        self.ln_2 = nn.LayerNorm(config.hidden_size,
                                 epsilon=config.layer_norm_epsilon)
        use_moe = (config.num_experts > 0
                   and (layer_idx + 1) % max(1, config.moe_every) == 0)
        if use_moe:
            self.mlp = MoELayer(config.hidden_size, config.ffn_size,
                                num_expert=config.num_experts,
                                top_k=config.moe_top_k,
                                capacity_factor=config.moe_capacity_factor,
                                gate=config.moe_gate)
        else:
            self.mlp = GPTMLP(config)
        self.is_moe = use_moe

    def forward(self, x, attention_mask=None):
        x = x + self.attn(self.ln_1(x), attention_mask)
        x = x + self.mlp(self.ln_2(x))
        return x


class GPTModel(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.wte = VocabParallelEmbedding(config.vocab_size,
                                          config.hidden_size)
        self.wpe = nn.Embedding(config.max_position_embeddings,
                                config.hidden_size)
        self.h = nn.LayerList([GPTBlock(config, i)
                               for i in range(config.num_hidden_layers)])
        self.ln_f = nn.LayerNorm(config.hidden_size,
                                 epsilon=config.layer_norm_epsilon)

    def forward(self, input_ids, attention_mask=None):
        b, s = input_ids.shape
        if s > self.config.max_position_embeddings:
            raise ValueError(
                f"sequence length {s} exceeds max_position_embeddings "
                f"{self.config.max_position_embeddings}")
        with jax.named_scope("embed"):
            pos = Tensor(jnp.arange(s, dtype=jnp.int32))
            x = self.wte(input_ids) + self.wpe(pos)
        for block in self.h:
            x = block(x, attention_mask)
        return self.ln_f(x)


class GPTForCausalLM(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.transformer = GPTModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = ColumnParallelLinear(config.hidden_size,
                                                config.vocab_size,
                                                has_bias=False)

    def forward(self, input_ids, attention_mask=None):
        h = self.transformer(input_ids, attention_mask)
        with jax.named_scope("head_loss"):
            if self.lm_head is None:
                from ..ops.linalg import matmul
                return matmul(h, self.transformer.wte.weight,
                              transpose_y=True)
            return self.lm_head(h)

    def generate(self, input_ids, attention_mask=None, **kwargs):
        """KV-cached decoding (dense blocks only; see generation.py)."""
        from ..generation import generate
        return generate(self, input_ids, attention_mask=attention_mask,
                        **kwargs)

    def aux_loss(self):
        """Sum of MoE load-balance losses from the last forward (scaled)."""
        total = None
        for block in self.transformer.h:
            if getattr(block, "is_moe", False) and block.mlp.l_aux is not None:
                total = block.mlp.l_aux if total is None \
                    else total + block.mlp.l_aux
        if total is None:
            return None
        return total * self.config.aux_loss_weight

    @jax.named_scope("head_loss")
    def compute_loss(self, logits, labels):
        from ..ops.manipulation import reshape
        b, s, v = logits.shape
        loss = F.cross_entropy(reshape(logits[:, :-1, :], [b * (s - 1), v]),
                               reshape(labels[:, 1:], [b * (s - 1)]))
        aux = self.aux_loss()
        return loss if aux is None else loss + aux

    def num_params(self):
        return sum(p.numel() for p in self.parameters())
