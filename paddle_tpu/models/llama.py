"""Llama-2 model family (flagship; BASELINE.json config #2).

Reference parity: the PaddleNLP llama modeling stack the reference's fleet
hybrid-parallel trains (fused rope / rms_norm / flash attention kernels named
in phi/kernels/fusion/gpu). TPU-native: built from fleet TP layers whose
parameters carry mp-axis sharding annotations; under the SPMD trainer, GSPMD
partitions attention/MLP the Megatron way (column→row) with collectives on ICI.
Flash attention lowers to the Pallas kernel on TPU.

Weight layout matches paddle Linear ([in, out]) so checkpoints map over.
"""
from __future__ import annotations

import contextlib
import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from .. import nn
from ..distributed.fleet.meta_parallel import (ColumnParallelLinear,
                                               ColumnSequenceParallelLinear,
                                               RowParallelLinear,
                                               RowSequenceParallelLinear,
                                               VocabParallelEmbedding,
                                               scatter as sp_scatter)
from ..nn import functional as F
from ..ops.dispatch import dispatch, ensure_tensor
from ..tensor import Tensor


def _tp_linears(config):
    """Column/Row TP layer classes; the SP variants keep activations
    seq-sharded over mp between blocks (Megatron-SP,
    fleet/utils/sequence_parallel_utils.py:429,:564)."""
    if getattr(config, "sequence_parallel", False):
        return ColumnSequenceParallelLinear, RowSequenceParallelLinear
    return ColumnParallelLinear, RowParallelLinear


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: Optional[int] = None  # GQA; None = MHA
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    use_flash_attention: bool = True
    sequence_parallel: bool = False  # Megatron-SP inside the TP group
    dtype: str = "float32"

    @staticmethod
    def llama2_7b():
        return LlamaConfig()

    @staticmethod
    def llama2_13b():
        return LlamaConfig(hidden_size=5120, intermediate_size=13824,
                           num_hidden_layers=40, num_attention_heads=40)

    @staticmethod
    def tiny(vocab_size=256, hidden_size=64, layers=2, heads=4, kv_heads=2,
             seq=128):
        return LlamaConfig(vocab_size=vocab_size, hidden_size=hidden_size,
                           intermediate_size=hidden_size * 2,
                           num_hidden_layers=layers, num_attention_heads=heads,
                           num_key_value_heads=kv_heads,
                           max_position_embeddings=seq)


def build_rope_cache(seq_len: int, head_dim: int, theta: float = 10000.0,
                     dtype=jnp.float32):
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                                / head_dim))
    t = jnp.arange(seq_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)  # [seq, hd/2]
    return jnp.cos(freqs).astype(dtype), jnp.sin(freqs).astype(dtype)


def apply_rope(q, k, cos, sin):
    """Rotate pairs (parity: fused_rope_kernel.cu:27 FusedRopeKernel semantics,
    NeoX/llama style half-rotation). q,k: [b, s, h, d]."""
    def rotate(x):
        x1 = x[..., 0::2]
        x2 = x[..., 1::2]
        c = cos[None, :, None, :]
        s = sin[None, :, None, :]
        ro1 = x1 * c - x2 * s
        ro2 = x2 * c + x1 * s
        out = jnp.stack([ro1, ro2], axis=-1)
        # keep the input dtype: an fp32 rope cache must not silently promote
        # bf16 activations (and the Pallas path preserves dtype)
        return out.reshape(x.shape).astype(x.dtype)
    return rotate(q), rotate(k)


def fused_rope(query, key, cos, sin):
    """Tensor-level rope (recorded as one tape op). With
    FLAGS_use_pallas_fused on TPU, the forward runs the single-HBM-pass
    Pallas kernel (fused_rope_kernel.cu:27 analog); backward is AD of the
    jnp oracle either way."""
    cos_a = cos._data if isinstance(cos, Tensor) else cos
    sin_a = sin._data if isinstance(sin, Tensor) else sin

    def fwd(q, k):
        from ..kernels import fused_pallas as fp
        if fp.enabled():
            # forward via the Pallas kernel, backward via the jnp oracle's
            # vjp (rope is linear in q/k, so the cotangent rule is exact)
            prim = lambda qq, kk: fp.fused_rope_pallas(qq, kk, cos_a, sin_a)
            oracle = lambda qq, kk: apply_rope(qq, kk, cos_a, sin_a)
            f = jax.custom_vjp(prim)
            f.defvjp(lambda qq, kk: (prim(qq, kk), (qq, kk)),
                     lambda res, g: jax.vjp(oracle, *res)[1](g))
            return f(q, k)
        return apply_rope(q, k, cos_a, sin_a)

    return dispatch("fused_rope", fwd,
                    ensure_tensor(query), ensure_tensor(key))


class LlamaAttention(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.hidden_size = config.hidden_size
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads or self.num_heads
        self.head_dim = self.hidden_size // self.num_heads
        Col, Row = _tp_linears(config)
        self.q_proj = Col(self.hidden_size, self.num_heads * self.head_dim,
                          has_bias=False)
        self.k_proj = Col(self.hidden_size, self.num_kv_heads * self.head_dim,
                          has_bias=False)
        self.v_proj = Col(self.hidden_size, self.num_kv_heads * self.head_dim,
                          has_bias=False)
        self.o_proj = Row(self.num_heads * self.head_dim, self.hidden_size,
                          has_bias=False)

    @jax.named_scope("attention")
    def forward(self, hidden_states, rope_cache, attention_mask=None,
                startend_row_indices=None):
        b, s, _ = hidden_states.shape
        q = self.q_proj(hidden_states).reshape([b, s, self.num_heads,
                                                self.head_dim])
        k = self.k_proj(hidden_states).reshape([b, s, self.num_kv_heads,
                                                self.head_dim])
        v = self.v_proj(hidden_states).reshape([b, s, self.num_kv_heads,
                                                self.head_dim])
        cos, sin = rope_cache
        q, k = fused_rope(q, k, cos, sin)
        if startend_row_indices is not None:
            if attention_mask is not None:
                raise NotImplementedError(
                    "attention_mask cannot be combined with "
                    "attn_startend_row_indices; fold padding into the "
                    "column bounds (a padded key column is a fully-masked "
                    "band)")
            # packed-document / sparse-mask attention: O(S) column bounds
            # instead of a dense mask (reference PaddleNLP flashmask
            # integration over flash_attention.py:1299); GQA handled inside
            return self.o_proj(F.flashmask_attention(
                q, k, v, startend_row_indices, causal=True)
                .reshape([b, s, self.num_heads * self.head_dim]))
        if self.num_kv_heads != self.num_heads:
            rep = self.num_heads // self.num_kv_heads
            from ..ops.manipulation import repeat_interleave
            k = repeat_interleave(k, rep, axis=2)
            v = repeat_interleave(v, rep, axis=2)
        out = F.scaled_dot_product_attention(
            q, k, v, attn_mask=attention_mask, is_causal=True,
            allow_flash=self.config.use_flash_attention)
        out = out.reshape([b, s, self.num_heads * self.head_dim])
        return self.o_proj(out)


class LlamaMLP(nn.Layer):
    """SwiGLU (parity: fused_bias_act / swiglu in the reference kernel list)."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        Col, Row = _tp_linears(config)
        self.gate_proj = Col(config.hidden_size, config.intermediate_size,
                             has_bias=False)
        self.up_proj = Col(config.hidden_size, config.intermediate_size,
                           has_bias=False)
        self.down_proj = Row(config.intermediate_size, config.hidden_size,
                             has_bias=False)

    @jax.named_scope("mlp")
    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class LlamaDecoderLayer(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.self_attn = LlamaAttention(config)
        self.mlp = LlamaMLP(config)
        self.input_layernorm = nn.RMSNorm(config.hidden_size,
                                          epsilon=config.rms_norm_eps)
        self.post_attention_layernorm = nn.RMSNorm(config.hidden_size,
                                                   epsilon=config.rms_norm_eps)

    def forward(self, hidden_states, rope_cache, attention_mask=None,
                startend_row_indices=None):
        residual = hidden_states
        h = self.input_layernorm(hidden_states)
        h = self.self_attn(h, rope_cache, attention_mask,
                           startend_row_indices)
        h = residual + h
        residual = h
        h2 = self.post_attention_layernorm(h)
        h2 = self.mlp(h2)
        return residual + h2


class LlamaModel(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = VocabParallelEmbedding(config.vocab_size,
                                                   config.hidden_size)
        self.layers = nn.LayerList(
            [LlamaDecoderLayer(config) for _ in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)
        head_dim = config.hidden_size // config.num_attention_heads
        cos, sin = build_rope_cache(config.max_position_embeddings, head_dim,
                                    config.rope_theta)
        self.register_buffer("rope_cos", Tensor(cos), persistable=False)
        self.register_buffer("rope_sin", Tensor(sin), persistable=False)

    def forward(self, input_ids, attention_mask=None,
                attn_startend_row_indices=None):
        with jax.named_scope("embed"):
            h = self.embed_tokens(input_ids)
        if self.config.sequence_parallel:
            # Megatron-SP: activations between blocks live seq-sharded over mp
            # (reference: split_inputs_sequence_dim + ScatterOp after embed)
            h = sp_scatter(h)
        s = input_ids.shape[1]
        cos = Tensor(self.rope_cos._data[:s])
        sin = Tensor(self.rope_sin._data[:s])
        run_blocks = getattr(self, "_pp_run_blocks", None)
        if run_blocks is not None:
            if attention_mask is not None or \
                    attn_startend_row_indices is not None:
                raise NotImplementedError(
                    "attention_mask / attn_startend_row_indices are not "
                    "threaded through the pipelined block region yet "
                    "(causal masking only); pad with ignore_index labels "
                    "instead")
            # pipeline-parallel trace: the trainer replaces the block loop
            # with the compiled circular-pipeline region
            h = Tensor(run_blocks(h._data, cos._data, sin._data))
        else:
            for layer in self.layers:
                h = layer(h, (cos, sin), attention_mask,
                          attn_startend_row_indices)
        return self.norm(h)


class LlamaForCausalLM(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.model = LlamaModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            Col, _ = _tp_linears(config)
            self.lm_head = Col(config.hidden_size, config.vocab_size,
                               has_bias=False)

    def forward(self, input_ids, attention_mask=None,
                attn_startend_row_indices=None):
        """attn_startend_row_indices: FlashMask column bounds
        [B, KH, S, {1, 2}] (causal forms: LTS, or LTS+LTE) for packed-
        document / sparse-mask attention (reference flashmask_attention,
        flash_attention.py:1299). Mutually exclusive with
        attention_mask."""
        h = self.model(input_ids, attention_mask,
                       attn_startend_row_indices)
        return self._head(h)

    @jax.named_scope("head_loss")
    def _head(self, h):
        """Logits of the (tied or own) head, under the loss's scope."""
        if self.lm_head is None:
            from ..ops.linalg import matmul
            return matmul(h, self.model.embed_tokens.weight, transpose_y=True)
        return self.lm_head(h)

    def generate(self, input_ids, attention_mask=None, **kwargs):
        """KV-cached autoregressive decoding as one compiled program
        (greedy / temperature / top-k / top-p; see generation.generate)."""
        from ..generation import generate
        return generate(self, input_ids, attention_mask=attention_mask,
                        **kwargs)

    @jax.named_scope("head_loss")
    def compute_loss(self, logits, labels):
        """Shifted next-token cross entropy."""
        from ..ops.manipulation import reshape
        b, s, v = logits.shape
        shift_logits = logits[:, :-1, :]
        shift_labels = labels[:, 1:]
        return F.cross_entropy(reshape(shift_logits, [b * (s - 1), v]),
                               reshape(shift_labels, [b * (s - 1)]))

    def forward_loss(self, input_ids, labels, loss_chunk_size=None,
                     attention_mask=None, attn_startend_row_indices=None):
        """Trunk forward + shifted CE without materializing full logits.

        With loss_chunk_size=c, the head matmul + softmax run per sequence
        chunk inside a remat'd lax.scan, so peak memory holds [B, c, V]
        logits instead of [B, S, V] (plus the same-sized cotangent) — the
        difference between fitting and OOMing a 1B-class model on one 16GB
        chip. Numerics identical to compute_loss(self(ids), labels).
        """
        if loss_chunk_size is None:
            return self.compute_loss(
                self(input_ids, attention_mask,
                     attn_startend_row_indices), labels)
        h = self.model(input_ids, attention_mask,
                       attn_startend_row_indices)
        tied = self.lm_head is None
        w = (self.model.embed_tokens.weight if tied
             else self.lm_head.weight)  # tied: [V, H]; head: [H, V]
        lt = ensure_tensor(labels)
        c = int(loss_chunk_size)

        def fwd(h_a, w_raw, y_a):
            w_a = w_raw.T if tied else w_raw
            hs = h_a[:, :-1, :]
            ys = y_a[:, 1:]
            b, sm1, hid = hs.shape
            nc = -(-sm1 // c)
            pad = nc * c - sm1
            hs = jnp.pad(hs, ((0, 0), (0, pad), (0, 0)))
            ys = jnp.pad(ys, ((0, 0), (0, pad)), constant_values=0)
            valid = jnp.pad(jnp.ones((b, sm1), jnp.bool_),
                            ((0, 0), (0, pad)))
            hs = hs.reshape(b, nc, c, hid).swapaxes(0, 1)
            ys = ys.reshape(b, nc, c).swapaxes(0, 1)
            valid = valid.reshape(b, nc, c).swapaxes(0, 1)

            def body(carry, xs):
                hc, yc, mc = xs
                # honor cross_entropy's ignore_index=-100 contract so the
                # chunked path matches compute_loss on padded batches
                mc = mc & (yc != -100)
                yc = jnp.where(yc < 0, 0, yc)
                logits = hc.astype(jnp.float32) @ w_a.astype(jnp.float32)
                logp = jax.nn.log_softmax(logits, axis=-1)
                nll = -jnp.take_along_axis(
                    logp, yc[..., None].astype(jnp.int32), axis=-1)[..., 0]
                s_ = jnp.sum(jnp.where(mc, nll, 0.0))
                n_ = jnp.sum(mc)
                return (carry[0] + s_, carry[1] + n_), None

            (tot, cnt), _ = jax.lax.scan(
                jax.checkpoint(body), (jnp.float32(0.0), jnp.int32(0)),
                (hs, ys, valid))
            return tot / jnp.maximum(cnt, 1).astype(jnp.float32)

        with jax.named_scope("head_loss"):
            return dispatch("chunked_causal_ce", fwd, h, ensure_tensor(w), lt)

    # -- pipeline protocol (parallel.pipeline.PipelinedTrainer) ---------------
    def pp_block_layers(self):
        return list(self.model.layers)

    # 1F1B protocol: embed/tail halves so the loss runs inside the pipeline
    # region (parity: PipelineLayer's SharedLayerDesc head placement,
    # parallel_layers/pp_layers.py:77).
    def pp_embed(self, input_ids):
        h = self.model.embed_tokens(input_ids)
        s = input_ids.shape[1]
        cos = self.model.rope_cos._data[:s]
        sin = self.model.rope_sin._data[:s]
        return h, (cos, sin)

    def pp_tail(self, h, labels):
        return self.compute_loss(self._head(self.model.norm(h)), labels)

    def pp_embed_param_names(self):
        return ["model.embed_tokens.weight"]

    def pp_tail_param_names(self):
        names = ["model.norm.weight"]
        names.append("model.embed_tokens.weight" if self.lm_head is None
                     else "lm_head.weight")
        return names

    @staticmethod
    def pp_block_call(layer, h, cos, sin):
        return layer(h, (cos, sin))

    @contextlib.contextmanager
    def pp_install(self, run_blocks):
        """Route this model's block loop through `run_blocks(h, *consts)` for
        the duration of a pipeline-parallel trace; forward() is otherwise
        unchanged, so any user loss_fn(model, *batch) works pipelined."""
        self.model._pp_run_blocks = run_blocks
        try:
            yield
        finally:
            self.model._pp_run_blocks = None

    def num_params(self):
        return sum(p.numel() for p in self.parameters())
