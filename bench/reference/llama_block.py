"""Plain reference of the Llama-shaped block that Mistral-7B uses (Jiang et
al. 2023, arXiv:2310.06825): RMSNorm, rotary positions, grouped-query causal
attention, SwiGLU, untied head. float32 ``jax.numpy``, one sequence at a time.

Weights are taken by the names of a Mistral state dict with [in, out]
matrices. One departure from the published code: rotary pairs are the
neighbouring channels (2i, 2i+1) and not the halves (i, i + d/2). The two
differ by a fixed permutation of the q and k projections' output columns, so
with weights drawn from a seed they are the same model; the system under
test pairs neighbours, and the reference has to rotate what it rotates.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import causal_attention, mm


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _rope(x, theta):
    """x: [S, heads, d]; position p rotates pair i by p / theta**(2i/d)."""
    s, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    c, sn = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * sn, x2 * c + x1 * sn], -1).reshape(x.shape)


def embed(top, ids, cfg):
    return top["model.embed_tokens.weight"].astype(jnp.float32)[ids]


def block(top, lw, x, cfg, q=None):
    """The walk's one step. x: [S, hidden] of one sequence; ``top`` is not
    read."""
    s, h = x.shape
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = h // heads
    eps = cfg["rms_norm_eps"]
    a = _rms(x, lw["input_layernorm.weight"], eps)
    qh = mm(a, lw["self_attn.q_proj.weight"], q).reshape(s, heads, d)
    kh = mm(a, lw["self_attn.k_proj.weight"], q).reshape(s, kv, d)
    vh = mm(a, lw["self_attn.v_proj.weight"], q).reshape(s, kv, d)
    qh, kh = _rope(qh, cfg["rope_theta"]), _rope(kh, cfg["rope_theta"])
    kh = jnp.repeat(kh, heads // kv, axis=1)
    vh = jnp.repeat(vh, heads // kv, axis=1)
    att = causal_attention(qh, kh, vh).reshape(s, h)
    x = x + mm(att, lw["self_attn.o_proj.weight"], q)
    a = _rms(x, lw["post_attention_layernorm.weight"], eps)
    m = jax.nn.silu(mm(a, lw["mlp.gate_proj.weight"], q)) \
        * mm(a, lw["mlp.up_proj.weight"], q)
    return x + mm(m, lw["mlp.down_proj.weight"], q)


def head(top, x, cfg, q=None):
    x = _rms(x, top["model.norm.weight"], cfg["rms_norm_eps"])
    return mm(x, top["lm_head.weight"], q)
