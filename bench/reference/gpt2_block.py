"""Plain reference of the GPT-2 block (Radford et al. 2019; the shape of
Cerebras-GPT, arXiv:2304.03208): pre-LayerNorm, learned positions, fused
qkv with biases, multi-head causal attention, erf GELU, head tied to the
token embedding. float32 ``jax.numpy``, one sequence at a time.

Weights are taken by the names of a GPT-2 state dict with [in, out]
matrices. Departures from the published description: none.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import causal_attention, mm


def _ln(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w.astype(jnp.float32) \
        + b.astype(jnp.float32)


def embed(top, ids, cfg):
    """ids: [S] -> [S, n_embd]."""
    pos = jnp.arange(ids.shape[0])
    return top["transformer.wte.weight"].astype(jnp.float32)[ids] \
        + top["transformer.wpe.weight"].astype(jnp.float32)[pos]


def block(top, lw, x, cfg, q=None):
    """The walk's one step. x: [S, n_embd] of one sequence; ``top`` is not
    read."""
    s, h = x.shape
    heads = cfg["n_head"]
    eps = cfg["layer_norm_epsilon"]
    a = _ln(x, lw["ln_1.weight"], lw["ln_1.bias"], eps)
    qkv = mm(a, lw["attn.qkv_proj.weight"], q) \
        + lw["attn.qkv_proj.bias"].astype(jnp.float32)
    qkv = qkv.reshape(s, 3, heads, h // heads)
    att = causal_attention(qkv[:, 0], qkv[:, 1], qkv[:, 2]).reshape(s, h)
    x = x + mm(att, lw["attn.out_proj.weight"], q) \
        + lw["attn.out_proj.bias"].astype(jnp.float32)
    a = _ln(x, lw["ln_2.weight"], lw["ln_2.bias"], eps)
    m = jax.nn.gelu(mm(a, lw["mlp.fc_in.weight"], q)
                    + lw["mlp.fc_in.bias"].astype(jnp.float32),
                    approximate=False)
    return x + mm(m, lw["mlp.fc_out.weight"], q) \
        + lw["mlp.fc_out.bias"].astype(jnp.float32)


def head(top, x, cfg, q=None):
    """x: [S, n_embd] -> logits [S, vocab]."""
    x = _ln(x, top["transformer.ln_f.weight"], top["transformer.ln_f.bias"],
            cfg["layer_norm_epsilon"])
    return mm(x, top["transformer.wte.weight"].astype(jnp.float32).T, q)
