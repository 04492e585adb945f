"""Plain reference of Ouro, the looped decoder of Ouro-2.6B (ByteDance,
huggingface.co/ByteDance/Ouro-2.6B): a Llama-shaped block with four RMSNorms
(one before and one after each branch), ONE stack of layers run
``total_ut_steps`` times a token with the model's final norm after every
pass, and an exit gate. float32 ``jax.numpy``, one sequence at a time.

    layer:  a = x + N2(Attn(N1(x)));  y = a + N4(MLP(N3(a)))
    model:  h_0 = Embed(ids);  h_t = Norm(L_n(... L_1(h_{t-1}))), t = 1..T
    gate:   lambda_t = sigmoid(w_g . h_t + b_g)
            p_t = lambda_t prod_{j<t}(1 - lambda_j) for t < T, p_T the rest
            t* = first t whose cumulative p reaches early_exit_threshold,
                 else T;  logits = W_head h_{t*}   (no further norm)

Pass ``t`` of a layer attends to the pass-``t`` keys of earlier positions,
which a full causal forward a pass gives with no cache. All passes always
run; the exit only picks whose state feeds the head.

The harness walks ``block`` x layers then ``pass_end``, once a pass, and
carries ONE ``[width, hidden]`` array between stops, so ``head`` sees the
last pass's state only: the walked copy follows the published threshold,
``early_exit_threshold`` 1, where ``t* = T`` whenever no ``lambda_t`` rounds
to exactly 1 (with weights of std 0.02 on a normed state the gate's logit has
a standard deviation of about 1: a float32 sigmoid reads 1 from 17 on).
``pass_end`` still computes the gate and refuses a cell at another threshold,
so the file holds the whole published forward; ``forward`` beside it is the
same model written straight through with the selection at any threshold, for
the tests.

Departures from the published code: rotary pairs are the neighbouring
channels (2i, 2i+1), not the halves, as in ``llama_block.py`` and for the
same reason; weights are taken by the published state dict's names with
[in, out] matrices, the gate's weight ``[hidden, 1]``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import causal_attention, mm
from .llama_block import _rms, _rope


def embed(top, ids, cfg):
    return top["model.embed_tokens.weight"].astype(jnp.float32)[ids]


def block(top, lw, x, cfg, q=None):
    """One visit of one layer. x: [S, hidden] of one sequence; ``top`` is
    not read."""
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
    x = x + _rms(mm(att, lw["self_attn.o_proj.weight"], q),
                 lw["input_layernorm_2.weight"], eps)
    a = _rms(x, lw["post_attention_layernorm.weight"], eps)
    m = jax.nn.silu(mm(a, lw["mlp.gate_proj.weight"], q)) \
        * mm(a, lw["mlp.up_proj.weight"], q)
    return x + _rms(mm(m, lw["mlp.down_proj.weight"], q),
                    lw["post_attention_layernorm_2.weight"], eps)


def gate(top, x, q=None):
    """lambda of a normed state x: [S, hidden] -> [S]."""
    return jax.nn.sigmoid(
        mm(x, top["model.early_exit_gate.weight"], q)[:, 0]
        + top["model.early_exit_gate.bias"].astype(jnp.float32)[0])


def pass_end(top, lw, x, cfg, q=None):
    """The end of a pass: the model's norm. The normed state starts the
    next pass and is what the gate and, after the last pass, the head read.
    ``lw`` is None. The gate is computed and not carried (one array goes
    from stop to stop); a threshold under 1 needs ``forward``."""
    if cfg["early_exit_threshold"] < 1:
        raise ValueError("the walked reference follows early_exit_threshold "
                         "1; bench.reference.ouro_block.forward selects at "
                         "any threshold")
    x = _rms(x, top["model.norm.weight"], cfg["rms_norm_eps"])
    gate(top, x, q)
    return x


def head(top, x, cfg, q=None):
    """``lm_head`` alone: x was normed at its pass's end."""
    return mm(x, top["lm_head.weight"], q)


def forward(top, layers, ids, cfg, q=None):
    """The whole model on one sequence, straight through. ``layers``: the
    layers' leaves in order; ids: [S]. Returns (logits [S, vocab], t* [S]
    in 1..T)."""
    passes, thr = cfg["total_ut_steps"], cfg["early_exit_threshold"]
    x = embed(top, ids, cfg)
    left = jnp.zeros(ids.shape, bool)
    t_star = jnp.full(ids.shape, passes, jnp.int32)
    x_exit = jnp.zeros_like(x)
    cum = jnp.zeros(ids.shape, jnp.float32)
    stay = jnp.ones(ids.shape, jnp.float32)
    for t in range(1, passes + 1):
        for lw in layers:
            x = block(top, lw, x, cfg, q)
        x = _rms(x, top["model.norm.weight"], cfg["rms_norm_eps"])
        lam = gate(top, x, q)
        cum = cum + (lam * stay if t < passes else stay)
        stay = stay * (1.0 - lam)
        now = ~left & ((cum >= thr) | (t == passes))
        x_exit = jnp.where(now[:, None], x, x_exit)
        t_star = jnp.where(now, t, t_star)
        left = left | now
    return head(top, x_exit, cfg, q), t_star
