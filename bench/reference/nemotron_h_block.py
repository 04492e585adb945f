"""Plain reference of Nemotron-H, the hybrid decoder of
NVIDIA-Nemotron-3-Super-120B-A12B (huggingface.co/nvidia/
NVIDIA-Nemotron-3-Super-120B-A12B-BF16, ``model_type`` ``nemotron_h``, and
the modelling code published with it), as one chip's share of its experts
and of its vocabulary. float32 ``jax.numpy``, one sequence at a time, the
recurrence a plain ``lax.scan`` over the tokens from a zero state: no
chunks, no cache, no kernel.

Every layer is ``x + part(RMSNorm(x))``; the walk has one stop for each kind
of part:

    mamba:      [z | xBC | dt] = W_in h
                xBC = silu(conv(xBC) + b)       # causal, depthwise, width K
                [x | B | C] = xBC               # x: heads x P, B, C: groups x N
                dt = softplus(dt + dt_bias),  A = -exp(A_log)      # a head
                S_t = exp(dt_t A) S_(t-1) + dt_t x_t (x) B_t[g]    # P x N a head
                y_t = S_t C_t[g] + D x_t        # g = head // (heads / groups)
                out = W_out RMSNorm_groups(y * silu(z))   # gate, then norm
    attention:  q, k, v = W h; causal softmax(q k / sqrt(d)) v over grouped
                heads; W_o. No rotary.
    moe:        s = sigmoid(W_r h) over all the router's outputs
                chosen = the k largest of s + b          # b for the choice only
                w_e = factor * s_e / sum_chosen s        # normalised, unbiased
                y = W_up_lat sum_{e chosen, held here} w_e E_e(W_down_lat h)
                    + E_shared(h)               # E(x) = W2 relu(W1 x)^2

The configuration's ``n_routed_experts`` counts the routed experts held here,
ids ``first_expert ..``; the router's width is read off its weight. What the
routed experts held elsewhere would add is left out, here as in the program.
``vocab_size`` is the slice of the vocabulary this chip holds.

Departures from the published code: weights are [in, out] matrices but
``lm_head.weight`` [vocab, hidden] as published; the depthwise convolution's
weight is [K, channels] (published [channels, 1, K]); the held experts are
two banks ``[held, in, out]``.

The recurrence and the expert layer's three parts are functions of their
own (``recurrence``, ``route``, ``routed_part``, ``shared_part``), so that
``bench/tools/nemotron_faults.py`` can put a wrong one in a part's place and
show that the comparison sees each part; nothing here knows of it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import causal_attention, mm
from .llama_block import _rms


def embed(top, ids, cfg):
    return top["backbone.embeddings.weight"].astype(jnp.float32)[ids]


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def recurrence(x, b, c, dt, a, cfg):
    """x: [S, heads, P]; b, c: [S, groups, N]; dt: [S, heads]; a: [heads].
    Returns y [S, heads, P], from a zero state."""
    heads, groups = cfg["mamba_num_heads"], cfg["n_groups"]
    rep = heads // groups

    def one(state, row):
        xt, bt, ct, dtt = row
        bh, ch = jnp.repeat(bt, rep, axis=0), jnp.repeat(ct, rep, axis=0)
        state = jnp.exp(dtt * a)[:, None, None] * state \
            + (dtt[:, None] * xt)[:, :, None] * bh[:, None, :]
        return state, jnp.sum(state * ch[:, None, :], axis=-1)

    state = jnp.zeros((heads, cfg["mamba_head_dim"], cfg["ssm_state_size"]),
                      jnp.float32)
    return jax.lax.scan(one, state, (x, b, c, dt))[1]


def mamba(top, lw, x, cfg, q=None):
    """A Mamba-2 layer. x: [S, hidden] of one sequence."""
    s = x.shape[0]
    heads, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    groups, n, k = cfg["n_groups"], cfg["ssm_state_size"], cfg["conv_kernel"]
    d, eps = heads * p, cfg["layer_norm_epsilon"]
    h = _rms(x, lw["norm.weight"], eps)
    zxbcdt = mm(h, lw["mixer.in_proj.weight"], q)
    z, xbc, dt = zxbcdt[:, :d], zxbcdt[:, d:-heads], zxbcdt[:, -heads:]
    seen = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1])), xbc], axis=0)
    w_conv = lw["mixer.conv1d.weight"].astype(jnp.float32)
    xbc = jax.nn.silu(sum(w_conv[j] * seen[j:j + s] for j in range(k))
                      + lw["mixer.conv1d.bias"].astype(jnp.float32))
    xs = xbc[:, :d].reshape(s, heads, p)
    b = xbc[:, d:d + groups * n].reshape(s, groups, n)
    c = xbc[:, d + groups * n:].reshape(s, groups, n)
    dt = jax.nn.softplus(dt + lw["mixer.dt_bias"].astype(jnp.float32))
    a = -jnp.exp(lw["mixer.A_log"].astype(jnp.float32))
    y = recurrence(xs, b, c, dt, a, cfg) \
        + lw["mixer.D"].astype(jnp.float32)[:, None] * xs
    g = (y.reshape(s, d) * jax.nn.silu(z)).reshape(s, groups, -1)
    g = g / jnp.sqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    y = g.reshape(s, d) * lw["mixer.norm.weight"].astype(jnp.float32)
    return x + mm(y, lw["mixer.out_proj.weight"], q)


def attention(top, lw, x, cfg, q=None):
    """The attention layer: grouped-query, causal, no rotary."""
    s = x.shape[0]
    heads, kvh, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    h = _rms(x, lw["norm.weight"], cfg["layer_norm_epsilon"])
    qh = mm(h, lw["mixer.q_proj.weight"], q).reshape(s, heads, hd)
    kh = mm(h, lw["mixer.k_proj.weight"], q).reshape(s, kvh, hd)
    vh = mm(h, lw["mixer.v_proj.weight"], q).reshape(s, kvh, hd)
    rep = heads // kvh
    att = causal_attention(qh, jnp.repeat(kh, rep, axis=1),
                           jnp.repeat(vh, rep, axis=1))      # / sqrt(hd)
    return x + mm(att.reshape(s, heads * hd), lw["mixer.o_proj.weight"], q)


def route(lw, h, cfg, q=None):
    """(chosen [S, k] router outputs, weights [S, k])."""
    scores = jax.nn.sigmoid(mm(h, lw["mixer.gate.weight"], q))
    biased = scores + lw["mixer.gate.e_score_correction_bias"].astype(
        jnp.float32)
    _, chosen = jax.lax.top_k(biased, cfg["num_experts_per_tok"])
    weight = jnp.take_along_axis(scores, chosen, axis=-1)
    weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    return chosen, cfg["routed_scaling_factor"] * weight


def routed_part(lw, h, chosen, weight, cfg, q=None):
    """What a token's chosen experts add that are HELD here: down to the
    latent width, each held expert weighted, up again."""
    held, first = cfg["n_routed_experts"], cfg["first_expert"]
    lat = mm(h, lw["mixer.fc1_latent_proj.weight"], q)
    # [S, held]: the weight of each held expert for each token
    mine = jnp.sum(jnp.where(
        chosen[:, :, None] == first + jnp.arange(held)[None, None, :],
        weight[:, :, None], 0.0), axis=1)

    def one(acc, ew):
        up, down, w_e = ew
        return acc + w_e[:, None] * mm(_relu2(mm(lat, up, q)), down, q), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(lat),
                        (lw["mixer.experts.up_proj"],
                         lw["mixer.experts.down_proj"], mine.T))
    return mm(y, lw["mixer.fc2_latent_proj.weight"], q)


def shared_part(lw, h, cfg, q=None):
    """The shared expert, in the full width."""
    return mm(_relu2(mm(h, lw["mixer.shared_experts.up_proj.weight"], q)),
              lw["mixer.shared_experts.down_proj.weight"], q)


def moe(top, lw, x, cfg, q=None):
    """The expert layer, this chip's share of it."""
    h = _rms(x, lw["norm.weight"], cfg["layer_norm_epsilon"])
    chosen, weight = route(lw, h, cfg, q)
    return x + routed_part(lw, h, chosen, weight, cfg, q) \
        + shared_part(lw, h, cfg, q)


def head(top, x, cfg, q=None):
    x = _rms(x, top["backbone.norm_f.weight"], cfg["layer_norm_epsilon"])
    return mm(x, top["lm_head.weight"].T, q)
