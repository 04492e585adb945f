"""Plain reference of LongCat-Flash, the language model of LongCat-Flash-Omni
(Meituan, huggingface.co/meituan-longcat/LongCat-Flash-Omni; LongCat-Flash
Technical Report, arXiv:2509.01322, and the ``longcat_flash`` modelling code
published with it), as one chip's share of its experts. float32
``jax.numpy``, one sequence at a time, attention UNABSORBED (every head's
keys and values are formed from the latent; ``q~`` is never formed), a Python
loop over a token's chosen experts.

    one double layer (the walk's one stop, ``block``):
      for i in (0, 1):
          a   = x + MLA_i(RMSNorm(x))
          h_i = RMSNorm(a)
          if i == 0:  m = MoE(h_0)            # the shortcut, from the FIRST half
          x   = a + FFN_i(h_i)                # W_down(silu(W_gate h) * W_up h)
      x = x + m                               # joins after the SECOND half

    MLA, a token at position p:
      c_q = RMSNorm(W_qa x);  [q_nope, q_rope]_h = (W_qb c_q)_h * s_q
      [c_kv, k_rope] = W_kva x;  c = RMSNorm(c_kv) * s_kv
      [k_nope, v]_h = (W_kvb c)_h;  rope on q_rope_h and k_rope
      score_h(p, s) = (q_nope_h . k_nope_h(s) + q_rope_h . k_rope(s)) / sqrt(nope + rope)
      out = W_o concat_h(sum_s softmax_s(score_h)(p, s) v_h(s))
      s_q = sqrt(hidden / q_lora_rank), s_kv = sqrt(hidden / kv_lora_rank)

    MoE(h):
      s = softmax(W_r h) over all the router's outputs     # routed, then zero
      chosen = the moe_topk largest of s + b               # b for the choice only
      w_e = routed_scaling_factor * s_e, not renormalised
      y = sum_{e chosen, held here} w_e E_e(h) + sum_{e chosen, zero} w_e h

The configuration's ``n_routed_experts`` counts the routed experts held here,
ids ``first_expert ..``; the router's width is read off its weight, so its
routed outputs are its width less ``zero_expert_num``. What the routed
experts held elsewhere would add is left out, here as in the program.

Departures from the published code: rotary pairs are the neighbouring
channels (2i, 2i+1) as the published MLA turns them; weights are [in, out]
matrices but ``lm_head.weight`` [vocab, hidden] as published; the held
experts are three banks ``[held, in, out]``.

The expert layer is three functions, ``route``, ``held_part`` and
``zero_part``, so that ``bench/tools/longcat_faults.py`` can put a wrong one
in a part's place and show that the comparison sees each part; nothing here
knows of it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import causal_attention, mm
from .llama_block import _rms, _rope

def embed(top, ids, cfg):
    return top["model.embed_tokens.weight"].astype(jnp.float32)[ids]


def mla(lw, pre, x, cfg, q=None):
    """One latent-attention block on a normed x: [S, hidden]."""
    s, hidden = x.shape
    heads, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rope, v = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    kl = cfg["kv_lora_rank"]
    s_q = (hidden / cfg["q_lora_rank"]) ** 0.5 if cfg["mla_scale_q_lora"] else 1.0
    s_kv = (hidden / kl) ** 0.5 if cfg["mla_scale_kv_lora"] else 1.0
    c_q = _rms(mm(x, lw[pre + "q_a_proj.weight"], q),
               lw[pre + "q_a_layernorm.weight"], eps)
    qh = mm(c_q, lw[pre + "q_b_proj.weight"], q).reshape(s, heads, nope + rope) * s_q
    kv = mm(x, lw[pre + "kv_a_proj_with_mqa.weight"], q)
    c = _rms(kv[:, :kl], lw[pre + "kv_a_layernorm.weight"], eps) * s_kv
    kvh = mm(c, lw[pre + "kv_b_proj.weight"], q).reshape(s, heads, nope + v)
    q_rope = _rope(qh[..., nope:], cfg["rope_theta"])
    k_rope = _rope(kv[:, None, kl:], cfg["rope_theta"])          # [S, 1, rope]
    qh = jnp.concatenate([qh[..., :nope], q_rope], axis=-1)
    kh = jnp.concatenate([kvh[..., :nope],
                          jnp.broadcast_to(k_rope, (s, heads, rope))], axis=-1)
    att = causal_attention(qh, kh, kvh[..., nope:])              # / sqrt(nope + rope)
    return mm(att.reshape(s, heads * v), lw[pre + "o_proj.weight"], q)


def ffn(x, gate, up, down, q=None):
    return mm(jax.nn.silu(mm(x, gate, q)) * mm(x, up, q), down, q)


def route(lw, h, cfg, q=None):
    """(chosen [S, k] router outputs, weights [S, k])."""
    scores = jax.nn.softmax(mm(h, lw["mlp.router.classifier.weight"], q), axis=-1)
    biased = scores + lw["mlp.router.e_score_correction_bias"].astype(jnp.float32)
    _, chosen = jax.lax.top_k(biased, cfg["moe_topk"])
    return chosen, cfg["routed_scaling_factor"] * jnp.take_along_axis(
        scores, chosen, axis=-1)


def held_part(lw, h, chosen, weight, cfg, q=None):
    """What a token's chosen experts add that are HELD here."""
    held, first = cfg["n_routed_experts"], cfg["first_expert"]
    outs = [ffn(h, lw["mlp.experts.gate_proj"][e], lw["mlp.experts.up_proj"][e],
                lw["mlp.experts.down_proj"][e], q) for e in range(held)]
    y = jnp.zeros_like(h)
    for j in range(cfg["moe_topk"]):          # a token's chosen experts, in turn
        for e in range(held):
            y = y + jnp.where(chosen[:, j] == first + e, weight[:, j],
                              0.0)[:, None] * outs[e]
    return y


def zero_part(lw, h, chosen, weight, cfg):
    """What a token's chosen ZERO experts add: its input, once for each."""
    routed = lw["mlp.router.classifier.weight"].shape[1] - cfg["zero_expert_num"]
    y = jnp.zeros_like(h)
    for j in range(cfg["moe_topk"]):
        y = y + jnp.where(chosen[:, j] >= routed, weight[:, j], 0.0)[:, None] * h
    return y


def moe(lw, h, cfg, q=None):
    """The expert layer on h: [S, hidden], this chip's share of it."""
    chosen, weight = route(lw, h, cfg, q)
    return held_part(lw, h, chosen, weight, cfg, q) \
        + zero_part(lw, h, chosen, weight, cfg)


def block(top, lw, x, cfg, q=None):
    """One double layer. x: [S, hidden] of one sequence; ``top`` is not
    read."""
    eps = cfg["rms_norm_eps"]
    shortcut = None
    for i in (0, 1):
        a = x + mla(lw, f"self_attn.{i}.", _rms(
            x, lw[f"input_layernorm.{i}.weight"], eps), cfg, q)
        h = _rms(a, lw[f"post_attention_layernorm.{i}.weight"], eps)
        if i == 0:
            shortcut = moe(lw, h, cfg, q)
        x = a + ffn(h, lw[f"mlps.{i}.gate_proj.weight"],
                    lw[f"mlps.{i}.up_proj.weight"],
                    lw[f"mlps.{i}.down_proj.weight"], q)
    return x + shortcut


def head(top, x, cfg, q=None):
    x = _rms(x, top["model.norm.weight"], cfg["rms_norm_eps"])
    return mm(x, top["lm_head.weight"].T, q)
