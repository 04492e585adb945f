"""Nemotron-H: the hybrid decoder of NVIDIA-Nemotron-3-Super-120B-A12B
(huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16, ``model_type``
``nemotron_h``, and the modelling code published with it).

Every layer is ``h + part(RMSNorm(h))`` with ONE part, named by its letter in
``hybrid_override_pattern`` (the first ``num_hidden_layers`` letters)::

    M  Mamba-2 mixer. in_proj -> z | xBC | dt; a causal depthwise convolution
       of width ``conv_kernel`` with bias over xBC, then SiLU; xBC -> x
       [heads, head_dim] | B [groups, state] | C [groups, state];
       dt = softplus(dt + dt_bias), A = -exp(A_log), both a head; for head h
       of group g = h // (heads / groups), S in R^(head_dim x state):
           S_t = exp(dt_t A) S_(t-1) + dt_t x_t (x) B_t[g]
           y_t = S_t C_t[g] + D x_t
       y = RMSNorm(y * silu(z)) in ``n_groups`` groups with one scale (the
       gate first, then the norm); out_proj.
    *  attention. q, k, v without bias, grouped-query causal softmax at
       head_dim ** -0.5, o_proj. No rotary is applied.
    E  LatentMoE. Router in float32 over the full width, sigmoid scores; the
       ``num_experts_per_tok`` largest of score + bias are chosen, weighted by
       their UNBIASED scores normalised to sum to one, times
       ``routed_scaling_factor``; a shared down-projection to
       ``moe_latent_size``, each expert ``W2 relu(W1 x)^2`` in that width, the
       weighted sum, a shared up-projection; plus one shared expert,
       ``relu2`` too, in the full width.

One chip of an expert-parallel deployment holds ``experts_held`` routed
experts, a contiguous range of ids from ``first_expert``: the router keeps
its published width, the sum over routed experts runs over the held ones, and
what the others would add is left out. The shared expert and both latent
projections are whole on every chip.

The functions below are the model's mathematics over plain arrays;
``forward`` runs them over whole sequences from a zero state, with every
held expert on every token (weights of nought where not chosen). Serving
goes through ``generation._NemotronHDecoder`` (the recurrent state in a pool
beside the pages, ``kernels.ssm_pallas``; routed dispatch through
``kernels.grouped_experts_pallas``), which ``_decoder_for`` picks by this
class. The multi-token-prediction module of the published model is a drafter
and is not built.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .. import nn
from ..distributed.fleet.meta_parallel import VocabParallelEmbedding
from ..ops.dispatch import dispatch

NEG_INF = -1e30
_PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
            "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")


@dataclass
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 4096
    num_hidden_layers: int = 88
    hybrid_override_pattern: str = _PATTERN
    layer_norm_epsilon: float = 1e-5
    max_position_embeddings: int = 262144
    # M
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    # *
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    # E
    n_routed_experts: int = 512        # the router's outputs
    num_experts_per_tok: int = 22
    moe_latent_size: int = 1024
    moe_intermediate_size: int = 2688
    moe_shared_expert_intermediate_size: int = 5376
    routed_scaling_factor: float = 5.0
    norm_topk_prob: bool = True
    experts_held: int = 512            # routed experts this chip holds
    first_expert: int = 0              # id of the first of them

    @property
    def pattern(self):
        """The kind of each layer that is built: ``M``, ``*`` or ``E``."""
        p = self.hybrid_override_pattern[:self.num_hidden_layers]
        if len(p) != self.num_hidden_layers or set(p) - set("M*E"):
            raise ValueError(
                f"hybrid_override_pattern {self.hybrid_override_pattern!r} "
                f"does not name {self.num_hidden_layers} layers of M, * or E")
        return p

    @property
    def d_inner(self):
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self):
        """The convolution's channels: x, then B, then C."""
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def rms_norm_eps(self):            # what the decoders call it
        return self.layer_norm_epsilon

    @staticmethod
    def tiny(vocab_size=256, layers=4, experts_held=16, first_expert=0,
             seq=128, pattern="ME*E"):
        """Every kind of layer at a size the CPU runs: 8 Mamba heads of 8
        in 2 groups over a state of 16, 4 query heads on 1 KV head, 16
        routed experts of which 4 a token, in a latent width of half the
        hidden one."""
        return NemotronHConfig(
            vocab_size=vocab_size, hidden_size=32, num_hidden_layers=layers,
            hybrid_override_pattern=pattern, max_position_embeddings=seq,
            mamba_num_heads=8, mamba_head_dim=8, ssm_state_size=16,
            n_groups=2, num_attention_heads=4, num_key_value_heads=1,
            head_dim=8, n_routed_experts=16, num_experts_per_tok=4,
            moe_latent_size=16, moe_intermediate_size=24,
            moe_shared_expert_intermediate_size=48,
            experts_held=experts_held, first_expert=first_expert)


# -- the mathematics, over plain arrays ---------------------------------------
def relu2(x):
    r = jnp.maximum(x.astype(jnp.float32), 0.0)
    return (r * r).astype(x.dtype)


def relu2_ffn(x, w_up, w_down):
    """``W2 relu(W1 x)^2``: no gate."""
    return relu2(x @ w_up) @ w_down


# M ---------------------------------------------------------------------------
def mamba_split(zxbcdt, cfg):
    """in_proj's output [..., d_inner + conv_dim + heads] as (z, xBC, dt)."""
    d, c = cfg.d_inner, cfg.conv_dim
    return zxbcdt[..., :d], zxbcdt[..., d:d + c], zxbcdt[..., d + c:]


def conv_act(taps, w_conv, b_conv):
    """The causal depthwise convolution at one position, then SiLU. taps:
    [..., K, C], the inputs at positions ``t - K + 1 .. t`` (zeros before
    the sequence's start); w_conv: [K, C]; b_conv: [C]. float32 inside."""
    f32 = jnp.float32
    u = jnp.sum(taps.astype(f32) * w_conv.astype(f32), axis=-2) \
        + b_conv.astype(f32)
    return jax.nn.silu(u).astype(taps.dtype)


def ssm_terms(xbc, dt_raw, w, cfg):
    """What the recurrence takes, from the activated xBC [..., conv_dim] and
    the raw dt [..., heads]: (x [..., heads, head_dim]; b, c [..., groups,
    state]; dt, decay [..., heads] float32, ``decay = exp(dt A)``)."""
    f32 = jnp.float32
    d, gs = cfg.d_inner, cfg.n_groups * cfg.ssm_state_size
    lead = xbc.shape[:-1]
    x = xbc[..., :d].reshape(lead + (cfg.mamba_num_heads, cfg.mamba_head_dim))
    b = xbc[..., d:d + gs].reshape(lead + (cfg.n_groups, cfg.ssm_state_size))
    c = xbc[..., d + gs:].reshape(lead + (cfg.n_groups, cfg.ssm_state_size))
    dt = jax.nn.softplus(dt_raw.astype(f32) + w["dt_bias"].astype(f32))
    decay = jnp.exp(-dt * jnp.exp(w["A_log"].astype(f32)))
    return x, b, c, dt, decay


def ssm_row(state, x, b, c, dt, decay, cfg):
    """One token of the recurrence, every head: state [heads, head_dim,
    state size] float32 -> (state', y [heads, head_dim] float32)."""
    f32 = jnp.float32
    rep = cfg.mamba_num_heads // cfg.n_groups
    bh = jnp.repeat(b.astype(f32), rep, axis=0)           # [heads, state]
    ch = jnp.repeat(c.astype(f32), rep, axis=0)
    state = decay[:, None, None] * state \
        + (dt[:, None] * x.astype(f32))[:, :, None] * bh[:, None, :]
    return state, jnp.sum(state * ch[:, None, :], axis=-1)


def gated_norm(y, z, w_norm, cfg):
    """``RMSNorm(y * silu(z))`` in ``n_groups`` groups of the inner width,
    one scale over all of it. y: float32; returns z's dtype."""
    f32 = jnp.float32
    g = (y.astype(f32) * jax.nn.silu(z.astype(f32)))
    lead = g.shape[:-1]
    g = g.reshape(lead + (cfg.n_groups, -1))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                          + cfg.layer_norm_epsilon)
    return (g.reshape(lead + (-1,)) * w_norm.astype(f32)).astype(z.dtype)


def mamba2_mixer(x, w, cfg, conv_tail, state):
    """The Mamba-2 mixer over the rows of ONE sequence, in order. x: [S,
    hidden], normed; w: the mixer's leaves by their short names; conv_tail:
    [K - 1, conv_dim], the convolution's inputs at the K - 1 positions
    before the first row (zeros at a sequence's start); state: [heads,
    head_dim, state size] float32. Returns (y [S, hidden], conv_tail',
    state'): what the next rows of the sequence start from."""
    k = cfg.conv_kernel
    z, xbc, dt_raw = mamba_split(x @ w["in_proj.weight"], cfg)
    seen = jnp.concatenate([conv_tail.astype(xbc.dtype), xbc], axis=0)
    taps = jnp.stack([seen[j:j + x.shape[0]] for j in range(k)], axis=1)
    xs, b, c, dt, decay = ssm_terms(
        conv_act(taps, w["conv1d.weight"], w["conv1d.bias"]), dt_raw, w, cfg)
    state, y = jax.lax.scan(
        lambda s, r: ssm_row(s, *r, cfg), state.astype(jnp.float32),
        (xs, b, c, dt, decay))
    y = y + w["D"].astype(jnp.float32)[:, None] * xs.astype(jnp.float32)
    y = gated_norm(y.reshape(x.shape[0], -1), z, w["norm.weight"], cfg)
    return y @ w["out_proj.weight"], seen[-(k - 1):], state


# * ---------------------------------------------------------------------------
def attention_rows(x, w, cfg):
    """The projections of the attention layer on normed rows x [...,
    hidden]: (q [..., heads, head_dim], k, v [..., kv heads, head_dim]). No
    rotary: position enters through the causal mask alone."""
    lead, hd = x.shape[:-1], cfg.head_dim
    return ((x @ w["q_proj.weight"]).reshape(
        lead + (cfg.num_attention_heads, hd)),
        (x @ w["k_proj.weight"]).reshape(
            lead + (cfg.num_key_value_heads, hd)),
        (x @ w["v_proj.weight"]).reshape(
            lead + (cfg.num_key_value_heads, hd)))


def attention_causal(x, w, cfg):
    """The attention layer over ONE whole sequence x [S, hidden]."""
    s = x.shape[0]
    f32 = jnp.float32
    q, k, v = attention_rows(x, w, cfg)
    rep = cfg.num_attention_heads // cfg.num_key_value_heads
    qg = q.reshape(s, cfg.num_key_value_heads, rep, cfg.head_dim)
    scores = jnp.einsum("sgrd,tgd->grst", qg.astype(f32), k.astype(f32)) \
        * cfg.head_dim ** -0.5
    prob = jax.nn.softmax(
        jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, NEG_INF), axis=-1)
    out = jnp.einsum("grst,tgd->sgrd", prob, v.astype(f32)).astype(x.dtype)
    return out.reshape(s, -1) @ w["o_proj.weight"]


# E ---------------------------------------------------------------------------
def route(h, w_router, bias, cfg):
    """h: [T, hidden]. Returns (chosen [T, k] int32, the router outputs
    chosen; weights [T, k] float32): sigmoid scores in float32 over every
    output, the k largest of score + bias, each weighted by its UNBIASED
    score, normalised over the k to sum to one, times the scaling factor."""
    f32 = jnp.float32
    scores = jax.nn.sigmoid(jnp.matmul(h, w_router,
                                       preferred_element_type=f32))
    _, chosen = jax.lax.top_k(scores + bias.astype(f32),
                              cfg.num_experts_per_tok)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg.norm_topk_prob:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return chosen.astype(jnp.int32), cfg.routed_scaling_factor * weights


def held_weights(chosen, weights, cfg):
    """The routing as dense weights [T, experts_held]: the weight of each
    held expert for each token, nought where not chosen."""
    local = chosen - cfg.first_expert
    return jnp.sum(
        jnp.where(local[..., None] == jnp.arange(cfg.experts_held), 1.0, 0.0)
        * weights[..., None], axis=1)


def routed_dense(lat, held, w_up, w_down):
    """The held experts' weighted sum in the latent width, every held
    expert on every token: the plain form the routed dispatch has to equal.
    lat: [T, latent]; held: [T, experts_held]; w_up: [held, latent, width];
    w_down: [held, width, latent]. float32."""
    def one(acc, ew):
        up, down, weight = ew
        return acc + weight[:, None] * relu2_ffn(lat, up, down) \
            .astype(jnp.float32), None

    y, _ = jax.lax.scan(one, jnp.zeros(lat.shape, jnp.float32),
                        (w_up, w_down, held.T))
    return y


def latent_moe(h, w, cfg):
    """The expert layer on h [T, hidden], this chip's share of it: the held
    experts' part in the latent width, projected up, plus the shared
    expert. w: the layer's leaves by their short names."""
    chosen, weights = route(h, w["gate.weight"],
                            w["gate.e_score_correction_bias"], cfg)
    lat = h @ w["fc1_latent_proj.weight"]
    y = routed_dense(lat, held_weights(chosen, weights, cfg),
                     w["experts.up_proj"], w["experts.down_proj"])
    return y.astype(h.dtype) @ w["fc2_latent_proj.weight"] + relu2_ffn(
        h, w["shared_experts.up_proj.weight"],
        w["shared_experts.down_proj.weight"])


# -- the layers ----------------------------------------------------------------
def _matrix(layer, shape):
    return layer.create_parameter(
        shape=list(shape), default_initializer=nn.initializer.Normal(0.0, 0.02))


class _Part(nn.Layer):
    """A layer's one part: its leaves by their short names, and the plain
    function that computes it over one sequence."""

    def leaves(self):
        return dict(self.named_parameters())


class NemotronHMamba2Mixer(_Part):
    def __init__(self, config: NemotronHConfig):
        super().__init__()
        c = self.config = config
        self.in_proj = nn.Linear(
            c.hidden_size, c.d_inner + c.conv_dim + c.mamba_num_heads,
            bias_attr=False)
        # depthwise, [K, channels]: row j multiplies the input K - 1 - j back
        self.conv1d = nn.Layer()
        self.conv1d.weight = _matrix(self.conv1d, (c.conv_kernel, c.conv_dim))
        self.conv1d.bias = self.conv1d.create_parameter(
            shape=[c.conv_dim], is_bias=True)
        self.A_log = self.create_parameter(shape=[c.mamba_num_heads],
                                           is_bias=True)
        self.D = self.create_parameter(shape=[c.mamba_num_heads], is_bias=True)
        self.dt_bias = self.create_parameter(shape=[c.mamba_num_heads],
                                             is_bias=True)
        self.norm = nn.RMSNorm(c.d_inner, epsilon=c.layer_norm_epsilon)
        self.out_proj = nn.Linear(c.d_inner, c.hidden_size, bias_attr=False)

    def one(self, x, w):
        cfg = self.config
        tail = jnp.zeros((cfg.conv_kernel - 1, cfg.conv_dim), x.dtype)
        state = jnp.zeros((cfg.mamba_num_heads, cfg.mamba_head_dim,
                           cfg.ssm_state_size), jnp.float32)
        return mamba2_mixer(x, w, cfg, tail, state)[0]


class NemotronHAttention(_Part):
    def __init__(self, config: NemotronHConfig):
        super().__init__()
        c = self.config = config
        kv = c.num_key_value_heads * c.head_dim
        self.q_proj = nn.Linear(c.hidden_size,
                                c.num_attention_heads * c.head_dim,
                                bias_attr=False)
        self.k_proj = nn.Linear(c.hidden_size, kv, bias_attr=False)
        self.v_proj = nn.Linear(c.hidden_size, kv, bias_attr=False)
        self.o_proj = nn.Linear(c.num_attention_heads * c.head_dim,
                                c.hidden_size, bias_attr=False)

    def one(self, x, w):
        return attention_causal(x, w, self.config)


class NemotronHMoE(_Part):
    def __init__(self, config: NemotronHConfig):
        super().__init__()
        c = self.config = config
        h, lat = c.hidden_size, c.moe_latent_size
        self.gate = nn.Layer()
        self.gate.weight = _matrix(self.gate, (h, c.n_routed_experts))
        self.gate.e_score_correction_bias = self.gate.create_parameter(
            shape=[c.n_routed_experts], is_bias=True)
        self.fc1_latent_proj = nn.Linear(h, lat, bias_attr=False)
        self.fc2_latent_proj = nn.Linear(lat, h, bias_attr=False)
        # the held experts' weights as two banks
        self.experts = nn.Layer()
        self.experts.up_proj = _matrix(
            self.experts, (c.experts_held, lat, c.moe_intermediate_size))
        self.experts.down_proj = _matrix(
            self.experts, (c.experts_held, c.moe_intermediate_size, lat))
        self.shared_experts = nn.Layer()
        self.shared_experts.up_proj = nn.Linear(
            h, c.moe_shared_expert_intermediate_size, bias_attr=False)
        self.shared_experts.down_proj = nn.Linear(
            c.moe_shared_expert_intermediate_size, h, bias_attr=False)

    def one(self, x, w):
        return latent_moe(x, w, self.config)


_PARTS = {"M": NemotronHMamba2Mixer, "*": NemotronHAttention,
          "E": NemotronHMoE}
_SCOPES = {"M": "ssm", "*": "attention", "E": "moe"}


class NemotronHBlock(nn.Layer):
    """``h + mixer(norm(h))``, the mixer of the kind the pattern names."""

    def __init__(self, config: NemotronHConfig, kind: str):
        super().__init__()
        self.kind = kind
        self.norm = nn.RMSNorm(config.hidden_size,
                               epsilon=config.layer_norm_epsilon)
        self.mixer = _PARTS[kind](config)

    def forward(self, h):
        names, ws = zip(*self.mixer.leaves().items())
        with jax.named_scope(_SCOPES[self.kind]):
            return h + dispatch(
                "nemotron_h_" + _SCOPES[self.kind],
                lambda x, *ws: jax.vmap(
                    lambda row: self.mixer.one(row, dict(zip(names, ws))))(x),
                self.norm(h), *ws)


class NemotronHModel(nn.Layer):
    def __init__(self, config: NemotronHConfig):
        super().__init__()
        self.config = config
        self.embeddings = VocabParallelEmbedding(config.vocab_size,
                                                 config.hidden_size)
        self.layers = nn.LayerList([NemotronHBlock(config, kind)
                                    for kind in config.pattern])
        self.norm_f = nn.RMSNorm(config.hidden_size,
                                 epsilon=config.layer_norm_epsilon)

    def forward(self, input_ids):
        with jax.named_scope("embed"):
            h = self.embeddings(input_ids)
        for layer in self.layers:
            h = layer(h)
        return self.norm_f(h)


class NemotronHForCausalLM(nn.Layer):
    """The trunk (``backbone``, as published) and an untied head,
    ``lm_head.weight`` [vocab, hidden]. ``forward`` is causal over whole
    rows from a zero state; no pipeline or tensor-parallel protocol is
    offered, and no backward pass through the recurrence is tested."""

    def __init__(self, config: NemotronHConfig):
        super().__init__()
        self.config = config
        self.backbone = NemotronHModel(config)
        self.lm_head = nn.Layer()
        self.lm_head.weight = _matrix(
            self.lm_head, (config.vocab_size, config.hidden_size))

    def forward(self, input_ids, attention_mask=None):
        if attention_mask is not None:
            raise NotImplementedError(
                "NemotronHForCausalLM.forward is causal over whole rows; "
                "the serving engine takes ragged batches")
        h = self.backbone(input_ids)
        with jax.named_scope("head"):
            return dispatch("nemotron_h_head", lambda h, w: h @ w.T, h,
                            self.lm_head.weight)

    def generate(self, input_ids, attention_mask=None, **kwargs):
        from ..generation import generate
        return generate(self, input_ids, attention_mask=attention_mask,
                        **kwargs)
