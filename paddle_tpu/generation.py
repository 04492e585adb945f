"""Autoregressive decoding with KV caches — the serving decode path.

Reference parity (capability): the fused decode attention kernels
(`phi/kernels/fusion/gpu/masked_multihead_attention_kernel.cu`,
`block_multi_head_attention_kernel.cu`) plus the PaddleNLP-style
`generate()` loop the inference engine serves. TPU-native design: decode is
inference-only, so it does NOT thread Tensor tape nodes through the eager
layers — the whole generation (prefill + every decode step + sampling) is
ONE jitted XLA program over the model's weight arrays:

  * preallocated per-layer KV caches [B, max_len, kv_heads, hd], appended
    with `lax.dynamic_update_slice` (static shapes, no recompilation per
    step);
  * the decode loop is `lax.fori_loop` with a static trip count — finished
    rows keep computing but their tokens are masked to pad (data-dependent
    early exit would break XLA's static control flow);
  * left-padded ragged prompts: per-row positions from the attention mask
    drive both the rope rotation and the causal/padding score mask;
  * sampling (greedy / temperature / top-k / top-p) happens on-device with
    the framework PRNG.

Numerics are parity-tested against the training forward
(tests/test_generation.py): a cached decode step must reproduce the
full-recompute logits exactly.
"""
from __future__ import annotations

from collections import OrderedDict
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .tensor import Tensor

NEG_INF = -1e30


# -- weight-only int8 (serving quantization) ----------------------------------
#
# Reference capability: ops.yaml `weight_quantize` / `weight_only_linear` —
# the llm_int8 serving path. TPU-native design: the quantized matrix rides in
# the weights pytree as two leaves (`name::q` int8 [in, out], `name::s` fp32
# per-output-channel scale) and the matmul becomes (x @ q.astype(x.dtype)) * s.
# XLA fuses the int8→bf16 convert into the dot's operand read, so the decode
# loop — which is HBM-bandwidth-bound on every weight matrix — reads half the
# bytes; the per-column scale is applied to the [B, S, out] result, which is
# mathematically identical to scaling the matrix (sum_i x_i q_ij s_j).

from .quantization._kernels import (ALGO_BITS as _QUANT_BITS,
                                    quant_matmul_arrays as _qmm,
                                    quantize_weight_arrays as _wq)


def _quant_leaves(src, names, lm_from_embed=None, bits=8):
    """Quantize each 2-D matmul weight in `names` to ::q/::s leaves; when
    `lm_from_embed` is set (tied head), add __lm::q/__lm::s from embed.T so
    the [H, V] logits matmul also reads narrow ints while the embedding
    GATHER keeps the original-precision table (it reads B rows, not V*H)."""
    leaves = {}
    for n in names:
        q, s = _wq(src[n], bits=bits)
        leaves[n + "::q"] = q
        leaves[n + "::s"] = s
    if lm_from_embed is not None:
        q, s = _wq(src[lm_from_embed].T, bits=bits)
        leaves["__lm::q"] = q
        leaves["__lm::s"] = s
    return leaves


def _mm(x, w, name):
    """x @ weight, transparently reading the int8 form when present."""
    q = w.get(name + "::q")
    if q is None:
        return x @ w[name]
    return _qmm(x, q, w[name + "::s"])


def _head_logits(w, h, tied, embed_key):
    """The LM-head matmul, shared by both decoders: quantized tied head
    (__lm leaves) > fp tied head (embed.T) > (possibly quantized) lm_head."""
    if "__lm::q" in w:
        return _qmm(h, w["__lm::q"], w["__lm::s"])
    if tied:
        return h @ w[embed_key].T
    return _mm(h, w, "lm_head.weight")


def _quant_weights_cached(dec, model, quant):
    """Build the decode pytree: live fp leaves (re-read from the model on
    EVERY call — norms/biases/embeddings are never cached) + int8/scale
    leaves for the matmul weights, quantized once per weight snapshot.
    The cache holds WEAKREFS to the source matmul arrays (invalidate when
    a training step / load_dict swaps any of them) and strong refs ONLY
    to the int8 copies — its payload, which persists until the next quant
    generate; superseded fp arrays are never pinned."""
    import weakref
    src = dec.weights(model)
    names, lm_key = dec.quant_plan()
    watched = names if lm_key is None else [*names, lm_key]
    cache = model.__dict__.setdefault("_quant_weights_cache", {})
    leaves = None
    cached = cache.get(quant)   # keyed per algo: int8/int4 coexist
    if cached is not None:
        prev_refs, prev_leaves = cached
        if list(prev_refs) == watched and \
                all(prev_refs[k]() is src[k] for k in watched):
            leaves = prev_leaves
    if leaves is None:
        leaves = _quant_leaves(src, names, lm_from_embed=lm_key,
                               bits=_QUANT_BITS[quant])
        cache[quant] = ({k: weakref.ref(src[k]) for k in watched}, leaves)
    drop = set(names)
    w = {k: v for k, v in src.items() if k not in drop}
    w.update(leaves)
    return w


# -- pure llama math over weight arrays ---------------------------------------

def _rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(ms + eps) * w.astype(jnp.float32)) \
        .astype(x.dtype)


def _rope_rows(x, cos, sin):
    """Rotate pairs with PER-ROW position tables. x: [B, S, H, D];
    cos/sin: [B, S, D/2] (already gathered at each row's positions)."""
    x1 = x[..., 0::2]
    x2 = x[..., 1::2]
    c = cos[:, :, None, :]
    s = sin[:, :, None, :]
    ro1 = x1 * c - x2 * s
    ro2 = x2 * c + x1 * s
    return jnp.stack([ro1, ro2], axis=-1).reshape(x.shape).astype(x.dtype)


def _attend(q, k, v, score_mask):
    """q: [B, S, H, D]; k/v: [B, T, H, D]; score_mask: [B, 1, S, T] bool
    (True = visible). Returns [B, S, H, D]."""
    d = q.shape[-1]
    scores = jnp.einsum("bshd,bthd->bhst", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / np.sqrt(d)
    scores = jnp.where(score_mask, scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhst,bthd->bshd", p, v.astype(jnp.float32)) \
        .astype(q.dtype)


def _attend_gqa(q, k, v, score_mask, rep):
    """Grouped-query attention without expanding the KV cache. q:
    [B, S, G*rep, D]; k/v: [B, T, G, D]; score_mask: [B, 1, S, T].
    Returns [B, S, G*rep, D]."""
    b, s, h, d = q.shape
    g = h // rep
    qg = q.reshape(b, s, g, rep, d)
    scores = jnp.einsum("bsgrd,btgd->bgrst", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / np.sqrt(d)
    scores = jnp.where(score_mask[:, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bgrst,btgd->bsgrd", p, v.astype(jnp.float32))
    return out.reshape(b, s, h, d).astype(q.dtype)


@jax.named_scope("kv_write")
def _join_entries(k_pools, v_pools):
    """Stacked pools [E, P, kvh, bs, D] as one run of pages [E * P, kvh, bs,
    D] each, entry ``e`` at pages ``e * P ..`` (no element moves): the form
    every ragged step threads through its layers, writes in place and
    attends from."""
    return _join_entry_pages(k_pools), _join_entry_pages(v_pools)


def _join_entry_pages(pools):
    return pools.reshape((pools.shape[0] * pools.shape[1],) + pools.shape[2:])


@jax.named_scope("kv_write")
def _page_plan(scatter, bs):
    """For a step that writes whole pages: which of the step's rows lands in
    each slot of each row's page. scatter = (pages [T], offs [T]). Returns
    (hit [T, bs] bool, src [T, bs] int32): slot ``o`` of row ``r``'s page
    takes row ``src[r, o]``'s K/V where ``hit[r, o]``. Rows of one page get
    equal plans, so writing the page once a row writes one content."""
    pages, offs = scatter
    lands = (pages[:, None, None] == pages[None, :, None]) \
        & (offs[None, :, None] == jnp.arange(bs)[None, None, :])   # [T, T', bs]
    return lands.any(1), jnp.argmax(lands, axis=1).astype(jnp.int32)


def _entry_seams(pool_shape, scatter, attend):
    """What a layer needs to work on ONE cache entry of the joined pools.
    pool_shape: the stacked pools' [E, P, kvh, bs, D]; scatter: the step's
    (pages [T], offs [T]) within an entry, page ``P`` a dropped row; attend:
    ``ragged.make_attend``'s callable. Returns ``seam(e)`` -> (the scatter
    ``_kv_write_pages`` takes for entry ``e``, the ``attend(q, kf, vf)``
    that reads entry ``e``); ``e`` may be traced. The page plan is computed
    here, once a step."""
    entry_pages, all_pages = pool_shape[1], pool_shape[0] * pool_shape[1]
    pages, _ = scatter
    plan = _page_plan(scatter, pool_shape[3])

    def seam(e):
        first_page = e * entry_pages
        with jax.named_scope("kv_write"):
            # a dropped row (one past the entry) stays dropped
            at = jnp.where(pages >= entry_pages, all_pages,
                           pages + first_page)
        return (at, *plan), partial(attend, first_page=first_page)

    return seam


@jax.named_scope("kv_write")
def _kv_write_pages(kf, vf, k, v, scatter):
    """Put this step's K/V rows ([T, 1, kvh, D]) into the joined pools [N,
    kvh, bs, D], the one write of every decoder: each row's page is read,
    the step's rows are put into its slots and the page is written back
    whole, a page a row and nothing else of the pools moved, so the
    scatter updates the step's donated argument (or a loop's carry) in
    place. A scatter of single rows makes the compiler keep the pools in
    the layout that scatter likes, slots before heads, and copy the whole
    of them into the attention kernel's layout at every call; a page is the
    unit both agree on. scatter = (pages [T] in the joined pools, ``N`` = a
    dropped row; ``_page_plan``'s hit and src). Scope ``kv_write``: with
    ``_join_entries``, ``_page_plan`` and ``_entry_seams``' targets the
    whole cost of keeping the pools, whatever the attention reads."""
    return _write_pages(kf, k, scatter), _write_pages(vf, v, scatter)


def _write_pages(pool, new, scatter):
    """One pool's half of ``_kv_write_pages`` (a latent pool is one)."""
    pages, hit, src = scatter
    at = jnp.minimum(pages, pool.shape[0] - 1)
    rows = new[:, 0][src].transpose(0, 2, 1, 3)          # [T, kvh, bs, D]
    page = jnp.where(hit[:, None, :, None], rows.astype(pool.dtype),
                     pool[at])
    return pool.at[pages].set(page, mode="drop")


@jax.named_scope("kv_write")
def _split_entries(kf, vf, pool_shape):
    """The joined pools back as the [E, P, kvh, bs, D] the engine keeps
    between steps (no element moves)."""
    return kf.reshape(pool_shape), vf.reshape(pool_shape)


class _LlamaDecoder:
    """Pure functions over a LlamaForCausalLM state dict.

    Holds ONLY static config — the weight arrays are a jit ARGUMENT (the
    `w` dict threaded through every method), so the compiled executable
    never closure-captures them: training steps after a generate() don't
    pin superseded arrays, and weight updates need no cache invalidation.
    """

    # what the serving attention takes besides the heads: the scores' scale
    # (None: hd ** -0.5) and the value's width inside a latent cache's row
    # (None: K and V pages per head)
    attn_scale = None
    latent_dim = None

    def __init__(self, model):
        cfg = model.config
        self.cfg = cfg
        self.n_heads = cfg.num_attention_heads
        self.n_kv = cfg.num_key_value_heads or self.n_heads
        self.hd = cfg.hidden_size // self.n_heads
        self.v_dim = self.hd      # a V row's width; 0 where no V is kept
        self.eps = cfg.rms_norm_eps
        # two numbers: layers that hold weights, and K/V cache entries a
        # token keeps (what the caches' and the pools' first axis counts).
        # Equal where every layer runs once a token.
        self.n_layers = cfg.num_hidden_layers
        self.cache_entries = self.n_layers
        self.tied = model.lm_head is None
        self.embed_key = "model.embed_tokens.weight"

    def describe(self):
        """What ``telemetry()["model"]`` says of this decoder beside its
        layers and cache entries: nothing here."""
        return {}

    def beside_width(self, rows):
        """Entries of what ``step_ragged`` returns beside its logits for
        ``rows`` packed rows, as the engine's step program hands them back
        behind the sampled tokens: the counters where the decoder has
        ``COUNTERS``, else none."""
        return len(getattr(self, "COUNTERS", ()))

    def _static_key(self):
        """Everything the traced step() reads off `self` — two decoders
        with equal keys produce identical traces, so they may share jit
        executables (the decoder is a STATIC jit argument)."""
        return (type(self), self.n_heads, self.n_kv, self.hd, self.eps,
                self.n_layers, self.tied, self.embed_key)

    def __hash__(self):
        return hash(self._static_key())

    def __eq__(self, other):
        return (type(other) is type(self)
                and other._static_key() == self._static_key())

    @staticmethod
    def weights(model):
        """The jit-argument pytree: params + buffers + the rope tables."""
        w = {n: t._data for n, t in model.named_state().items()}
        w["__rope_cos"] = model.model.rope_cos._data
        w["__rope_sin"] = model.model.rope_sin._data
        return w

    @staticmethod
    def _lw(w, i, name):
        return w[f"model.layers.{i}.{name}"]

    _QUANT_SUFFIXES = ("self_attn.q_proj.weight", "self_attn.k_proj.weight",
                       "self_attn.v_proj.weight", "self_attn.o_proj.weight",
                       "mlp.gate_proj.weight", "mlp.up_proj.weight",
                       "mlp.down_proj.weight")

    def quant_plan(self):
        """(matmul weight names to quantize, tied-embed key or None)."""
        names = [f"model.layers.{i}.{sfx}" for i in range(self.n_layers)
                 for sfx in self._QUANT_SUFFIXES]
        if not self.tied:
            names.append("lm_head.weight")
        return names, (self.embed_key if self.tied else None)

    def _qkv_proj(self, w, i, x, b, s):
        """Roped q/k/v projections shared by the dense and ragged layers
        (rope applied by the caller, which owns the position tables)."""
        pre = f"model.layers.{i}."
        q = _mm(x, w, pre + "self_attn.q_proj.weight") \
            .reshape(b, s, self.n_heads, self.hd)
        k = _mm(x, w, pre + "self_attn.k_proj.weight") \
            .reshape(b, s, self.n_kv, self.hd)
        v = _mm(x, w, pre + "self_attn.v_proj.weight") \
            .reshape(b, s, self.n_kv, self.hd)
        return q, k, v

    def _branch_out(self, w, i, norm, x):
        """What a branch's output goes through before it joins the
        residual; ``norm`` names the norm that opened the branch. Nothing
        here."""
        return x

    def _post_attn(self, w, i, h, att):
        """Residual + output projection + MLP, shared by both layer paths;
        att: [B, S, H*D]."""
        pre = f"model.layers.{i}."
        with jax.named_scope("attn_proj"):
            h = h + self._branch_out(
                w, i, "input_layernorm",
                _mm(att, w, pre + "self_attn.o_proj.weight"))
        with jax.named_scope("mlp"):
            x2 = _rms(h, self._lw(w, i, "post_attention_layernorm.weight"),
                      self.eps)
            gate = _mm(x2, w, pre + "mlp.gate_proj.weight")
            up = _mm(x2, w, pre + "mlp.up_proj.weight")
            swi = (jax.nn.silu(gate.astype(jnp.float32))
                   .astype(up.dtype) * up)
            return h + self._branch_out(
                w, i, "post_attention_layernorm",
                _mm(swi, w, pre + "mlp.down_proj.weight"))

    def _layer(self, w, i, h, cos, sin, kc, vc, write_pos, score_mask):
        """One decoder layer with cache append; h: [B, S, H*D]."""
        b, s, _ = h.shape
        x = _rms(h, self._lw(w, i, "input_layernorm.weight"), self.eps)
        q, k, v = self._qkv_proj(w, i, x, b, s)
        q = _rope_rows(q, cos, sin)
        k = _rope_rows(k, cos, sin)
        # append to the cache at write_pos (same slot for every row; rows
        # that are still inside their left-padding write garbage that the
        # score mask hides)
        kc = jax.lax.dynamic_update_slice(kc, k.astype(kc.dtype),
                                          (0, write_pos, 0, 0))
        vc = jax.lax.dynamic_update_slice(vc, v.astype(vc.dtype),
                                          (0, write_pos, 0, 0))
        if self.n_kv != self.n_heads:
            # grouped-query attention against the UNEXPANDED cache: no
            # n_heads/n_kv-fold repeat of [B, M, kvh, hd] on the decode
            # hot path
            rep = self.n_heads // self.n_kv
            att = _attend_gqa(q, kc, vc, score_mask, rep) \
                .reshape(b, s, -1)
        else:
            att = _attend(q, kc, vc, score_mask).reshape(b, s, -1)
        return self._post_attn(w, i, h, att), kc, vc

    def _layer_ragged(self, w, i, h, cos, sin, kf, vf, scatter, attend,
                      shard=None):
        """One layer over a PACKED ragged batch (mixed prefill chunks and
        decode tokens from different sequences as a [T, 1, ...] batch),
        working on one cache entry of the joined pools. kf/vf: [E * P, kvh,
        bs, D]; scatter, attend: that entry's pair from ``_entry_seams``:
        the write targets ``_kv_write_pages`` takes, and callable(q [T, H,
        D], kf, vf) -> [T, H, D], the ragged paged attention over the
        entry's pages (paddle_tpu.serving.ragged supplies it); shard: the
        serving engine's tensor-parallel annotator (None = single chip) —
        it pins q/k/v to the per-head layout right after the projection
        and the attention output right before the row-parallel o_proj,
        the same two seams the training side shards."""
        t, s, _ = h.shape
        with jax.named_scope("attn_proj"):
            x = _rms(h, self._lw(w, i, "input_layernorm.weight"), self.eps)
            q, k, v = self._qkv_proj(w, i, x, t, s)
            q = _rope_rows(q, cos, sin)
            k = _rope_rows(k, cos, sin)
            if shard is not None:
                q, k, v = shard.qkv(q, k, v)
        kf, vf = _kv_write_pages(kf, vf, k, v, scatter)
        att = attend(q[:, 0], kf, vf).reshape(t, 1, -1)
        if shard is not None:
            att = shard.att(att)
        return self._post_attn(w, i, h, att), kf, vf

    @jax.named_scope("embed")
    def _embed_ragged(self, w, tokens, positions):
        """The packed rows' embeddings [T, 1, H*D] and rope rows [T, 1,
        hd/2] at their positions."""
        return (w[self.embed_key][tokens][:, None],
                w["__rope_cos"][positions][:, None],
                w["__rope_sin"][positions][:, None])

    def step_ragged(self, w, tokens, positions, k_pools, v_pools, scatter,
                    attend, shard=None):
        """Ragged-batch twin of step(): tokens/positions: [T] packed
        mixed-phase batch (each entry one token of some sequence at its
        absolute position); k_pools/v_pools: [E, P, kvh, bs, D] shared
        block pools, one per cache entry (here one a layer); scatter:
        (pages [T], offs [T]) per-token write targets within an entry
        (page index P == dropped row); attend: ``ragged.make_attend``'s
        callable; shard as in _layer_ragged. The pools are joined into one
        run of pages, threaded through the layers and written in place a
        page a row: no entry is copied out and nothing is stacked again.
        Returns (logits [T, V], exits, k_pools', v_pools'): ``exits`` is
        the pass each row's logits were taken after, [T] int32, where the
        decoder runs its layers several times a token, and None here."""
        h, cos, sin = self._embed_ragged(w, tokens, positions)
        seam = _entry_seams(k_pools.shape, scatter, attend)
        kf, vf = _join_entries(k_pools, v_pools)
        for i in range(self.n_layers):
            h, kf, vf = self._layer_ragged(w, i, h, cos, sin, kf, vf,
                                           *seam(i), shard=shard)
        return (self._logits(w, h)[:, 0], None,
                *_split_entries(kf, vf, k_pools.shape))

    _TP_COL = ("self_attn.q_proj.weight", "self_attn.k_proj.weight",
               "self_attn.v_proj.weight", "mlp.gate_proj.weight",
               "mlp.up_proj.weight")
    _TP_ROW = ("self_attn.o_proj.weight", "mlp.down_proj.weight")

    def tp_specs(self):
        """Per-weight-name PartitionSpec entries (as plain tuples) for
        tensor-parallel serving over an ``mp`` mesh axis: the Megatron
        column/row split at the ``_qkv_proj``/``_post_attn`` seams —
        q/k/v/gate/up shard their OUTPUT dim (per-head / per-neuron, no
        collective), o_proj/down shard their INPUT dim (the psum lands
        on the residual). Names absent from the map stay replicated
        (embeddings, norms, rope tables, lm head)."""
        specs = {}
        for i in range(self.n_layers):
            pre = f"model.layers.{i}."
            for n in self._TP_COL:
                specs[pre + n] = (None, "mp")
            for n in self._TP_ROW:
                specs[pre + n] = ("mp", None)
        return specs

    @jax.named_scope("head")
    def _logits(self, w, h):
        h = _rms(h, w["model.norm.weight"], self.eps)
        return _head_logits(w, h, self.tied, self.embed_key)

    def step(self, w, tokens, positions, kcs, vcs, write_pos, score_mask):
        """tokens: [B, S] int; positions: [B, S] int (rope positions);
        kcs/vcs: [E, B, M, kvh, hd], one cache per cache entry (here one a
        layer); score_mask: [B, 1, S, M].
        Returns (logits [B, S, V], kcs', vcs')."""
        emb = w[self.embed_key]
        h = emb[tokens]
        cos = w["__rope_cos"][positions]      # [B, S, hd/2]
        sin = w["__rope_sin"][positions]
        new_k, new_v = [], []
        for i in range(self.n_layers):
            h, kc, vc = self._layer(w, i, h, cos, sin, kcs[i], vcs[i],
                                    write_pos, score_mask)
            new_k.append(kc)
            new_v.append(vc)
        return self._logits(w, h), jnp.stack(new_k), jnp.stack(new_v)


class _OuroDecoder(_LlamaDecoder):
    """Pure functions over an OuroForCausalLM state dict: the Llama block
    with a norm after each branch as well as before it, the SAME layers run
    ``n_passes`` times a token (the model's one norm after every pass), and
    a gate that picks which pass's state feeds the head.

    Pass ``t`` of layer ``l`` attends to the pass-``t``, layer-``l`` keys of
    earlier tokens, so a token keeps ``n_passes * n_layers`` cache entries
    over ``n_layers`` layers of weights; entry ``(t, l)`` is ``t * n_layers
    + l``. Both step programs loop over the passes (one traced body of
    ``n_layers`` layers, whatever ``n_passes`` is) with the caches in the
    loop's carry. The ragged step carries the stacked pools as one run of
    pages and writes and attends at ``entry * P``: no entry is copied out
    and nothing is stacked again, so no second array of the pools' size
    exists. All passes always run; the exit only selects."""

    def __init__(self, model):
        super().__init__(model)
        self.n_passes = int(self.cfg.total_ut_steps)
        self.cache_entries = self.n_passes * self.n_layers
        self.exit_threshold = float(self.cfg.early_exit_threshold)

    def _static_key(self):
        return super()._static_key() + (self.n_passes, self.exit_threshold)

    def _branch_out(self, w, i, norm, x):
        """Each branch is normed again before it joins the residual:
        ``input_layernorm_2``, ``post_attention_layernorm_2``."""
        return _rms(x, self._lw(w, i, norm + "_2.weight"), self.eps)

    def beside_width(self, rows):
        """One exit pass a row."""
        return rows

    def step_counts(self, passes, rows):
        """``serve.emit``'s arguments from what a step brought back beside
        its tokens: the pass each sampled row left after."""
        return {"exit_pass_sum": int(passes[rows].sum()),
                "exit_rows": len(rows)}

    @jax.named_scope("loop_exit")
    def _pass_end(self, w, h):
        """``model.norm`` on a pass's output: what the next pass starts
        from, and what the gate and the head read."""
        return _rms(h, w["model.norm.weight"], self.eps)

    @jax.named_scope("loop_exit")
    def _exit(self, w, states):
        """states: [n_passes, ..., H] normed pass outputs. Returns (the
        state each row leaves with, the pass it leaves after)."""
        from .models.ouro import exit_select
        return exit_select(states, w["model.early_exit_gate.weight"],
                           w["model.early_exit_gate.bias"],
                           self.exit_threshold)

    @jax.named_scope("head")
    def _logits(self, w, h):
        """The head on an exit state: normed at its pass's end already."""
        return _head_logits(w, h, self.tied, self.embed_key)

    def step_ragged(self, w, tokens, positions, k_pools, v_pools, scatter,
                    attend, shard=None):
        """See _LlamaDecoder.step_ragged; k_pools/v_pools: [n_passes *
        n_layers, P, kvh, bs, D]. ``exits``: [T] int32 in 1..n_passes."""
        h, cos, sin = self._embed_ragged(w, tokens, positions)
        seam = _entry_seams(k_pools.shape, scatter, attend)

        def one_pass(carry, t):
            h, kf, vf = carry
            for i in range(self.n_layers):
                h, kf, vf = self._layer_ragged(
                    w, i, h, cos, sin, kf, vf, *seam(t * self.n_layers + i),
                    shard=shard)
            h = self._pass_end(w, h)
            return (h, kf, vf), h

        (_, kf, vf), states = jax.lax.scan(
            one_pass, (h, *_join_entries(k_pools, v_pools)),
            jnp.arange(self.n_passes, dtype=jnp.int32))
        kf, vf = _split_entries(kf, vf, k_pools.shape)
        h_exit, exit_pass = self._exit(w, states)
        return self._logits(w, h_exit)[:, 0], exit_pass[:, 0], kf, vf

    def step(self, w, tokens, positions, kcs, vcs, write_pos, score_mask):
        """See _LlamaDecoder.step; kcs/vcs: [n_passes * n_layers, B, M, kvh,
        hd]."""
        h = w[self.embed_key][tokens]
        cos = w["__rope_cos"][positions]      # [B, S, hd/2]
        sin = w["__rope_sin"][positions]

        def one_pass(carry, t):
            h, kcs, vcs = carry
            for i in range(self.n_layers):
                e = t * self.n_layers + i
                h, kc, vc = self._layer(
                    w, i, h, cos, sin,
                    jax.lax.dynamic_index_in_dim(kcs, e, keepdims=False),
                    jax.lax.dynamic_index_in_dim(vcs, e, keepdims=False),
                    write_pos, score_mask)
                kcs = jax.lax.dynamic_update_index_in_dim(kcs, kc, e, 0)
                vcs = jax.lax.dynamic_update_index_in_dim(vcs, vc, e, 0)
            h = self._pass_end(w, h)
            return (h, kcs, vcs), h

        (_, kcs, vcs), states = jax.lax.scan(
            one_pass, (h, kcs, vcs),
            jnp.arange(self.n_passes, dtype=jnp.int32))
        return self._logits(w, self._exit(w, states)[0]), kcs, vcs


class _LongcatDecoder(_LlamaDecoder):
    """Pure functions over a LongcatFlashForCausalLM state dict
    (``models/longcat_flash.py`` has the equations): a DOUBLE layer with two
    latent-attention blocks, two dense FFNs and one expert layer whose
    output skips over the second half.

    The cache is a LATENT one: a token keeps, for each of the ``2 *
    n_layers`` attention blocks, one row of ``row_dim`` numbers: the normed,
    rescaled latent (``latent_dim``), the roped key all heads share, and
    zeros up to whole lane tiles (512 + 64 -> 640). There is no V cache
    (``v_dim`` 0: the pools' and caches' V side is zero wide): the value is
    the row's first ``latent_dim`` columns. Attention runs ABSORBED, in the
    prefill rows of a step as in its decode rows: ``kv_b_proj``'s key part is
    folded into the query and its value part into the output, so every head
    attends to the one row (``n_kv`` 1).

    The expert layer routes in float32 over every output of the router,
    sorts the (token, expert) pairs that fall on the held experts into tiles
    of one expert each (``kernels.grouped_experts_pallas``), runs one grouped
    product over them at shapes fixed by the number of rows alone, and
    gathers each token's weighted results back; the zero experts are one
    multiply by their summed weights. Nothing is dropped, whatever the
    routing. The step's routing counts come back beside the sampled tokens
    (``COUNTERS``)."""

    # what step_ragged returns beside the logits, summed over the layers
    COUNTERS = ("moe_pairs", "moe_pairs_held", "moe_pairs_zero",
                "moe_peak_tokens", "moe_experts_touched")

    def __init__(self, model):
        cfg = model.config
        self.cfg = cfg
        self.n_heads = cfg.num_attention_heads
        self.n_kv = 1
        self.latent_dim = cfg.kv_lora_rank
        self.rope_dim = cfg.qk_rope_head_dim
        self.hd = -(-(self.latent_dim + self.rope_dim) // 128) * 128
        self.v_dim = 0
        self.attn_scale = cfg.attn_scale
        self.eps = cfg.rms_norm_eps
        self.n_layers = cfg.num_layers
        self.cache_entries = 2 * self.n_layers
        self.tied = False
        self.embed_key = "model.embed_tokens.weight"

    def _static_key(self):
        import dataclasses
        return (type(self), dataclasses.astuple(self.cfg))

    def describe(self):
        """What ``telemetry()["model"]`` adds for this decoder."""
        c = self.cfg
        return {"cache": "latent", "latent_row": self.latent_dim
                + self.rope_dim, "latent_row_padded": self.hd,
                "experts_held": c.experts_held,
                "experts_published": c.n_routed_experts,
                "zero_experts": c.zero_expert_num, "experts_a_token":
                c.moe_topk}

    def step_counts(self, beside, rows):
        """``serve.emit``'s arguments from the counters a step brought
        back."""
        out = {k: int(v) for k, v in zip(self.COUNTERS, beside)}
        out["moe_held_mean_tokens"] = out["moe_pairs_held"] \
            / self.cfg.experts_held
        return out

    def quant_plan(self):
        raise NotImplementedError(
            "weight-only quantization is not offered for LongCat-Flash")

    def tp_specs(self):
        """Replicated: expert sharding over a mesh is not offered yet."""
        return {}

    # -- pieces ---------------------------------------------------------------
    def _block(self, w, i, j):
        """MLA block ``j`` of layer ``i``: its leaves by their short names."""
        from .models.longcat_flash import LongcatFlashMLA
        pre = f"model.layers.{i}.self_attn.{j}."
        return {n: w[pre + n] for n in LongcatFlashMLA.NAMES}

    def _norm(self, w, i, name, j, x):
        return _rms(x, w[f"model.layers.{i}.{name}.{j}.weight"], self.eps)

    def _mla_rows(self, w, i, j, x, cos, sin):
        """x: [..., hidden] -> (q [..., heads, row_dim]: the absorbed query
        beside the roped one; row [..., row_dim]: what the token keeps)."""
        from .models.longcat_flash import kv_b_parts, mla_project
        p = self._block(w, i, j)
        q_nope, q_rope, c, k_rope = mla_project(p, x, cos, sin, self.cfg)
        w_k, _ = kv_b_parts(p["kv_b_proj.weight"], self.cfg)
        q_lat = jnp.einsum("...hn,chn->...hc", q_nope, w_k)
        pad = self.hd - self.latent_dim - self.rope_dim
        q = jnp.concatenate(
            [q_lat, q_rope, jnp.zeros(q_lat.shape[:-1] + (pad,), q_lat.dtype)],
            axis=-1)
        row = jnp.concatenate(
            [c, k_rope, jnp.zeros(c.shape[:-1] + (pad,), c.dtype)], axis=-1)
        return q, row

    def _mla_out(self, w, i, j, o_lat):
        """o_lat: [..., heads, latent], the probabilities' sum of latents ->
        the block's output [..., hidden]."""
        from .models.longcat_flash import kv_b_parts
        p = self._block(w, i, j)
        _, w_v = kv_b_parts(p["kv_b_proj.weight"], self.cfg)
        o = jnp.einsum("...hc,chv->...hv", o_lat, w_v)
        return _mm(o.reshape(o.shape[:-2] + (-1,)), p, "o_proj.weight")

    def _ffn(self, w, i, j, x):
        from .models.longcat_flash import swiglu
        pre = f"model.layers.{i}.mlps.{j}."
        return swiglu(x, w[pre + "gate_proj.weight"],
                      w[pre + "up_proj.weight"], w[pre + "down_proj.weight"])

    def _moe(self, w, i, h, valid, shard=None):
        """The expert layer on h: [T, hidden]; valid: [T] bool, rows that
        are somebody's (the others are routed nowhere). Returns (m [T,
        hidden], counters [5] int32)."""
        from .kernels import grouped_experts_pallas as ge
        from .models.longcat_flash import route
        cfg = self.cfg
        pre = f"model.layers.{i}.mlp."
        t, k, held_n = h.shape[0], cfg.moe_topk, cfg.experts_held
        with jax.named_scope("moe_route"):
            chosen, weight = route(h, w[pre + "router.classifier.weight"],
                                   w[pre + "router.e_score_correction_bias"],
                                   cfg)
            local = chosen - cfg.first_expert
            held = (local >= 0) & (local < held_n) & valid[:, None]
            zero = (chosen >= cfg.n_routed_experts) & valid[:, None]
            keys = jnp.where(held, local, held_n).reshape(-1)
            sizes, tile_group, n_live, row_pair, pair_row = ge.group_plan(
                keys, held_n)
            live = row_pair >= 0
            xs = jnp.where(live[:, None],
                           h[jnp.maximum(row_pair, 0) // k], 0)
        with jax.named_scope("moe_experts"):
            ys = ge.grouped_experts(
                xs, tile_group, n_live, w[pre + "experts.gate_proj"],
                w[pre + "experts.up_proj"], w[pre + "experts.down_proj"],
                kernel=shard is None)
            mine = ys[jnp.maximum(pair_row, 0)].reshape(t, k, -1)
            m = jnp.sum(jnp.where(held[..., None],
                                  weight[..., None] * mine.astype(jnp.float32),
                                  0.0), axis=1)
        with jax.named_scope("moe_zero"):
            m = m + jnp.sum(jnp.where(zero, weight, 0.0), axis=1)[:, None] \
                * h.astype(jnp.float32)
        with jax.named_scope("moe_route"):
            counters = jnp.stack([
                valid.sum() * k, held.sum(), zero.sum(), sizes.max(),
                (sizes > 0).sum()]).astype(jnp.int32)
        return m.astype(h.dtype), counters

    def _double_layer(self, w, i, h, attention, valid, shard=None):
        """One double layer on h [..., hidden]; ``attention(j, x)`` is MLA
        block ``j`` on the normed input (it owns the cache)."""
        shortcut = counters = None
        for j in (0, 1):
            a = h + attention(j, self._norm(w, i, "input_layernorm", j, h))
            with jax.named_scope("mlp"):
                x = self._norm(w, i, "post_attention_layernorm", j, a)
            if j == 0:
                flat = x.reshape(-1, x.shape[-1])
                shortcut, counters = self._moe(w, i, flat, valid, shard)
                shortcut = shortcut.reshape(x.shape)
            with jax.named_scope("mlp"):
                h = a + self._ffn(w, i, j, x)
        return h + shortcut, counters

    @jax.named_scope("head")
    def _logits(self, w, h):
        h = _rms(h, w["model.norm.weight"], self.eps)
        return h @ w["lm_head.weight"].T

    # -- the two step programs ---------------------------------------------------
    def step_ragged(self, w, tokens, positions, k_pools, v_pools, scatter,
                    attend, shard=None):
        """See _LlamaDecoder.step_ragged; k_pools: [2 * n_layers, P, 1, bs,
        row_dim], v_pools zero wide and handed back as they came. The
        second result is the step's routing counters, [5] int32
        (``COUNTERS``)."""
        h, cos, sin = self._embed_ragged(w, tokens, positions)
        seam = _entry_seams(k_pools.shape, scatter, attend)
        valid = scatter[0] < k_pools.shape[1]
        with jax.named_scope("kv_write"):
            kf = _join_entry_pages(k_pools)
        total = jnp.zeros(len(self.COUNTERS), jnp.int32)
        for i in range(self.n_layers):
            def attention(j, x, i=i):
                nonlocal kf
                with jax.named_scope("attn_proj"):
                    q, row = self._mla_rows(w, i, j, x, cos, sin)
                sc, att = seam(2 * i + j)
                with jax.named_scope("kv_write"):
                    kf = _write_pages(kf, row[:, :, None], sc)
                o_lat = att(q[:, 0], kf, None)[:, None]
                with jax.named_scope("attn_proj"):
                    return self._mla_out(w, i, j, o_lat)

            h, counters = self._double_layer(w, i, h, attention, valid, shard)
            total = total + counters
        with jax.named_scope("kv_write"):
            kp = kf.reshape(k_pools.shape)
        return self._logits(w, h)[:, 0], total, kp, v_pools

    def step(self, w, tokens, positions, kcs, vcs, write_pos, score_mask):
        """See _LlamaDecoder.step; kcs: [2 * n_layers, B, M, 1, row_dim],
        vcs zero wide."""
        h = w[self.embed_key][tokens]
        cos = w["__rope_cos"][positions]      # [B, S, rope/2]
        sin = w["__rope_sin"][positions]
        valid = jnp.ones(h.shape[0] * h.shape[1], bool)
        new = []
        for i in range(self.n_layers):
            def attention(j, x, i=i):
                q, row = self._mla_rows(w, i, j, x, cos, sin)
                kc = jax.lax.dynamic_update_slice(
                    kcs[2 * i + j], row[:, :, None].astype(kcs.dtype),
                    (0, write_pos, 0, 0))
                new.append(kc)
                rows = kc[:, :, 0].astype(jnp.float32)        # [B, M, row]
                scores = jnp.einsum("bshd,bmd->bhsm", q.astype(jnp.float32),
                                    rows) * self.attn_scale
                p = jax.nn.softmax(jnp.where(score_mask, scores, NEG_INF),
                                   axis=-1)
                o_lat = jnp.einsum("bhsm,bmc->bshc", p,
                                   rows[..., :self.latent_dim])
                return self._mla_out(w, i, j, o_lat.astype(x.dtype))

            h, _ = self._double_layer(w, i, h, attention, valid)
        return self._logits(w, h), jnp.stack(new), vcs


def _ln(x, w, b, eps):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean((x32 - mu) ** 2, axis=-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)
            + b.astype(jnp.float32)).astype(x.dtype)


class _GPTDecoder:
    """Pure decode functions over a GPTForCausalLM state dict (pre-LN
    GPT-2: learned positions, fused-qkv biases, erf GELU). MoE blocks
    decode with NO-DROP routing: per-token top-k expert mixing without
    capacity dropping (a training-throughput device that would make a
    cached step depend on which OTHER tokens were in the recompute batch
    — dropped-token decode could never match the full forward). All
    experts run densely and combine through exact 0/1 masks, so a no-drop
    eval forward is reproduced bit-for-bit."""

    attn_scale = None         # see _LlamaDecoder
    latent_dim = None

    def __init__(self, model):
        cfg = model.config
        self.moe_layers = {}
        from .incubate.distributed.models.moe.gate import BaseGate
        for i, blk in enumerate(model.transformer.h):
            if getattr(blk, "is_moe", False):
                if blk.mlp.w1 is None:
                    raise NotImplementedError(
                        "generate() supports batched-expert MoE blocks "
                        "(stacked w1/w2 banks); per-expert Layer lists "
                        "have no stacked weights to decode against")
                if type(blk.mlp.gate).forward is not BaseGate.forward:
                    raise NotImplementedError(
                        "generate() routes with the standard linear gate; "
                        f"{type(blk.mlp.gate).__name__} overrides "
                        "forward(), which the decode program cannot "
                        "reproduce from the state dict")
                if (blk.mlp.gate.capacity_factor(training=False) is not None
                        and blk.mlp._capacity_override is None):
                    # capacity routing makes a token's expert assignment
                    # depend on which OTHER tokens share the forward call
                    # (earlier tokens win slots) — a cached decode step sees
                    # only the current positions, so it cannot reproduce the
                    # full-forward drops; refuse rather than silently diverge
                    raise NotImplementedError(
                        f"generate() cannot reproduce "
                        f"{type(blk.mlp.gate).__name__}'s eval capacity "
                        "dropping (routing depends on batch composition). "
                        "Use NaiveGate (unbounded), or set "
                        "mlp._capacity_override >= tokens-per-forward to "
                        "make eval routing no-drop")
                self.moe_layers[i] = {
                    "top_k": blk.mlp.gate.top_k,
                    "act": blk.mlp._act,
                    "has_bias": blk.mlp.gate.bias is not None,
                }
                # generate() re-checks this bound against the actual
                # tokens-per-forward of each call (b * (s + max_new))
                ov = blk.mlp._capacity_override
                if ov is not None:
                    self.min_capacity_override = min(
                        getattr(self, "min_capacity_override", ov), int(ov))
        self.cfg = cfg
        self.n_heads = cfg.num_attention_heads
        self.n_kv = self.n_heads
        self.hd = cfg.hidden_size // self.n_heads
        self.v_dim = self.hd
        self.eps = cfg.layer_norm_epsilon
        self.n_layers = cfg.num_hidden_layers
        self.cache_entries = self.n_layers    # see _LlamaDecoder
        self.tied = model.lm_head is None
        self.embed_key = "transformer.wte.weight"

    def describe(self):
        return {}                 # see _LlamaDecoder

    def beside_width(self, rows):
        return 0                  # see _LlamaDecoder

    def _static_key(self):
        """See _LlamaDecoder._static_key. The MoE fingerprint keys the
        activation by function object — gates resolve activations from the
        shared _ACTS registry, so equal configs get the same object."""
        moe = tuple((i, m["top_k"], m["act"], m["has_bias"])
                    for i, m in sorted(self.moe_layers.items()))
        return (type(self), self.n_heads, self.hd, self.eps, self.n_layers,
                self.tied, self.embed_key, moe)

    def __hash__(self):
        return hash(self._static_key())

    def __eq__(self, other):
        return (type(other) is type(self)
                and other._static_key() == self._static_key())

    @staticmethod
    def weights(model):
        return {n: t._data for n, t in model.named_state().items()}

    _QUANT_SUFFIXES = ("attn.qkv_proj.weight", "attn.out_proj.weight",
                       "mlp.fc_in.weight", "mlp.fc_out.weight")

    def quant_plan(self):
        """(matmul weight names to quantize, tied-embed key or None).
        MoE blocks keep fp expert banks (3-D [e,·,·] weights); only their
        attention projections quantize."""
        names = [f"transformer.h.{i}.{sfx}" for i in range(self.n_layers)
                 for sfx in self._QUANT_SUFFIXES
                 if not (i in self.moe_layers and sfx.startswith("mlp."))]
        if not self.tied:
            names.append("lm_head.weight")
        return names, (self.embed_key if self.tied else None)

    def _qkv_proj(self, w, i, x, b, s):
        """Fused-qkv projection shared by the dense and ragged layers."""
        p = f"transformer.h.{i}."
        qkv = (_mm(x, w, p + "attn.qkv_proj.weight")
               + w[p + "attn.qkv_proj.bias"]) \
            .reshape(b, s, 3, self.n_heads, self.hd)
        return qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]

    def _post_attn(self, w, i, h, att):
        """Residual + out proj + (MoE-)MLP, shared by both layer paths."""
        p = f"transformer.h.{i}."
        with jax.named_scope("attn_proj"):
            h = h + _mm(att, w, p + "attn.out_proj.weight") \
                + w[p + "attn.out_proj.bias"]
        with jax.named_scope("mlp"):
            x2 = _ln(h, w[p + "ln_2.weight"], w[p + "ln_2.bias"], self.eps)
            if i in self.moe_layers:
                return h + self._moe_mlp(w, i, x2)
            m = jax.nn.gelu((_mm(x2, w, p + "mlp.fc_in.weight")
                             + w[p + "mlp.fc_in.bias"]).astype(jnp.float32),
                            approximate=False).astype(h.dtype)
            return h + _mm(m, w, p + "mlp.fc_out.weight") \
                + w[p + "mlp.fc_out.bias"]

    def _layer(self, w, i, h, kc, vc, write_pos, score_mask):
        p = f"transformer.h.{i}."
        b, s, _ = h.shape
        x = _ln(h, w[p + "ln_1.weight"], w[p + "ln_1.bias"], self.eps)
        q, k, v = self._qkv_proj(w, i, x, b, s)
        kc = jax.lax.dynamic_update_slice(kc, k.astype(kc.dtype),
                                          (0, write_pos, 0, 0))
        vc = jax.lax.dynamic_update_slice(vc, v.astype(vc.dtype),
                                          (0, write_pos, 0, 0))
        att = _attend(q, kc, vc, score_mask).reshape(b, s, -1)
        return self._post_attn(w, i, h, att), kc, vc

    def _layer_ragged(self, w, i, h, kf, vf, scatter, attend, shard=None):
        """Packed ragged-batch layer (see _LlamaDecoder._layer_ragged);
        GPT has no rope — positions enter through the wpe embedding."""
        p = f"transformer.h.{i}."
        t, s, _ = h.shape
        with jax.named_scope("attn_proj"):
            x = _ln(h, w[p + "ln_1.weight"], w[p + "ln_1.bias"], self.eps)
            q, k, v = self._qkv_proj(w, i, x, t, s)
            if shard is not None:
                q, k, v = shard.qkv(q, k, v)
        kf, vf = _kv_write_pages(kf, vf, k, v, scatter)
        att = attend(q[:, 0], kf, vf).reshape(t, 1, -1)
        if shard is not None:
            att = shard.att(att)
        return self._post_attn(w, i, h, att), kf, vf

    def step_ragged(self, w, tokens, positions, k_pools, v_pools, scatter,
                    attend, shard=None):
        """Ragged-batch twin of step(); see _LlamaDecoder.step_ragged."""
        with jax.named_scope("embed"):
            h = (w["transformer.wte.weight"][tokens]
                 + w["transformer.wpe.weight"][positions])[:, None]
        seam = _entry_seams(k_pools.shape, scatter, attend)
        kf, vf = _join_entries(k_pools, v_pools)
        for i in range(self.n_layers):
            h, kf, vf = self._layer_ragged(w, i, h, kf, vf, *seam(i),
                                           shard=shard)
        with jax.named_scope("head"):
            h = _ln(h, w["transformer.ln_f.weight"],
                    w["transformer.ln_f.bias"], self.eps)
            logits = _head_logits(w, h, self.tied, self.embed_key)
        return logits[:, 0], None, *_split_entries(kf, vf, k_pools.shape)

    def tp_specs(self):
        """See _LlamaDecoder.tp_specs. GPT's fused qkv projection packs
        its output dim [3, heads, hd]-major — slicing that dim over mp
        would NOT be head-aligned, so the attention matmul weights stay
        replicated and the per-head layout is pinned on the ACTIVATIONS
        (the ``shard.qkv`` seam); the dense MLP gets the column/row
        split. MoE expert banks ride the ep story, not mp: replicated."""
        specs = {}
        for i in range(self.n_layers):
            p = f"transformer.h.{i}."
            if i in self.moe_layers:
                continue
            specs[p + "mlp.fc_in.weight"] = (None, "mp")
            specs[p + "mlp.fc_in.bias"] = ("mp",)
            specs[p + "mlp.fc_out.weight"] = ("mp", None)
        return specs

    def _moe_mlp(self, w, i, x2):
        """No-drop top-k expert mixing; x2: [B, S, D] -> [B, S, D].

        Every expert runs on every token (dense [t, e, h] FFN — decode
        steps have t = B tokens, so the e-fold compute is cheap next to
        attention over the cache) and the top-k combine weights select via
        exact one-hot masks: identical math to the training MoELayer with
        an unbounded capacity, without its O(t^2 e) dispatch one-hots."""
        p = f"transformer.h.{i}.mlp."
        meta = self.moe_layers[i]
        b, s, d = x2.shape
        xt = x2.reshape(b * s, d)
        logits = xt @ w[p + "gate.weight"]
        if meta["has_bias"]:
            logits = logits + w[p + "gate.bias"]
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        topv, topi = jax.lax.top_k(probs, meta["top_k"])
        if meta["top_k"] > 1:
            topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
        e = probs.shape[-1]
        comb = jnp.zeros((b * s, e), jnp.float32)
        for j in range(meta["top_k"]):
            comb = comb + topv[:, j, None] * jax.nn.one_hot(topi[:, j], e)
        # scan over the expert bank: each expert's FFN runs on all tokens
        # (dense compute; routing selects via comb's 0 weights) but only
        # O(t, h) activation memory is live at once — the fused [t, e, h]
        # einsum would scale e-fold with prompt length on the PREFILL step
        def body(acc, ew):
            w1_e, b1_e, w2_e, b2_e, comb_e = ew
            hh = meta["act"](xt @ w1_e + b1_e[None])
            return acc + comb_e[:, None].astype(xt.dtype) \
                * (hh @ w2_e + b2_e[None]), None
        y, _ = jax.lax.scan(
            body, jnp.zeros_like(xt),
            (w[p + "w1"], w[p + "b1"], w[p + "w2"], w[p + "b2"], comb.T))
        return y.reshape(b, s, d)

    def step(self, w, tokens, positions, kcs, vcs, write_pos, score_mask):
        wte = w["transformer.wte.weight"]
        h = wte[tokens] + w["transformer.wpe.weight"][positions]
        new_k, new_v = [], []
        for i in range(self.n_layers):
            h, kc, vc = self._layer(w, i, h, kcs[i], vcs[i], write_pos,
                                    score_mask)
            new_k.append(kc)
            new_v.append(vc)
        h = _ln(h, w["transformer.ln_f.weight"], w["transformer.ln_f.bias"],
                self.eps)
        logits = _head_logits(w, h, self.tied, self.embed_key)
        return logits, jnp.stack(new_k), jnp.stack(new_v)


# -- sampling ------------------------------------------------------------------

def _sample(logits, key, do_sample, temperature, top_k, top_p):
    """logits: [B, V] -> tokens [B]."""
    if not do_sample:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    lg = logits.astype(jnp.float32) / jnp.maximum(temperature, 1e-6)
    if top_k and top_k > 0:
        kth = jax.lax.top_k(lg, top_k)[0][..., -1:]
        lg = jnp.where(lg < kth, NEG_INF, lg)
    if top_p < 1.0:
        sorted_lg = jnp.sort(lg, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_lg, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep the smallest set whose mass reaches top_p (first token
        # always kept)
        keep_sorted = jnp.roll(cum, 1, axis=-1) < top_p
        keep_sorted = keep_sorted.at[..., 0].set(True)
        cutoff = jnp.min(jnp.where(keep_sorted, sorted_lg, jnp.inf),
                         axis=-1, keepdims=True)
        lg = jnp.where(lg < cutoff, NEG_INF, lg)
    return jax.random.categorical(key, lg, axis=-1).astype(jnp.int32)


# -- public API ----------------------------------------------------------------

def _prefill(dec, w, ids, mask, max_new):
    """Shared prefill: cache alloc (one cache per cache entry of the
    decoder), left-padded positions, key/pre masks, and the prompt step.
    Returns (kcs, vcs, key_mask, last_logits)."""
    b, s = ids.shape
    m_total = s + max_new
    positions = jnp.maximum(
        jnp.cumsum(mask, axis=1).astype(jnp.int32) - 1, 0)   # [B, S]
    kcs = jnp.zeros((dec.cache_entries, b, m_total, dec.n_kv, dec.hd),
                    w[dec.embed_key].dtype)
    # a latent cache keeps no V: its V side is zero wide
    vcs = jnp.zeros(kcs.shape[:-1] + (dec.v_dim,), kcs.dtype)
    t_idx = jnp.arange(m_total)[None, None, None, :]         # key slots
    q_idx = jnp.arange(s)[None, None, :, None]
    key_mask = jnp.concatenate(
        [mask.astype(bool), jnp.zeros((b, max_new), bool)], axis=1)
    pre_mask = (t_idx <= q_idx) & key_mask[:, None, None, :]
    logits, kcs, vcs = dec.step(w, ids, positions, kcs, vcs, 0, pre_mask)
    # left padding => the last REAL token sits at index s-1 for every row
    return kcs, vcs, key_mask, logits[:, -1]


def _generate_impl(dec: "_LlamaDecoder", w, ids, mask, key, max_new,
                   do_sample, temperature, eos_id, has_eos, top_k, top_p,
                   rep_penalty, has_rep):
    b, s = ids.shape
    lengths = jnp.sum(mask, axis=1).astype(jnp.int32)        # [B]
    kcs, vcs, key_mask, last_logits = _prefill(dec, w, ids, mask, max_new)
    v = last_logits.shape[-1]
    # CTRL-style repetition penalty: tokens already seen (prompt or
    # generated) have positive logits divided / negative multiplied by it.
    # has_rep is STATIC: the neutral default traces to a program with no
    # seen state and no per-step penalty passes on the decode hot path.
    seen0 = jnp.zeros((b, v), bool).at[
        jnp.arange(b)[:, None], ids].max(mask.astype(bool)) \
        if has_rep else jnp.zeros((b, 1), bool)

    def body(t, carry):
        kcs, vcs, last_logits, key_mask, out, finished, key, seen = carry
        key, k_step = jax.random.split(key)
        lg = last_logits
        if has_rep:
            lg = lg.astype(jnp.float32)
            lg = jnp.where(seen, jnp.where(lg > 0, lg / rep_penalty,
                                           lg * rep_penalty), lg)
        tok = _sample(lg, k_step, do_sample, temperature, top_k, top_p)
        if has_eos:
            tok = jnp.where(finished, eos_id, tok)
            finished = finished | (tok == eos_id)
        out = out.at[:, t].set(tok)
        write_pos = s + t
        key_mask = key_mask.at[:, write_pos].set(True)
        positions_t = (lengths + t)[:, None]                 # [B, 1]
        step_mask = key_mask[:, None, None, :]               # attend all real
        if has_rep:
            seen = seen.at[jnp.arange(b), tok].set(True)
        logits, kcs, vcs = dec.step(w, tok[:, None], positions_t, kcs,
                                    vcs, write_pos, step_mask)
        return kcs, vcs, logits[:, 0], key_mask, out, finished, key, seen

    out0 = jnp.zeros((b, max_new), jnp.int32)
    finished0 = jnp.zeros((b,), bool)
    carry = (kcs, vcs, last_logits, key_mask, out0, finished0, key, seen0)
    carry = jax.lax.fori_loop(0, max_new, body, carry)
    return carry[4], carry[5]




def _beam_impl(dec, w, ids, mask, max_new, num_beams, eos_id, has_eos,
               length_penalty):
    """Greedy beam search sharing dec.step. Beams live as an expanded batch
    [B*K, ...]; each step scores K*V continuations per row, keeps the top
    K, and reorders the KV caches along the beam axis. Finished beams
    persist by emitting exactly one eos continuation at their frozen
    score. Returns the best beam per row by length-penalized score."""
    b, s = ids.shape
    k = num_beams
    bk = b * k
    rep = lambda a: jnp.repeat(a, k, axis=0)
    ids_r, mask_r = rep(ids), rep(mask)
    kcs, vcs, key_mask, last_logits = _prefill(dec, w, ids_r, mask_r,
                                               max_new)
    last_lp = jax.nn.log_softmax(last_logits.astype(jnp.float32), -1)

    v = last_lp.shape[-1]
    # beam 0 starts live, the rest at -inf so step 0 picks K distinct
    # tokens from beam 0 (all beams are identical clones at this point)
    scores0 = jnp.where(jnp.arange(k)[None, :] == 0, 0.0, NEG_INF)
    scores0 = jnp.broadcast_to(scores0, (b, k))

    def body(t, carry):
        kcs, vcs, last_lp, key_mask, scores, out, finished = carry
        lp = last_lp.reshape(b, k, v)
        if has_eos:
            # finished beams contribute ONE candidate (eos) at their
            # frozen score; everything else from them is -inf
            only_eos = jnp.where(jnp.arange(v)[None, None, :] == eos_id,
                                 0.0, NEG_INF)
            lp = jnp.where(finished.reshape(b, k)[:, :, None], only_eos, lp)
        cand = scores[:, :, None] + lp                    # [B, K, V]
        flat = cand.reshape(b, k * v)
        top_sc, top_ix = jax.lax.top_k(flat, k)           # [B, K]
        src_beam = (top_ix // v).astype(jnp.int32)        # [B, K]
        tok = (top_ix % v).astype(jnp.int32)              # [B, K]

        def reorder(a):
            # a: [..., B*K, ...] with beam-major rows; gather along beams
            shp = a.shape
            ax = 1 if a.ndim > 3 else 0   # kcs/vcs: [E, BK, ...]; 2-d: BK
            aa = jnp.moveaxis(a, ax, 0).reshape((b, k) + shp[:ax]
                                                + shp[ax + 1:])
            ga = jnp.take_along_axis(
                aa, src_beam.reshape((b, k) + (1,) * (aa.ndim - 2)), axis=1)
            return jnp.moveaxis(ga.reshape((bk,) + shp[:ax] + shp[ax + 1:]),
                                0, ax)

        kcs = reorder(kcs)
        vcs = reorder(vcs)
        # key_mask needs no reorder: all K beams of a row share the same
        # prompt mask and every step sets the same column for all rows
        out = jnp.take_along_axis(out, src_beam[:, :, None], axis=1)
        out = out.at[:, :, t].set(tok)
        if has_eos:
            finished = jnp.take_along_axis(finished.reshape(b, k),
                                           src_beam, axis=1)
            finished = finished | (tok == eos_id)
        scores = top_sc

        write_pos = s + t
        key_mask = key_mask.at[:, write_pos].set(True)
        positions_t = (jnp.repeat(jnp.sum(mask, 1).astype(jnp.int32), k)
                       + t)[:, None]
        step_mask = key_mask[:, None, None, :]
        logits, kcs, vcs = dec.step(w, tok.reshape(bk, 1), positions_t,
                                    kcs, vcs, write_pos, step_mask)
        last_lp = jax.nn.log_softmax(logits[:, 0].astype(jnp.float32), -1)
        return kcs, vcs, last_lp, key_mask, scores, out, finished.reshape(
            b, k) if has_eos else finished

    out0 = jnp.zeros((b, k, max_new), jnp.int32)
    fin0 = jnp.zeros((b, k), bool)
    carry = (kcs, vcs, last_lp, key_mask, scores0, out0, fin0)
    kcs, vcs, last_lp, key_mask, scores, out, finished = jax.lax.fori_loop(
        0, max_new, body, carry)
    # length-penalized best beam (finished beams' length = tokens to eos)
    if has_eos:
        first_eos = jnp.argmax(out == eos_id, axis=2)
        has = jnp.any(out == eos_id, axis=2)
        gen_len = jnp.where(has, first_eos + 1, max_new).astype(jnp.float32)
    else:
        gen_len = jnp.full((b, k), float(max_new), jnp.float32)
    norm = scores / (gen_len ** length_penalty)
    best = jnp.argmax(norm, axis=1)
    tokens = jnp.take_along_axis(out, best[:, None, None], axis=1)[:, 0]
    fin = jnp.take_along_axis(finished, best[:, None], axis=1)[:, 0]
    return tokens, fin


def generate(model, input_ids, attention_mask=None, max_new_tokens: int = 32,
             do_sample: bool = False, temperature: float = 1.0,
             top_k: int = 0, top_p: float = 1.0,
             eos_token_id: Optional[int] = None, seed: Optional[int] = None,
             num_beams: int = 1, length_penalty: float = 1.0,
             repetition_penalty: float = 1.0,
             quant: Optional[str] = None):
    """Greedy/sampled continuation of `input_ids` ([B, S] int, LEFT-padded
    for ragged batches with `attention_mask` [B, S] in {0,1}).

    quant="weight_only_int8" / "weight_only_int4" decodes against
    per-channel narrow-int weight matrices (reference
    weight_only_linear/llm_int8 serving capability) — the quantized
    pytree is cached per weight snapshot and the dequant folds into each
    matmul's operand read.

    Returns (tokens [B, max_new_tokens] Tensor, finished [B] Tensor) —
    rows that hit eos_token_id keep emitting eos. One compiled program per
    (batch, prompt_len, max_new_tokens, sampling-config) signature."""
    if quant is not None and quant not in _QUANT_BITS:
        raise NotImplementedError(
            f"generate(quant={quant!r}): supported algos are "
            f"{sorted(_QUANT_BITS)}")
    ids = input_ids._data if isinstance(input_ids, Tensor) \
        else jnp.asarray(input_ids)
    ids = ids.astype(jnp.int32)
    b, s = ids.shape
    if attention_mask is None:
        mask = jnp.ones((b, s), jnp.int32)
    else:
        mask = (attention_mask._data if isinstance(attention_mask, Tensor)
                else jnp.asarray(attention_mask)).astype(jnp.int32)
        # left padding is the contract: real tokens are a suffix
        lengths = jnp.sum(mask, axis=1)
        suffix = jnp.arange(s)[None, :] >= (s - lengths[:, None])
        if not bool(jnp.all(mask.astype(bool) == suffix)):
            raise ValueError(
                "generate() requires LEFT-padded prompts: attention_mask "
                "must mark a suffix of real tokens per row")
    if model.config.max_position_embeddings < s + max_new_tokens:
        raise ValueError(
            f"prompt {s} + max_new_tokens {max_new_tokens} exceeds "
            f"max_position_embeddings "
            f"{model.config.max_position_embeddings}")
    dec = _decoder_for(model)
    mco = getattr(dec, "min_capacity_override", None)
    if mco is not None and mco < b * (s + max_new_tokens):
        # an override below tokens-per-forward means the eval forward DOES
        # drop tokens, recreating exactly the decode-vs-forward divergence
        # the no-drop contract forbids
        raise ValueError(
            f"MoE _capacity_override={mco} < tokens-per-forward "
            f"{b * (s + max_new_tokens)} (batch {b} x (prompt {s} + "
            f"max_new_tokens {max_new_tokens})): the full forward would "
            "drop tokens, which the cached no-drop decode cannot "
            "reproduce; raise the override or shorten the request")
    weights = (_quant_weights_cached(dec, model, quant) if quant
               else dec.weights(model))
    has_eos_b = eos_token_id is not None
    if num_beams > 1:
        if do_sample:
            raise NotImplementedError(
                "beam search with sampling is not supported; use "
                "do_sample=False (greedy beams) or num_beams=1")
        if repetition_penalty != 1.0:
            raise NotImplementedError(
                "repetition_penalty under beam search is not supported")
        toks, fin = _jits_for(dec)[1](
            weights, ids, mask, int(max_new_tokens), int(num_beams),
            jnp.int32(eos_token_id if has_eos_b else 0),
            has_eos_b, jnp.float32(length_penalty))
        return Tensor(toks), Tensor(fin)
    key = jax.random.PRNGKey(0 if seed is None else seed)
    if seed is None and do_sample:
        from .framework.random import next_key
        key = next_key()
    has_eos = eos_token_id is not None
    toks, finished = _jits_for(dec)[0](
        weights, ids, mask, key, int(max_new_tokens),
        bool(do_sample), float(temperature),
        jnp.int32(eos_token_id if has_eos else 0), has_eos, int(top_k),
        float(top_p), jnp.float32(repetition_penalty),
        repetition_penalty != 1.0)
    return Tensor(toks), Tensor(finished)


def draft_greedy_batch(model, seqs, k: int, width: int = 64,
                       quant: Optional[str] = None):
    """Greedy k-token draft continuations of every ``seqs`` entry (each
    a python token list) in ONE generate() call — speculative decoding
    (``serving.speculative``) drafts for the whole decode batch per
    step, not one device call per sequence.

    Reuses the one-program generate() path — same ``_LlamaDecoder`` /
    ``_GPTDecoder`` step machinery as the target model — but pins each
    context into a FIXED left-padded window of ``width`` tokens, so a
    serving drafter compiles one program per (batch, width, k)
    signature instead of one per prompt length. A sequence longer than
    the window keeps its most recent tokens (sliding-window drafting:
    the drafter only proposes; verification restores exactness).
    Returns a list of k-int lists, one per input sequence."""
    if k < 1 or not seqs:
        return [[] for _ in seqs]
    max_pos = model.config.max_position_embeddings
    if max_pos <= k:
        raise ValueError(
            f"draft model caps at {max_pos} positions, cannot draft "
            f"{k} tokens")
    width = int(min(width, max_pos - k))
    ids = np.zeros((len(seqs), width), np.int32)
    mask = np.zeros((len(seqs), width), np.int32)
    for b, seq in enumerate(seqs):
        ctx = [int(t) for t in seq[-width:]]
        ids[b, width - len(ctx):] = ctx
        mask[b, width - len(ctx):] = 1
    toks, _ = generate(model, ids, attention_mask=mask,
                       max_new_tokens=k, quant=quant)
    return [[int(t) for t in row] for row in np.asarray(toks._data)]


def draft_greedy(model, seq, k: int, width: int = 64,
                 quant: Optional[str] = None):
    """Single-sequence convenience over ``draft_greedy_batch``."""
    if k < 1:
        return []
    return draft_greedy_batch(model, [seq], k, width=width, quant=quant)[0]


# The decoder keys a bounded registry of jitted entry points: every model
# with the same architecture — predictor-pool clones, test fixtures,
# reloaded checkpoints — shares ONE compiled executable per (shapes,
# sampling-config) signature instead of recompiling per instance. Weights
# stay ordinary jit ARGUMENTS: never captured, so updates need no
# invalidation and old arrays aren't pinned. The registry is LRU-bounded so
# a serving process cycling through many architectures doesn't accumulate
# executables (and their pinned decoder/config objects) forever — evicting
# a decoder's entry drops its whole jit cache.
_DEC_JIT = OrderedDict()
_DEC_JIT_MAX = 8


def _jits_for(dec):
    ent = _DEC_JIT.pop(dec, None)
    if ent is None:
        # post-partial arg indices (dec bound):
        # gen: w=0, ids=1, mask=2, key=3, max_new=4(s), do_sample=5(s),
        #      temperature=6, eos_id=7, has_eos=8(s), top_k=9(s),
        #      top_p=10(s), rep_penalty=11, has_rep=12(s)
        # beam: w=0, ids=1, mask=2, max_new=3(s), num_beams=4(s),
        #       eos_id=5, has_eos=6(s), length_penalty=7
        ent = (jax.jit(partial(_generate_impl, dec),
                       static_argnums=(4, 5, 8, 9, 10, 12)),
               jax.jit(partial(_beam_impl, dec), static_argnums=(3, 4, 6)))
    _DEC_JIT[dec] = ent
    while len(_DEC_JIT) > _DEC_JIT_MAX:
        _DEC_JIT.popitem(last=False)
    return ent


def _live_moe_struct(model):
    """Fingerprint of the model's CURRENT MoE block state — everything the
    decoder snapshots at construction, so mutating a block (swapped mlp,
    changed top_k, custom gate) rebuilds the decoder instead of silently
    decoding with stale routing."""
    blocks = getattr(getattr(model, "transformer", None), "h", None)
    if blocks is None:
        return ()
    fp = []
    for i, blk in enumerate(blocks):
        if getattr(blk, "is_moe", False):
            g = blk.mlp.gate
            fp.append((i, g.top_k, getattr(blk.mlp, "_act", None),
                       g.bias is not None, blk.mlp.w1 is None,
                       type(g).forward, g.capacity_factor(training=False),
                       blk.mlp._capacity_override))
    return tuple(fp)


def _decoder_for(model):
    """One decoder per model instance (holds only static config; equal
    configs hash equal, so the module jits share executables across
    instances)."""
    from .models.gpt import GPTForCausalLM
    from .models.longcat_flash import LongcatFlashForCausalLM
    from .models.nemotron_h import NemotronHForCausalLM
    from .models.ouro import OuroForCausalLM
    cls = _GPTDecoder if isinstance(model, GPTForCausalLM) \
        else _OuroDecoder if isinstance(model, OuroForCausalLM) \
        else _LongcatDecoder if isinstance(model, LongcatFlashForCausalLM) \
        else _NemotronHDecoder if isinstance(model, NemotronHForCausalLM) \
        else _LlamaDecoder
    struct = (cls, model.lm_head is None,    # head tying is baked into the
              _live_moe_struct(model))       # traced logits branch
    dec = model.__dict__.get("_decode_cache")
    if dec is None or dec._struct != struct:
        dec = cls(model)
        dec._struct = struct
        model.__dict__["_decode_cache"] = dec
    return dec


class _NemotronHDecoder(_LlamaDecoder):
    """Pure functions over a NemotronHForCausalLM state dict
    (``models/nemotron_h.py`` has the equations, and every one used here is
    that file's): each layer ONE part, a Mamba-2 mixer, an attention layer
    without rotary or an expert layer in a latent width, by
    ``hybrid_override_pattern``.

    Only the attention layers keep pages (``cache_entries`` counts them).
    A Mamba layer keeps a STATE a sequence, whatever the context: the
    recurrence's state and the convolution's last inputs, in two pools
    ``[state layers, slots, ...]`` that the engine holds beside the page
    pools by the request's slot (``state_shapes``; None on every other
    decoder) and threads through ``step_ragged``. A step's rows of one
    sequence pass through the recurrence in order, from what the slot holds
    or from nothing where the first row stands at position 0, and leave the
    slot holding what the next step starts from
    (``kernels.ssm_pallas.ssm_scan``). The expert layer is dispatched as
    ``_LongcatDecoder``'s is, over two-bank ``relu2`` experts, and counts
    under the same names."""

    COUNTERS = _LongcatDecoder.COUNTERS

    def __init__(self, model):
        from .kernels import ssm_pallas as ssm
        cfg = model.config
        self.cfg = cfg
        self.kinds = cfg.pattern
        self.n_heads = cfg.num_attention_heads
        self.n_kv = cfg.num_key_value_heads
        self.hd = self.v_dim = cfg.head_dim
        self.eps = cfg.layer_norm_epsilon
        self.n_layers = cfg.num_hidden_layers
        self.cache_entries = self.kinds.count("*")
        self.tied = False
        self.embed_key = "backbone.embeddings.weight"
        # what a sequence keeps in each Mamba layer, (shape, dtype; None =
        # the activations'): the engine's pools are [state_layers, slots]
        # + shape
        self.state_layers = self.kinds.count("M")
        self.state_shapes = (
            (ssm.pool_shape(cfg.mamba_num_heads, cfg.n_groups,
                            cfg.mamba_head_dim, cfg.ssm_state_size),
             "float32"),
            # the tail's K - 1 inputs side by side in one row: [3, 10240]
            # would be padded to 16 sublanes a slot, five times its bytes
            (((cfg.conv_kernel - 1) * cfg.conv_dim,), None))

    def _static_key(self):
        import dataclasses
        return (type(self), dataclasses.astuple(self.cfg))

    @staticmethod
    def weights(model):
        return {n: t._data for n, t in model.named_state().items()}

    def describe(self):
        """What ``telemetry()["model"]`` adds for this decoder."""
        c = self.cfg
        return {"layer_kinds": self.kinds, "state_layers": self.state_layers,
                "experts_held": c.experts_held,
                "experts_published": c.n_routed_experts,
                "experts_a_token": c.num_experts_per_tok}

    step_counts = _LongcatDecoder.step_counts

    def quant_plan(self):
        raise NotImplementedError(
            "weight-only quantization is not offered for Nemotron-H")

    def tp_specs(self):
        """Replicated: a mesh engine is refused for a decoder with a
        state (``ServingEngine``)."""
        return {}

    def step(self, w, tokens, positions, kcs, vcs, write_pos, score_mask):
        raise NotImplementedError(
            "generate() carries K/V caches and nothing else; a Nemotron-H "
            "sequence keeps a recurrent state beside them: serve it "
            "through paddle_tpu.serving.ServingEngine (submit / step), "
            "which holds that state by the request's slot")

    # -- pieces ---------------------------------------------------------------
    @staticmethod
    def _part(w, i):
        """Layer ``i``'s mixer: its leaves by their short names."""
        pre = f"backbone.layers.{i}.mixer."
        return {n[len(pre):]: a for n, a in w.items() if n.startswith(pre)}

    @jax.named_scope("ssm_conv")
    def _tap_plan(self, meta, positions):
        """Where each row's convolution finds its ``K - 1`` earlier inputs,
        once a step: in the step's rows while they are its sequence's, else
        in the slot's tail, else (before position 0) nowhere. Returns (for
        each j = 1 .. K - 1: the row j back, whether it is the sequence's,
        and for each place in the tail whether that holds the input
        instead: with ``d`` rows of the sequence before this one in the
        step, place ``K - 1 - j + d``), each row's slot, and the scheduled
        sequences' (slots, last rows) for the new tails; an unscheduled
        entry's slot is one past the pool, which a scatter drops."""
        k = self.cfg.conv_kernel
        rows = jnp.arange(positions.shape[0], dtype=jnp.int32)
        slot = jnp.maximum(meta.slots, 0)
        slots = meta.order.shape[0]
        start = jnp.zeros(slots, jnp.int32).at[meta.order].set(meta.starts)
        since = rows - start[slot]             # rows of mine before this one
        back = [(jnp.maximum(rows - j, 0), since >= j,
                 {k - 1 - j + d: (since == d) & (positions >= j)
                  for d in range(j)})
                for j in range(1, k)]
        live = jnp.arange(slots) < meta.n_live
        return back, slot, (jnp.where(live, meta.order, slots),
                            jnp.maximum(meta.starts + meta.counts - 1, 0))

    def _mamba(self, w, i, x, m, states, tails, meta, plan, kernel):
        """Mamba layer ``i``, the ``m``-th of them, on normed rows x [T,
        hidden]. Returns (y, states', tails')."""
        from .kernels import ssm_pallas as ssm
        from .models import nemotron_h as nh
        cfg, p = self.cfg, self._part(w, i)
        back, slot, (last_slot, last_row) = plan
        with jax.named_scope("ssm_proj"):
            # held once: its three readers stand far apart, and the
            # compiler would rather compute the product again for each
            z, xbc, dt_raw = nh.mamba_split(jax.lax.optimization_barrier(
                x @ p["in_proj.weight"]), cfg)
        with jax.named_scope("ssm_conv"):
            c = xbc.shape[1]
            # [T, (K - 1) * C], read once: a second reading would have to
            # come before the new tails are written, and so cost a copy of
            # the pool
            tail = jax.lax.optimization_barrier(tails[m][slot])
            taps = [xbc]
            for row, here, places in back:         # 1 .. K - 1 rows back
                tap = jnp.where(here[:, None], xbc[row], 0)
                for at, kept in places.items():
                    tap = jnp.where(kept[:, None],
                                    tail[:, at * c:(at + 1) * c], tap)
                taps.append(tap)
            taps = jnp.stack(taps[::-1], axis=1)   # oldest first
            act = nh.conv_act(taps, p["conv1d.weight"], p["conv1d.bias"])
            tails = tails.at[m, last_slot].set(
                taps[last_row, 1:].reshape(last_row.shape[0], -1),
                mode="drop")
        with jax.named_scope("ssm_scan"):
            xs, b, c, dt, decay = nh.ssm_terms(act, dt_raw, p, cfg)
            y, states = ssm.ssm_scan(states, m, xs, b, c, dt, decay, meta,
                                     kernel=kernel)
            y = y + (p["D"].astype(jnp.float32)[:, None]
                     * xs.astype(jnp.float32)).reshape(y.shape)
        with jax.named_scope("ssm_proj"):
            y = nh.gated_norm(y, z, p["norm.weight"], cfg)
            return y @ p["out_proj.weight"], states, tails

    def _attention(self, w, i, x, kf, vf, scatter, attend):
        """Attention layer ``i`` on normed rows x [T, hidden], on its cache
        entry of the joined pools. Returns (y, kf', vf')."""
        from .models import nemotron_h as nh
        p = self._part(w, i)
        with jax.named_scope("attn_proj"):
            q, k, v = nh.attention_rows(x, p, self.cfg)
        kf, vf = _kv_write_pages(kf, vf, k[:, None], v[:, None], scatter)
        att = attend(q, kf, vf).reshape(x.shape[0], -1)
        with jax.named_scope("attn_proj"):
            return att @ p["o_proj.weight"], kf, vf

    def _moe(self, w, i, x, valid, kernel):
        """Expert layer ``i`` on normed rows x [T, hidden]; valid: [T]
        bool, rows that are somebody's (the others are routed nowhere).
        Returns (y, counters [5] int32)."""
        from .kernels import grouped_experts_pallas as ge
        from .models import nemotron_h as nh
        cfg, p = self.cfg, self._part(w, i)
        t, k, held_n = x.shape[0], cfg.num_experts_per_tok, cfg.experts_held
        with jax.named_scope("moe_route"):
            chosen, weight = nh.route(x, p["gate.weight"],
                                      p["gate.e_score_correction_bias"], cfg)
            local = chosen - cfg.first_expert
            held = (local >= 0) & (local < held_n) & valid[:, None]
            keys = jnp.where(held, local, held_n).reshape(-1)
            sizes, tile_group, n_live, row_pair, pair_row = ge.group_plan(
                keys, held_n)
        with jax.named_scope("moe_latent"):
            lat = x @ p["fc1_latent_proj.weight"]
        with jax.named_scope("moe_route"):
            xs = jnp.where((row_pair >= 0)[:, None],
                           lat[jnp.maximum(row_pair, 0) // k], 0)
        with jax.named_scope("moe_experts"):
            ys = ge.grouped_experts(xs, tile_group, n_live, None,
                                    p["experts.up_proj"],
                                    p["experts.down_proj"], kernel=kernel)
            mine = ys[jnp.maximum(pair_row, 0)].reshape(t, k, -1)
            y = jnp.sum(jnp.where(held[..., None],
                                  weight[..., None] * mine.astype(jnp.float32),
                                  0.0), axis=1).astype(x.dtype)
        with jax.named_scope("moe_latent"):
            y = y @ p["fc2_latent_proj.weight"]
        with jax.named_scope("moe_shared"):
            y = y + nh.relu2_ffn(x, p["shared_experts.up_proj.weight"],
                                 p["shared_experts.down_proj.weight"])
        with jax.named_scope("moe_route"):
            counters = jnp.stack([
                valid.sum() * k, held.sum(), 0, sizes.max(),
                (sizes > 0).sum()]).astype(jnp.int32)
        return y, counters

    @jax.named_scope("head")
    def _logits(self, w, h):
        return _rms(h, w["backbone.norm_f.weight"], self.eps) \
            @ w["lm_head.weight"].T

    # -- the step program --------------------------------------------------------
    def step_ragged(self, w, tokens, positions, k_pools, v_pools, scatter,
                    attend, state, shard=None):
        """See _LlamaDecoder.step_ragged; k_pools, v_pools: one entry an
        ATTENTION layer. state: ((states [state layers, slots, tiles,
        state, lanes] float32, tails [state layers, slots, (K - 1) x conv
        channels]), slot_ids [T], valid [T]): the engine's state pools and
        whose each row is. The second result is the step's routing counters
        (``COUNTERS``), the fifth the state pools advanced: the scheduled
        sequences' slots hold what their next rows start from, every other
        slot is as it came."""
        from .kernels import ssm_pallas as ssm
        (states, tails), slot_ids, valid = state
        kernel = shard is None
        with jax.named_scope("embed"):
            h = w[self.embed_key][tokens]
        seam = _entry_seams(k_pools.shape, scatter, attend)
        kf, vf = _join_entries(k_pools, v_pools)
        with jax.named_scope("ssm_scan"):
            meta = ssm.scan_meta(slot_ids, positions, valid, states.shape[1])
        plan = self._tap_plan(meta, positions)
        total = jnp.zeros(len(self.COUNTERS), jnp.int32)
        seen = {"M": 0, "*": 0}
        for i, kind in enumerate(self.kinds):
            with jax.named_scope({"M": "ssm_proj", "*": "attn_proj",
                                  "E": "moe_route"}[kind]):
                x = _rms(h, w[f"backbone.layers.{i}.norm.weight"], self.eps)
            if kind == "M":
                y, states, tails = self._mamba(w, i, x, seen["M"], states,
                                               tails, meta, plan, kernel)
            elif kind == "*":
                y, kf, vf = self._attention(w, i, x, kf, vf, *seam(seen["*"]))
            else:
                y, counters = self._moe(w, i, x, valid, kernel)
                total = total + counters
            seen[kind] = seen.get(kind, 0) + 1
            h = h + y
        return (self._logits(w, h), total,
                *_split_entries(kf, vf, k_pools.shape), (states, tails))


__all__ = ["generate", "draft_greedy", "draft_greedy_batch"]
