"""Attention functionals.

Reference parity: python/paddle/nn/functional/flash_attention.py (flash_attention
:358, scaled_dot_product_attention, flashmask_attention :1299). TPU-native: the
fused path is a Pallas flash-attention kernel (paddle_tpu/kernels/flash_attention.py);
the reference XLA path below is the fallback and the numerics oracle.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ...framework.random import next_key
from ...ops.dispatch import dispatch, ensure_tensor
from ...tensor import Tensor


def _sdpa_reference(q, k, v, mask=None, dropout_p=0.0, causal=False,
                    scale=None, key=None):
    """q,k,v: [batch, seq, heads, dim] (reference layout). Returns same layout."""
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    # scores: [b, h, sq, sk]
    scores = jnp.einsum("bshd,bthd->bhst", qf, kf) * s
    if causal:
        sq, sk = scores.shape[-2], scores.shape[-1]
        causal_mask = jnp.tril(jnp.ones((sq, sk), jnp.bool_), k=sk - sq)
        scores = jnp.where(causal_mask, scores, -1e30)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            scores = jnp.where(mask, scores, -1e30)
        else:
            scores = scores + mask.astype(jnp.float32)
    probs = jax.nn.softmax(scores, axis=-1)
    if dropout_p > 0.0 and key is not None:
        keep = jax.random.bernoulli(key, 1.0 - dropout_p, probs.shape)
        probs = probs * keep / (1.0 - dropout_p)
    out = jnp.einsum("bhst,bthd->bshd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False, training=True,
                                 name=None, allow_flash=True):
    """Parity: paddle.nn.functional.scaled_dot_product_attention.

    Layout [batch, seq, num_heads, head_dim]. Uses the Pallas flash kernel
    on TPU for the mask-free case, XLA reference path otherwise.
    allow_flash=False (an additive knob; model configs' use_flash_attention
    routes here) forces the XLA path even where the kernel would fit.
    """
    qt, kt, vt = ensure_tensor(query), ensure_tensor(key), ensure_tensor(value)
    use_flash = attn_mask is None and dropout_p == 0.0 and allow_flash
    if use_flash:
        # Context parallelism: sequence sharded over the sep axis -> ring
        # attention (explicit KV rotation over ICI) instead of letting GSPMD
        # all-gather K/V.
        from ...parallel import context as pctx
        seq_ax = pctx.sequence_axis()
        if seq_ax is not None:
            from ...parallel.ring_attention import ring_attention
            mesh = pctx.current_mesh()
            baxes = pctx.batch_axes()
            return dispatch(
                "ring_attention",
                lambda q, k, v: ring_attention(q, k, v, mesh, seq_ax,
                                               batch_axes=baxes,
                                               causal=is_causal),
                qt, kt, vt)
        from ...kernels import flash_attention as fa
        if fa.is_available(qt._data, kt._data, causal=is_causal):
            from ...framework import flags as _flags
            if _flags.flag("use_autotune") and \
                    not isinstance(qt._data, jax.core.Tracer):
                # tune HERE, on concrete arrays, before dispatch's vjp
                # tracing makes everything a Tracer — and on the POST-AMP
                # dtype, which is what the kernel will actually execute
                from ...ops.dispatch import _amp_cast
                tq, tk, tv = _amp_cast(
                    "flash_attention", (qt._data, kt._data, vt._data))
                fa.tune_blocks(tq, tk, tv, causal=is_causal)
            flash = fa.over_mesh(
                lambda q, k, v: fa.flash_attention_bshd(q, k, v,
                                                        causal=is_causal),
                pctx.current_mesh(), pctx.batch_axes(),
                qt._data.shape, kt._data.shape)
            return dispatch("flash_attention", flash, qt, kt, vt)
    p_drop = float(dropout_p) if training else 0.0
    key = next_key() if p_drop > 0.0 else None
    if attn_mask is not None:
        mt = ensure_tensor(attn_mask)
        return dispatch(
            "sdpa",
            lambda q, k, v, m: _sdpa_reference(q, k, v, mask=m,
                                               causal=is_causal,
                                               dropout_p=p_drop, key=key),
            qt, kt, vt, mt)
    return dispatch(
        "sdpa", lambda q, k, v: _sdpa_reference(q, k, v, causal=is_causal,
                                                dropout_p=p_drop, key=key),
        qt, kt, vt)


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, fixed_seed_offset=None, rng_name="",
                    training=True, name=None):
    """Parity: paddle.nn.functional.flash_attention.flash_attention (:358)."""
    out = scaled_dot_product_attention(query, key, value, dropout_p=dropout,
                                       is_causal=causal, training=training)
    return out, None


def _canonical_startend(se, sq, causal):
    """Normalize startend_row_indices [B, KH, Sk, C] (C in {1, 2, 4}; see the
    reference doc at flash_attention.py:1299) to the canonical component
    stack (LTS, LTE, UTS, UTE) [B, KH, Sk, 4]: strict-lower-triangle rows
    [LTS, LTE) and strict-upper-triangle rows [UTS, UTE) are masked per key
    column."""
    se = se.astype(jnp.int32)
    c = se.shape[-1]
    zeros = jnp.zeros_like(se[..., 0])
    full = jnp.full_like(se[..., 0], sq)
    if causal:
        if c == 1:
            lts, lte, uts, ute = se[..., 0], full, zeros, zeros
        elif c == 2:
            lts, lte, uts, ute = se[..., 0], se[..., 1], zeros, zeros
        else:
            raise ValueError(
                f"causal flashmask expects startend_row_indices with last "
                f"dim 1 or 2, got {c}")
    else:
        if c == 2:
            lts, lte, uts, ute = se[..., 0], full, zeros, se[..., 1]
        elif c == 4:
            lts, lte, uts, ute = (se[..., 0], se[..., 1], se[..., 2],
                                  se[..., 3])
        else:
            raise ValueError(
                f"non-causal flashmask expects startend_row_indices with "
                f"last dim 2 or 4, got {c}")
    return jnp.stack([lts, lte, uts, ute], axis=-1)


def _flashmask_dense_visible(bounds, sq, sk, causal, window):
    """Dense [B, H, Sq, Sk] visibility mask from canonical bounds — the jnp
    oracle / fallback for the Pallas flashmask kernel (same semantics as
    kernels/flash_pallas._flashmask_visible)."""
    i = jnp.arange(sq)[:, None]
    j = jnp.arange(sk)[None, :]
    lts = bounds[..., None, :, 0]                         # [B, KH, 1, Sk]
    lte = bounds[..., None, :, 1]
    masked_low = (i > j) & (i >= lts) & (i < lte)
    if causal:
        masked_up = (i < j) & jnp.ones_like(masked_low)
    else:
        uts = bounds[..., None, :, 2]
        ute = bounds[..., None, :, 3]
        masked_up = (i < j) & (i >= uts) & (i < ute)
    masked = masked_low | masked_up
    if window is not None:
        wl, wr = window
        if wl is not None:
            masked = masked | (i > j + wl)
        if not causal and wr is not None:
            masked = masked | (i < j - wr)
    return ~masked


def _norm_window(window_size, causal):
    if window_size is None:
        return None
    if isinstance(window_size, int):
        wl = wr = int(window_size)
    else:
        wl, wr = (int(w) if w is not None else None for w in window_size)
    return (wl, None) if causal else (wl, wr)


def flashmask_attention(query, key, value, startend_row_indices=None, *,
                        dropout=0.0, causal=False, window_size=None,
                        return_softmax_lse=False, return_seed_offset=False,
                        fixed_seed_offset=None, rng_name="", training=True,
                        name=None):
    """FlashMask sparse-mask attention (parity:
    paddle.nn.functional.flashmask_attention, flash_attention.py:1299 —
    arXiv 2410.01359). Layout [batch, seq, num_heads, head_dim]; GQA
    supported (kv heads broadcast to query heads).

    startend_row_indices [B, KH, Sk, {1, 2, 4}] int32 gives per-key-column
    masked row bands — O(S) memory instead of an O(S^2) dense mask. On TPU
    with tiling-friendly shapes this runs the Pallas flashmask kernel
    (kernels/flash_pallas.flashmask_attention): fully-masked tiles are
    skipped on-device, so block-sparse masks (causal documents, sequence
    packing) cost compute proportional to the visible area. Elsewhere (CPU,
    odd shapes, dropout, return_softmax_lse) it falls back to the dense-mask
    XLA path with identical numerics."""
    if return_seed_offset:
        raise NotImplementedError(
            "return_seed_offset tracks the reference's CUDA dropout RNG "
            "state; randomness here comes from the framework PRNG "
            "(framework.random), which has no seed-offset notion")
    qt, kt, vt = ensure_tensor(query), ensure_tensor(key), ensure_tensor(value)
    b, sq, h, d = qt._data.shape
    sk, kh = kt._data.shape[1], kt._data.shape[2]
    window = _norm_window(window_size, causal)

    if startend_row_indices is None and window is None:
        out = scaled_dot_product_attention(qt, kt, vt, dropout_p=dropout,
                                           is_causal=causal,
                                           training=training)
        if return_softmax_lse:
            raise NotImplementedError(
                "return_softmax_lse requires startend_row_indices")
        return out

    if startend_row_indices is not None:
        se = ensure_tensor(startend_row_indices)._data
        if se.ndim != 4 or se.shape[2] != sk:
            raise ValueError(
                f"startend_row_indices must be [batch, kv_heads, {sk}, C], "
                f"got {se.shape}")
        bounds = _canonical_startend(se, sq, causal)       # [B, KH', Sk, 4]
    else:
        # window-only: empty bands (nothing extra masked)
        bounds = jnp.broadcast_to(
            jnp.array([sq, sq, 0, 0], jnp.int32), (b, 1, sk, 4))
    # broadcast mask heads to query heads (KH' in {1, kh}; GQA groups share)
    if bounds.shape[1] == 1:
        bounds_h = jnp.broadcast_to(bounds, (b, h, sk, 4))
    elif bounds.shape[1] == kh and kh != h:
        bounds_h = jnp.repeat(bounds, h // kh, axis=1)
    elif bounds.shape[1] == h:
        bounds_h = bounds
    else:
        raise ValueError(
            f"startend_row_indices kv_heads dim {bounds.shape[1]} must be 1, "
            f"{kh}, or {h}")

    p_drop = float(dropout) if training else 0.0
    from ...kernels import flash_attention as fa
    use_pallas = (p_drop == 0.0 and not return_softmax_lse and sq == sk
                  and fa.is_available(qt._data, kt._data, causal=causal))
    if use_pallas:
        from ...kernels import flash_pallas as fp

        def fwd(q, k, v):
            qh = jnp.swapaxes(q, 1, 2)
            kh_ = jnp.swapaxes(k, 1, 2)
            vh = jnp.swapaxes(v, 1, 2)
            if kh_.shape[1] != h:                          # GQA: expand kv
                kh_ = jnp.repeat(kh_, h // kh_.shape[1], axis=1)
                vh = jnp.repeat(vh, h // vh.shape[1], axis=1)
            out = fp.flashmask_attention(qh, kh_, vh, bounds_h,
                                         causal=causal, window=window)
            return jnp.swapaxes(out, 1, 2)

        return dispatch("flashmask_attention", fwd, qt, kt, vt)

    visible = _flashmask_dense_visible(bounds_h, sq, sk, causal, window)
    key_rng = next_key() if p_drop > 0.0 else None

    def fwd_dense(q, k, v):
        kr, vr = k, v
        if kr.shape[2] != h:                               # GQA: expand kv
            kr = jnp.repeat(kr, h // kr.shape[2], axis=2)
            vr = jnp.repeat(vr, h // vr.shape[2], axis=2)
        return _sdpa_reference(q, kr, vr, mask=visible, dropout_p=p_drop,
                               key=key_rng)

    out = dispatch("flashmask_attention", fwd_dense, qt, kt, vt)
    if return_softmax_lse:
        qf = qt._data.astype(jnp.float32)
        kf = kt._data.astype(jnp.float32)
        if kf.shape[2] != h:
            kf = jnp.repeat(kf, h // kf.shape[2], axis=2)
        scores = jnp.einsum("bshd,bthd->bhst", qf, kf) / math.sqrt(d)
        scores = jnp.where(visible, scores, -1e30)
        lse = jax.scipy.special.logsumexp(scores, axis=-1)
        return out, Tensor(lse)
    return out


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k,
                        max_seqlen_q, max_seqlen_k, scale, dropout=0.0,
                        causal=False, return_softmax=False,
                        fixed_seed_offset=None, rng_name="", training=True,
                        name=None):
    """Varlen flash attention: q/k/v are [total_tokens, heads, dim] packed.

    Implemented as a segment-masked SDPA (segment ids derived from cu_seqlens).
    """
    qt, kt, vt = ensure_tensor(query), ensure_tensor(key), ensure_tensor(value)
    cq = ensure_tensor(cu_seqlens_q)
    ck = ensure_tensor(cu_seqlens_k)

    def fwd(q, k, v, cu_q, cu_k):
        total_q = q.shape[0]
        total_k = k.shape[0]
        seg_q = jnp.searchsorted(cu_q, jnp.arange(total_q), side="right")
        seg_k = jnp.searchsorted(cu_k, jnp.arange(total_k), side="right")
        mask = seg_q[:, None] == seg_k[None, :]
        scores = jnp.einsum("shd,thd->hst", q.astype(jnp.float32),
                            k.astype(jnp.float32)) * scale
        if causal:
            pos_q = jnp.arange(total_q) - jnp.take(cu_q, seg_q - 1)
            pos_k = jnp.arange(total_k) - jnp.take(cu_k, seg_k - 1)
            mask = mask & (pos_q[:, None] >= pos_k[None, :])
        scores = jnp.where(mask[None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("hst,thd->shd", probs, v.astype(jnp.float32))
        return out.astype(q.dtype)
    return dispatch("flash_attn_unpadded", fwd, qt, kt, vt, cq, ck), None


def sdp_kernel(*args, **kwargs):  # config context no-op (XLA chooses)
    import contextlib
    return contextlib.nullcontext()


def flash_attn_qkvpacked(qkv, dropout=0.0, causal=False,
                         return_softmax=False, fixed_seed_offset=None,
                         rng_name="", training=True, name=None):
    """Parity: F.flash_attn_qkvpacked (flash_attention.py qkvpacked
    variant): qkv packed [batch, seq, 3, heads, dim] — unpack and ride
    the flash path (the packed layout exists for CUDA kernel-argument
    efficiency; XLA slices fuse into the same reads)."""
    t = ensure_tensor(qkv)
    if t.shape[2] != 3:
        raise ValueError(
            f"flash_attn_qkvpacked expects [b, s, 3, h, d], got {t.shape}")
    q = t[:, :, 0]
    k = t[:, :, 1]
    v = t[:, :, 2]
    return flash_attention(q, k, v, dropout=dropout, causal=causal,
                           return_softmax=return_softmax, training=training)


def flash_attn_varlen_qkvpacked(qkv, cu_seqlens_q, cu_seqlens_k,
                                max_seqlen_q, max_seqlen_k, scale,
                                dropout=0.0, causal=False,
                                return_softmax=False, fixed_seed_offset=None,
                                rng_name="", varlen_padded=True,
                                training=True, name=None):
    """Parity: F.flash_attn_varlen_qkvpacked — packed varlen form over
    the segment-masked SDPA path."""
    t = ensure_tensor(qkv)
    if t.shape[1] != 3:
        raise ValueError("flash_attn_varlen_qkvpacked expects "
                         f"[total, 3, h, d], got {t.shape}")
    q = t[:, 0]
    k = t[:, 1]
    v = t[:, 2]
    return flash_attn_unpadded(q, k, v, cu_seqlens_q, cu_seqlens_k,
                               max_seqlen_q, max_seqlen_k, scale,
                               dropout=dropout, causal=causal,
                               return_softmax=return_softmax,
                               training=training)
