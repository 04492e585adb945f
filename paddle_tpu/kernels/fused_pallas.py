"""Pallas TPU kernels: fused rope and fused RMSNorm(+residual).

Reference parity: phi/kernels/fusion/gpu/fused_rope_kernel.cu:27
(FusedRopeKernel) and fused_layernorm_kernel.cu / fused_rms_norm — the
memory-bound fusion list SURVEY §7 step 7 names. XLA already fuses these
elementwise chains into neighbors well; the Pallas versions exist to pin
the layout (single HBM pass, fp32 accumulation in VMEM) where profiles show
XLA splitting the chain. They are OFF by default — FLAGS_use_pallas_fused
routes the model-level fused_rope / rms_norm through them on TPU; the jnp
implementations remain the numerics oracle and the fallback.

Both kernels are forward-custom only (backward = jax AD of the jnp oracle
via custom_vjp's recompute): these ops are cheap relative to attention, so
the win is the forward HBM pass, not a bespoke backward.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..framework import flags
from . import on_tpu

flags.define_flag("use_pallas_fused", False,
                  "Route fused_rope/rms_norm through the Pallas kernels on "
                  "TPU (default: XLA-fused jnp).")

_INTERPRET = False  # tests flip


def _best_block(n: int, target: int) -> int:
    """Largest divisor of n that is <= target (whole-array blocks would blow
    the ~16MB VMEM budget for long sequences)."""
    b = min(target, n)
    while n % b:
        b -= 1
    return b


def enabled() -> bool:
    return flags.flag("use_pallas_fused") and (_INTERPRET or on_tpu())


# -- fused rope ---------------------------------------------------------------
# q,k: [b, s, h, d]; cos/sin: [s, d/2]. Interleaved-pair rotation (llama).
#
# Mosaic constraint: >2D gathers don't lower, so the pair rotation is NOT
# written as strided slices (x[..., 0::2]). Instead the host precomputes
# lane-duplicated cos/sin ([s, d], each value repeated per pair) and the
# kernel builds the rotated operand with two rolls along the lane axis plus
# constant even/odd masks — contiguous slices and elementwise only:
#   rot[2i] = -x[2i+1] = (roll(x,-1) * m_even_neg)[2i]
#   rot[2i+1] = x[2i]  = (roll(x,+1) * m_odd)[2i+1]
#   out = x * cos_dup + rot * sin_dup

def _rope_kernel(q_ref, k_ref, cos_ref, sin_ref, mneg_ref, mpos_ref,
                 oq_ref, ok_ref):
    c = cos_ref[0]                                  # [Bs, d] fp32
    s = sin_ref[0]
    m_neg = mneg_ref[0]                             # [1, d]: -1 even, 0 odd
    m_pos = mpos_ref[0]                             # [1, d]: 0 even, +1 odd
    for src, dst in ((q_ref, oq_ref), (k_ref, ok_ref)):
        x = src[0].astype(jnp.float32)              # [Bs, h, d]
        rot = (jnp.roll(x, -1, axis=-1) * m_neg[None]
               + jnp.roll(x, 1, axis=-1) * m_pos[None])
        out = x * c[:, None, :] + rot * s[:, None, :]
        dst[0] = out.astype(dst.dtype)


def fused_rope_pallas(q, k, cos, sin, block_s: int = 256):
    """One HBM pass over q and k (parity: fused_rope_kernel.cu:27)."""
    b, s, h, d = q.shape
    bs = _best_block(s, block_s)
    ns = s // bs
    cos2 = jnp.repeat(cos.astype(jnp.float32), 2, axis=-1)      # [s, d]
    sin2 = jnp.repeat(sin.astype(jnp.float32), 2, axis=-1)
    lane = jnp.arange(d, dtype=jnp.int32) % 2
    m_neg = jnp.where(lane == 0, -1.0, 0.0).astype(jnp.float32)[None]
    m_pos = jnp.where(lane == 1, 1.0, 0.0).astype(jnp.float32)[None]
    oq, ok = pl.pallas_call(
        _rope_kernel,
        grid=(b, ns),
        in_specs=[
            pl.BlockSpec((1, bs, h, d), lambda ib, i: (ib, i, 0, 0)),
            pl.BlockSpec((1, bs, k.shape[2], d), lambda ib, i: (ib, i, 0, 0)),
            pl.BlockSpec((1, bs, d), lambda ib, i: (0, i, 0)),
            pl.BlockSpec((1, bs, d), lambda ib, i: (0, i, 0)),
            pl.BlockSpec((1, 1, d), lambda ib, i: (0, 0, 0)),
            pl.BlockSpec((1, 1, d), lambda ib, i: (0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bs, h, d), lambda ib, i: (ib, i, 0, 0)),
            pl.BlockSpec((1, bs, k.shape[2], d), lambda ib, i: (ib, i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
        ],
        interpret=_INTERPRET,
    )(q, k, cos2[None], sin2[None], m_neg[None], m_pos[None])
    return oq, ok


# -- fused RMSNorm(+residual) -------------------------------------------------

def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps, has_residual, r_ref=None):
    x = x_ref[0].astype(jnp.float32)                # [Br, hidden]
    if has_residual:
        x = x + r_ref[0].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(ms + eps) * w_ref[0].astype(jnp.float32)
    o_ref[0] = y.astype(o_ref.dtype)


def _rmsnorm_res_kernel(x_ref, r_ref, w_ref, o_ref, *, eps):
    _rmsnorm_kernel(x_ref, w_ref, o_ref, eps=eps, has_residual=True,
                    r_ref=r_ref)


def fused_rms_norm_pallas(x, weight, eps: float = 1e-6, residual=None):
    """RMSNorm (optionally fused with a residual add) in one HBM pass
    (parity: fused_layernorm_kernel.cu / fused_rms_norm capability)."""
    orig_shape = x.shape
    hidden = orig_shape[-1]
    rows = 1
    for dd in orig_shape[:-1]:
        rows *= dd
    xr = x.reshape(rows, hidden)
    # Row block sized so ONE float32 working copy of the tile is 1 MiB.
    # The kernel holds a few of those beside the double-buffered input,
    # residual and output tiles; 512 rows of hidden 2048 asked Mosaic for
    # 16.2 MiB of its 16 MiB scoped VMEM on a v5e (PERF.md, PR 21).
    br = _best_block(rows, max(16, (1 << 18) // hidden))
    nr = rows // br
    if residual is not None:
        rr = residual.reshape(rows, hidden)
        out = pl.pallas_call(
            functools.partial(_rmsnorm_res_kernel, eps=eps),
            grid=(nr,),
            in_specs=[
                pl.BlockSpec((1, br, hidden), lambda i: (0, i, 0)),
                pl.BlockSpec((1, br, hidden), lambda i: (0, i, 0)),
                pl.BlockSpec((1, hidden), lambda i: (0, 0)),
            ],
            out_specs=pl.BlockSpec((1, br, hidden), lambda i: (0, i, 0)),
            out_shape=jax.ShapeDtypeStruct((1, rows, hidden), x.dtype),
            interpret=_INTERPRET,
        )(xr[None], rr[None], weight[None])
    else:
        out = pl.pallas_call(
            functools.partial(_rmsnorm_kernel, eps=eps, has_residual=False),
            grid=(nr,),
            in_specs=[
                pl.BlockSpec((1, br, hidden), lambda i: (0, i, 0)),
                pl.BlockSpec((1, hidden), lambda i: (0, 0)),
            ],
            out_specs=pl.BlockSpec((1, br, hidden), lambda i: (0, i, 0)),
            out_shape=jax.ShapeDtypeStruct((1, rows, hidden), x.dtype),
            interpret=_INTERPRET,
        )(xr[None], weight[None])
    return out.reshape(orig_shape)
