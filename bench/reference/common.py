"""What the plain references share: float32 matrix products at the highest
precision, and the lower-precision stand-in the controls compute in.

Nothing here, or in the block files beside it, imports the system under
test or takes anything it made.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST
_FP8_MAX = 448.0      # float8_e4m3fn


def fp8(x):
    """``x`` as float8 (e4m3, scaled by the tensor's largest magnitude)
    would hold it, back in float32; gradients pass straight through. The
    nearest precision below bfloat16, for the controls."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _FP8_MAX
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def mm(x, w, q=None):
    """``x @ w`` in float32, six passes; with ``q`` both operands are first
    rounded to the control's precision."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if q is not None:
        x, w = q(x), q(w)
    return jnp.matmul(x, w, precision=_HIGHEST)


def causal_attention(qh, kh, vh):
    """qh, kh, vh: [S, heads, d] float32 of one sequence, heads equal.
    Full causal softmax attention, nothing blocked or cached."""
    s, _, d = qh.shape
    scores = jnp.einsum("shd,thd->hst", qh, kh, precision=_HIGHEST) \
        / jnp.sqrt(jnp.float32(d))
    mask = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(mask[None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("hst,thd->shd", p, vh, precision=_HIGHEST)
