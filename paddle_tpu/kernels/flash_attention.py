"""Flash attention (Pallas TPU kernel + availability gate).

Reference parity: paddle/phi/kernels/gpu/flash_attn_kernel.cu:673 (FA2 via
dynload). TPU-native: online-softmax tiled kernel in Pallas (implemented in
flash_pallas.py); this module is the dispatch gate. Falls back to the XLA
reference path (nn/functional/attention.py) when shapes/platform don't fit.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import on_tpu


def is_available(q, k=None, causal=False) -> bool:
    """Pallas kernel requires TPU + seq/head-dim tiling-friendly shapes for
    BOTH q and k/v (a non-divisible kv length would silently truncate), and
    q_len <= kv_len for causal (bottom-right alignment leaves leading rows
    keyless otherwise — the XLA fallback defines that case)."""
    if not on_tpu():
        return False
    if q.ndim != 4:
        return False
    _, seq, _, head_dim = q.shape
    if k is not None:
        if k.ndim != 4 or k.shape[1] % 128 != 0 or \
                k.shape[3] != head_dim or k.dtype != q.dtype:
            return False
        if causal and seq > k.shape[1]:
            return False
    return seq % 128 == 0 and head_dim in (64, 128, 256) and \
        q.dtype in (jnp.float32, jnp.bfloat16)


def _tune_signature(q_bshd, k_bshd, causal):
    # MUST match flash_pallas._resolve_blocks' lookup key: (sq, sk,
    # head_dim, dtype, causal) — batch and head count don't change the
    # per-tile geometry
    b, sq, h, d = q_bshd.shape
    return (sq, k_bshd.shape[1], d, str(q_bshd.dtype), bool(causal))


def tune_blocks(q_bshd, k_bshd, v_bshd, causal: bool = False, scale=None):
    """Autotune (block_q, block_k) for these CONCRETE [b,s,h,d] inputs and
    cache the winner under the 'flash_fwd' key (kernels/autotune.py).
    Traced call sites need nothing special: flash_pallas._resolve_blocks
    consults the cache at trace time, and its fallback chain gives the
    backward the forward's winner unless a bwd-specific entry exists."""
    from . import autotune
    sq, sk, d = q_bshd.shape[1], k_bshd.shape[1], q_bshd.shape[3]
    sig = _tune_signature(q_bshd, k_bshd, causal)
    return autotune.pick(
        "flash_fwd", sig,
        autotune.flash_block_candidates(sq, sk, d, q_bshd.dtype.itemsize),
        lambda c: flash_attention_bshd(q_bshd, k_bshd, v_bshd, causal=causal,
                                       scale=scale, block_q=c[0],
                                       block_k=c[1]))


def over_mesh(fn, mesh, batch_axes, q_shape, k_shape):
    """Run ``fn(q, k, v)`` ([batch, seq, heads, dim]) per shard of
    ``mesh``: batch over ``batch_axes``, heads over ``mp``.

    A ``pallas_call`` is opaque to GSPMD: bare inside a multi-device jit
    it does not lower on a TPU at all ("Mosaic kernels cannot be
    automatically partitioned. Please wrap the call in a shard_map").
    Attention is independent per (batch, head), so a manual region over
    exactly those axes keeps each chip on its own slice with no
    collective. Returns ``fn`` unchanged where nothing divides, or inside
    an enclosing manual region (the pipeline's stage region), whose axes
    a nested region may not rebind."""
    if mesh is None or q_shape[2] != k_shape[2] or \
            jax.sharding.get_abstract_mesh().manual_axes:
        return fn
    from jax.sharding import PartitionSpec as P
    from ..utils.jax_compat import shard_map

    def live(axis):
        return axis in mesh.dim_names and mesh.get_dim_size(axis) > 1

    b_axes = tuple(a for a in (batch_axes or ()) if live(a))
    b_deg = 1
    for a in b_axes:
        b_deg *= mesh.get_dim_size(a)
    if q_shape[0] % b_deg:
        b_axes = ()
    h_axis = "mp" if live("mp") and \
        q_shape[2] % mesh.get_dim_size("mp") == 0 else None
    if not b_axes and h_axis is None:
        return fn
    spec = P(b_axes or None, None, h_axis, None)
    return shard_map(fn, mesh=mesh.to_jax(), in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)


def flash_attention_bshd(q, k, v, causal: bool = False, scale=None,
                         block_q=None, block_k=None):
    """[batch, seq, heads, dim] layout wrapper around the Pallas kernel.
    Block sizes stay None unless the caller pins them: the kernel's own
    _resolve_blocks consults the autotune cache per direction (fwd AND
    bwd keys)."""
    from .flash_pallas import flash_attention as fa_bhsd
    # kernel uses [batch, heads, seq, dim]
    qh = jnp.swapaxes(q, 1, 2)
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    out = fa_bhsd(qh, kh, vh, causal=causal, scale=scale, block_q=block_q,
                  block_k=block_k)
    return jnp.swapaxes(out, 1, 2)
