"""Pallas TPU kernel: fused (multi-tensor) AdamW update.

Reference parity: phi/kernels/fused_adam_kernel.h (FusedAdamKernel — the
multi-tensor apply that updates every parameter of a group in one launch)
and phi/kernels/adamw_kernel.h (fused decoupled-decay update).

TPU-native design: the whole parameter group is flattened and concatenated
into ONE 1-D buffer per role (p/g/m/v) and a single Pallas kernel streams
it block-by-block through VMEM with fp32 math in registers. The kernel
itself is four HBM reads + three writes per element; the concat prologue
and split epilogue add device-side copies (compiled into the same program
so XLA schedules them around the launch) — a persistent flat-buffer
optimizer state would remove those and is the natural extension. What one
launch buys over XLA's per-tensor fusions (which are already good — that
is why `merged_adam_` is decided-out as an *op*, OPS_COVERAGE.md:303) is
launch-overhead amortization and no per-tensor tail effects. OFF by
default — FLAGS_use_pallas_fused routes Adam/AdamW's step through it on
TPU; the jnp update stays the numerics oracle and fallback.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import fused_pallas as _fp


def _adamw_kernel(p_ref, g_ref, m_ref, v_ref, sc_ref,
                  op_ref, om_ref, ov_ref):
    """One VMEM block of the flat group, viewed 2-D [rows, 1024] (Mosaic
    wants >=2-D refs with a 128-multiple lane dim; the 1-D original
    crashed the TPU compiler in r04). sc_ref: [8] f32
    scalars in SMEM (lr, beta1, beta2, eps, wd, bc1, bc2, decoupled) —
    a VMEM scalar block would violate the (8,128) tile divisibility."""
    lr, b1, b2, eps = sc_ref[0], sc_ref[1], sc_ref[2], sc_ref[3]
    wd, bc1, bc2, dec = sc_ref[4], sc_ref[5], sc_ref[6], sc_ref[7]
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    m = m_ref[...]
    v = v_ref[...]
    # coupled (Adam+L2): decay joins the gradient; decoupled (AdamW):
    # decay scales the parameter directly
    g = g + (1.0 - dec) * wd * p
    m_new = b1 * m + (1.0 - b1) * g
    v_new = b2 * v + (1.0 - b2) * g * g
    upd = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps)
    p = p * (1.0 - dec * lr * wd) - lr * upd
    op_ref[...] = p.astype(op_ref.dtype)
    om_ref[...] = m_new
    ov_ref[...] = v_new


_LANES = 1024
# flat buffers are padded to _PAD elements = 64 rows of _LANES, so every
# [block_rows, _LANES] tile is divisible by both the f32 (8,128) and bf16
# (16,128) Mosaic tiles regardless of group size (padding only to _LANES
# made thin blocks for tiny groups, which Mosaic refused)
_PAD = _LANES * 64


@functools.partial(jax.jit, static_argnames=("decoupled", "block_rows"))
def _fused_adamw_flat(p, g, m, v, lr, beta1, beta2, eps, wd, step,
                      decoupled: bool, block_rows: int = 64):
    """p/g: flat [n] (param dtype), n a multiple of _PAD; m/v: flat [n]
    f32; scalars f32. The kernel streams [block_rows, _LANES] tiles."""
    n = p.shape[0]
    rows = n // _LANES
    br = _fp._best_block(rows, block_rows)
    bc1 = 1.0 - beta1 ** step
    bc2 = 1.0 - beta2 ** step
    sc = jnp.stack([lr, beta1, beta2, eps, wd, bc1, bc2,
                    jnp.float32(1.0 if decoupled else 0.0)])
    grid = (rows // br,)
    blk = pl.BlockSpec((br, _LANES), lambda i: (i, 0))
    sc_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    view = lambda a: a.reshape(rows, _LANES)
    op, om, ov = pl.pallas_call(
        _adamw_kernel,
        grid=grid,
        in_specs=[blk, blk, blk, blk, sc_spec],
        out_specs=[blk, blk, blk],
        out_shape=[jax.ShapeDtypeStruct((rows, _LANES), p.dtype),
                   jax.ShapeDtypeStruct((rows, _LANES), jnp.float32),
                   jax.ShapeDtypeStruct((rows, _LANES), jnp.float32)],
        interpret=_fp._INTERPRET,
    )(view(p), view(g), view(m), view(v), sc)
    return op.reshape(n), om.reshape(n), ov.reshape(n)


def _pad_to(x, mult):
    r = (-x.shape[0]) % mult
    return jnp.pad(x, (0, r)) if r else x


def fused_adamw_pallas(p, g, m, v, *, lr, beta1, beta2, eps, wd, step,
                       decoupled=True):
    """Single-tensor fused update: returns (p_new, m_new, v_new) with the
    same math as the jnp oracle (optimizer/__init__.py _adam_update).
    Flat views are padded to the TPU lane multiple; pad elements update
    junk that is sliced away."""
    shape = p.shape
    n = p.size
    out_p, out_m, out_v = _fused_adamw_flat(
        _pad_to(p.reshape(-1), _PAD), _pad_to(g.reshape(-1), _PAD),
        _pad_to(m.reshape(-1), _PAD), _pad_to(v.reshape(-1), _PAD),
        jnp.float32(lr), jnp.float32(beta1), jnp.float32(beta2),
        jnp.float32(eps), jnp.float32(wd), jnp.float32(step),
        bool(decoupled))
    return (out_p[:n].reshape(shape), out_m[:n].reshape(shape),
            out_v[:n].reshape(shape))


@functools.partial(jax.jit, static_argnames=("decoupled",))
def _group_update(ps, gs, ms, vs, lr, beta1, beta2, eps, wd, step,
                  decoupled):
    """One compiled program per group shape-set: concat prologue -> one
    Pallas launch -> split epilogue. The concat/split are device-side
    copies XLA schedules around the single kernel; a persistent
    flat-buffer optimizer state would eliminate them entirely and is the
    natural next step at scale — the launch amortization is what this
    path buys today."""
    flat_p = jnp.concatenate([p.reshape(-1) for p in ps])
    flat_g = jnp.concatenate([g.reshape(-1) for g in gs])
    flat_m = jnp.concatenate([m.reshape(-1) for m in ms])
    flat_v = jnp.concatenate([v.reshape(-1) for v in vs])
    np_, nm, nv = _fused_adamw_flat(
        _pad_to(flat_p, _PAD), _pad_to(flat_g, _PAD),
        _pad_to(flat_m, _PAD), _pad_to(flat_v, _PAD),
        lr, beta1, beta2, eps, wd, step, decoupled)
    out_p, out_m, out_v = [], [], []
    off = 0
    for p in ps:
        sz = p.size
        out_p.append(np_[off:off + sz].reshape(p.shape))
        out_m.append(nm[off:off + sz].reshape(p.shape))
        out_v.append(nv[off:off + sz].reshape(p.shape))
        off += sz
    return out_p, out_m, out_v


def multi_tensor_adamw_pallas(params, grads, ms, vs, *, lr, beta1, beta2,
                              eps, wds, step, decoupled=True):
    """Multi-tensor apply (FusedAdamKernel capability): every tensor of
    the group with the SAME weight-decay coefficient updates through one
    compiled concat -> kernel -> split program; distinct wd values (e.g.
    no-decay bias/norm groups) get one program each.

    params/grads/ms/vs: lists of arrays; wds: per-tensor wd floats.
    Grads pass at their own dtype (the kernel upcasts to f32 internally);
    note Adam.step pre-casts grads to the param dtype for exact parity
    with the per-tensor oracle, so the dtype split below only engages for
    direct callers that keep fp32 grads against bf16 params.
    Returns (new_params, new_ms, new_vs) lists in input order.
    """
    if not (len(params) == len(grads) == len(ms) == len(vs) == len(wds)):
        raise ValueError("multi_tensor_adamw: list length mismatch")
    out_p = [None] * len(params)
    out_m = [None] * len(params)
    out_v = [None] * len(params)
    groups = {}
    for i, (p, g, wd) in enumerate(zip(params, grads, wds)):
        groups.setdefault((float(wd), p.dtype, g.dtype), []).append(i)
    for (wd, _pdt, _gdt), idxs in groups.items():
        nps, nms, nvs = _group_update(
            [params[i] for i in idxs], [grads[i] for i in idxs],
            [ms[i] for i in idxs], [vs[i] for i in idxs],
            jnp.float32(lr), jnp.float32(beta1), jnp.float32(beta2),
            jnp.float32(eps), jnp.float32(wd), jnp.float32(step),
            bool(decoupled))
        for i, np_, nm, nv in zip(idxs, nps, nms, nvs):
            out_p[i] = np_
            out_m[i] = nm
            out_v[i] = nv
    return out_p, out_m, out_v


__all__ = ["fused_adamw_pallas", "multi_tensor_adamw_pallas"]
