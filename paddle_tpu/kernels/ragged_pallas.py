"""Pallas TPU ragged paged attention (serving decode path).

Reference capability: Ragged Paged Attention (PAPERS.md, arxiv
2604.15464) — one kernel serving mixed prefill+decode batches over
ragged page tables. This module is the flag-gated TPU path under
``serving.ragged.make_attend``; the pure-JAX implementation in
``serving/ragged.py`` stays the numerics oracle and the default
(FLAGS_use_ragged_pallas is OFF until a benchmark cell times it on the
chip, the same staging discipline as fused_pallas.py).

Design (this revision): every packed token is an independent query doing
an online-softmax walk over ITS page list — grid (T, MP), the page table
rides in scalar-prefetch memory so each kv tile's DMA is indexed by
``tables[t, p]`` before the body runs (the standard TPU paged-attention
pattern). That serves the continuous batcher's mixed-phase batches
correctly today; the RPA paper's fused prefill tiling (q-blocks of a
chunk sharing one page walk) is the planned upgrade once the chip can
time it.

MXU notes (pallas_guide): dots keep the input dtype and accumulate fp32
via preferred_element_type; the page walk is sequential ("arbitrary")
while tokens are parallel. On hardware the pool layout wants
(block_size, head_dim) tiles that are (8, 128)-aligned ((16, 128) for
bf16) — the tests' engine geometry is CPU-sized and runs the kernel in
interpret mode; ``tools/kernel_check.py`` runs it compiled at a serving
geometry.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..framework import flags
from . import on_tpu
from ..utils.jax_compat import tpu_compiler_params as _tpu_compiler_params

flags.define_flag("use_ragged_pallas", False,
                  "Route serving ragged paged attention through the Pallas "
                  "kernel on TPU (default: the pure-JAX reference).")

NEG_INF = -1e30
_INTERPRET = False  # tests flip this to run the kernel off-TPU


def enabled() -> bool:
    return flags.flag("use_ragged_pallas") and (_INTERPRET or on_tpu())


def _rpa_kernel(tabs_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                m_scratch, l_scratch, acc_scratch, *, bs, mp, rep):
    """One (token, page) cell: online-softmax accumulate this page's
    slots into the token's running (m, l, acc)."""
    t = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_scratch[:] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[:] = jnp.zeros_like(l_scratch)
        acc_scratch[:] = jnp.zeros_like(acc_scratch)

    q = q_ref[0]                                  # [H, D] (input dtype)
    k = k_ref[0]                                  # [KVH, bs, D]
    v = v_ref[0]
    if rep != 1:
        k = jnp.repeat(k, rep, axis=0)            # [H, bs, D]
        v = jnp.repeat(v, rep, axis=0)
    d = q.shape[-1]
    s = jax.lax.dot_general(
        q, k, (((1,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * (d ** -0.5)    # [H, bs]
    slot_pos = p * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    visible = (slot_pos <= pos_ref[t]) & (tabs_ref[t, p] >= 0)
    s = jnp.where(visible, s, NEG_INF)
    m_prev = m_scratch[:]                         # [H, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    pr = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scratch[:] = alpha * l_scratch[:] + jnp.sum(pr, axis=1, keepdims=True)
    acc_scratch[:] = acc_scratch[:] * alpha + jax.lax.dot_general(
        pr.astype(v.dtype), v, (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    m_scratch[:] = m_new

    @pl.when(p == mp - 1)
    def _finalize():
        l = l_scratch[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scratch[:] / l_safe).astype(o_ref.dtype)


def ragged_decode_attention(q, k_pool, v_pool, page_tables, slot_ids,
                            positions, valid, rep=1):
    """Drop-in for serving.ragged.ragged_paged_attention (same signature
    and semantics): q [T, H, D] packed queries, pools [P, kvh, bs, D].
    Each token walks its own page list; invalid rows are zeroed."""
    t, h, d = q.shape
    p_total, kvh, bs, _ = k_pool.shape
    mp = page_tables.shape[1]
    tabs = page_tables[slot_ids].astype(jnp.int32)          # [T, MP]
    pos_eff = jnp.where(valid, positions, -1).astype(jnp.int32)

    def kv_idx(t_i, p_i, tabs_ref, pos_ref):
        # unassigned (-1) pages clamp to page 0 for the DMA; the kernel
        # masks their scores via tabs_ref[t, p] < 0
        return (jnp.clip(tabs_ref[t_i, p_i], 0, p_total - 1), 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(t, mp),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda t_i, p_i, tabs_r, pos_r:
                         (t_i, 0, 0)),
            pl.BlockSpec((1, kvh, bs, d), kv_idx),
            pl.BlockSpec((1, kvh, bs, d), kv_idx),
        ],
        out_specs=pl.BlockSpec((1, h, d), lambda t_i, p_i, tabs_r, pos_r:
                               (t_i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_rpa_kernel, bs=bs, mp=mp, rep=rep),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, h, d), q.dtype),
        compiler_params=_tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_INTERPRET,
    )(tabs, pos_eff, q, k_pool, v_pool)
    return jnp.where(valid[:, None, None], out, 0.0).astype(q.dtype)


__all__ = ["ragged_decode_attention", "enabled"]
