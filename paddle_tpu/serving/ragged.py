"""Ragged paged attention — the mixed-phase serving attention path.

Reference capability: Ragged Paged Attention (PAPERS.md, arxiv 2604.15464)
— ONE kernel serving prefill chunks and decode steps together over ragged
page tables, which is exactly the attention shape a continuous batcher
emits. This module holds the pure-JAX reference implementation (the
numerics oracle, pinned against the dense ``generation._attend`` /
``_attend_gqa`` paths on CPU by tests/test_serve_engine.py) plus the
dispatch that routes decode-only steps through the flag-gated Pallas
kernel (``kernels/ragged_pallas.py``) on TPU.

Layout contract (shared with ``incubate...block_multihead_attention`` and
the serving engine):

  * pools: ``[P, kvh, bs, D]`` — P fixed-size pages of ``bs`` token slots;
  * ``page_tables [S, MP]``: page ids per sequence slot, position-ordered
    (table column c covers absolute positions ``c*bs .. c*bs+bs-1``), -1
    for unassigned;
  * queries arrive PACKED: ``q [T, H, D]`` with ``slot_ids [T]`` (row into
    the page table) and ``positions [T]`` (absolute position of each
    query token). Token t sees its slot's cache positions ``<= positions
    [t]`` — the pools already contain this step's K/V (the engine
    scatters before attending), so within-chunk causality falls out of
    the position compare with no separate mask.

Speculative verify chunks (``serving.speculative``) need nothing extra:
k drafted tokens occupy positions ``pos..pos+k-1`` of their sequence
exactly like a prefill chunk, so one forward scores every draft in the
same packed batch — and after a rejection the garbage K/V left past the
accepted frontier stays invisible to every later query, because the
position compare already hides slots beyond a query's position.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


def ragged_paged_attention(q, k_pool, v_pool, page_tables, slot_ids,
                           positions, valid, rep=1):
    """Pure-JAX reference. q: [T, H, D] packed mixed-phase queries;
    k_pool/v_pool: [P, kvh, bs, D]; page_tables: [S, MP] int32 (-1 =
    unassigned); slot_ids: [T] int32; positions: [T] int32; valid: [T]
    bool (False = padding row, output is zeroed); rep = H // kvh (GQA
    query groups per kv head). Returns [T, H, D] in q.dtype."""
    t, h, d = q.shape
    p_total, kvh, bs, _ = k_pool.shape
    mp = page_tables.shape[1]
    tabs = page_tables[slot_ids]                       # [T, MP]
    safe = jnp.clip(tabs, 0, p_total - 1)
    kg = k_pool[safe]                                  # [T, MP, kvh, bs, D]
    vg = v_pool[safe]
    kg = kg.transpose(0, 2, 1, 3, 4).reshape(t, kvh, mp * bs, d)
    vg = vg.transpose(0, 2, 1, 3, 4).reshape(t, kvh, mp * bs, d)
    slot_pos = jnp.arange(mp * bs)[None, :]            # [1, MP*bs]
    live = (slot_pos <= positions[:, None]) & valid[:, None]
    page_ok = jnp.broadcast_to((tabs >= 0)[:, :, None],
                               (t, mp, bs)).reshape(t, mp * bs)
    live = live & page_ok
    if rep == 1:
        scores = jnp.einsum("thd,thmd->thm", q.astype(jnp.float32),
                            kg.astype(jnp.float32)) / np.sqrt(d)
        scores = jnp.where(live[:, None, :], scores, NEG_INF)
        p = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("thm,thmd->thd", p, vg.astype(jnp.float32))
    else:
        qg = q.reshape(t, kvh, rep, d)
        scores = jnp.einsum("tgrd,tgmd->tgrm", qg.astype(jnp.float32),
                            kg.astype(jnp.float32)) / np.sqrt(d)
        scores = jnp.where(live[:, None, None, :], scores, NEG_INF)
        p = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("tgrm,tgmd->tgrd", p, vg.astype(jnp.float32))
        out = out.reshape(t, h, d)
    out = jnp.where(valid[:, None, None], out, 0.0)
    return out.astype(q.dtype)


def make_attend(page_tables, slot_ids, positions, valid, rep):
    """Bind the ragged metadata into the ``attend(q, kp, vp)`` callable
    ``generation.step_ragged`` expects, routing through the Pallas kernel
    when it is flag-enabled (the kernel walks one query token per grid
    cell, so prefill chunks are served but not blocked; ROADMAP S4).
    Whatever implements it runs under the scope ``paged_attention``: the
    device time of the gather over the page tables and of the attention
    itself is found by that name."""
    from ..kernels import ragged_pallas as _rp

    @jax.named_scope("paged_attention")
    def attend(q, kp, vp):
        if _rp.enabled():
            return _rp.ragged_decode_attention(
                q, kp, vp, page_tables, slot_ids, positions, valid, rep)
        return ragged_paged_attention(q, kp, vp, page_tables, slot_ids,
                                      positions, valid, rep)

    return attend


__all__ = ["ragged_paged_attention", "make_attend"]
