"""Ragged paged attention — the mixed-phase serving attention path.

Reference capability: Ragged Paged Attention (PAPERS.md, arxiv 2604.15464)
— ONE kernel serving prefill chunks and decode steps together over ragged
page tables, which is exactly the attention shape a continuous batcher
emits. This module holds the pure-JAX reference implementation (the
numerics oracle, pinned against the dense ``generation._attend`` /
``_attend_gqa`` paths on CPU by tests/test_serve_engine.py) and the
dispatch between it and the Pallas kernel (``kernels/ragged_pallas.py``).
No flag chooses: ``attention_path`` looks at the backend, at the engine's
tensor-parallel annotator and at the pool's geometry. A single TPU chip
runs the kernel, whose reads follow each scheduled sequence's live pages;
the CPU and a mesh (which cannot partition a bare ``pallas_call``) run
the reference, whose gather follows ``token_budget x max_model_len``.

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
                           positions, valid, rep=1, scale=None, latent=None):
    """Pure-JAX reference. q: [T, H, D] packed mixed-phase queries;
    k_pool/v_pool: [P, kvh, bs, D]; page_tables: [S, MP] int32 (-1 =
    unassigned); slot_ids: [T] int32; positions: [T] int32; valid: [T]
    bool (False = padding row, output is zeroed); rep = H // kvh (GQA
    query groups per kv head); scale multiplies the scores (``D ** -0.5``
    unless given). Returns [T, H, D] in q.dtype. With ``latent`` the pool
    is a latent one (``kernels.ragged_pallas``): ``v_pool`` is not read,
    the value is the key row's first ``latent`` columns and the result is
    [T, H, latent]."""
    t, h, d = q.shape
    p_total, kvh, bs, _ = k_pool.shape
    mp = page_tables.shape[1]
    over = np.sqrt(d) if scale is None else 1.0 / scale
    tabs = page_tables[slot_ids]                       # [T, MP]
    safe = jnp.clip(tabs, 0, p_total - 1)
    kg = k_pool[safe]                                  # [T, MP, kvh, bs, D]
    kg = kg.transpose(0, 2, 1, 3, 4).reshape(t, kvh, mp * bs, d)
    if latent is None:
        vg = v_pool[safe].transpose(0, 2, 1, 3, 4).reshape(
            t, kvh, mp * bs, d)
    else:
        vg = kg[..., :latent]
    slot_pos = jnp.arange(mp * bs)[None, :]            # [1, MP*bs]
    live = (slot_pos <= positions[:, None]) & valid[:, None]
    page_ok = jnp.broadcast_to((tabs >= 0)[:, :, None],
                               (t, mp, bs)).reshape(t, mp * bs)
    live = live & page_ok
    if rep == 1:
        scores = jnp.einsum("thd,thmd->thm", q.astype(jnp.float32),
                            kg.astype(jnp.float32)) / over
        scores = jnp.where(live[:, None, :], scores, NEG_INF)
        p = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("thm,thmd->thd", p, vg.astype(jnp.float32))
    else:
        qg = q.reshape(t, kvh, rep, d)
        scores = jnp.einsum("tgrd,tgmd->tgrm", qg.astype(jnp.float32),
                            kg.astype(jnp.float32)) / over
        scores = jnp.where(live[:, None, None, :], scores, NEG_INF)
        p = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("tgrm,tgmd->tgrd", p, vg.astype(jnp.float32))
        out = out.reshape(t, h, -1)
    out = jnp.where(valid[:, None, None], out, 0.0)
    return out.astype(q.dtype)


def attention_path(shard, pool_shape, dtype) -> str:
    """Which implementation serves the step's attention, from what the
    code can observe: ``"paged_kernel"`` on a single TPU chip (``shard``
    is None) whose pool Mosaic can tile, else ``"reference"``.
    ``telemetry()["attention"]`` reports it."""
    from ..kernels import on_tpu, ragged_pallas as _rp
    if shard is None and (_rp._INTERPRET or (
            on_tpu() and _rp.tiles(pool_shape, dtype))):
        return "paged_kernel"
    return "reference"


def write_path(shard, pool_shape, dtype, rows) -> str:
    """Which implementation writes a step's ``rows`` new K/V rows into the
    pools: ``"page_kernel"`` (``kernels/kv_write_pallas.py``) wherever
    ``attention_path`` runs the attention kernel and the kernel's VMEM
    holds the step's rows (``kv_write_pallas.fits``), else ``"xla"``
    (``generation._write_pages``). ``telemetry()["kv_write"]["path"]``
    reports it."""
    from ..kernels import kv_write_pallas as _kw
    if (attention_path(shard, pool_shape, dtype) == "paged_kernel"
            and _kw.fits(rows, pool_shape, dtype)):
        return "page_kernel"
    return "xla"


def make_attend(page_tables, slot_ids, positions, valid, rep, shard=None,
                scale=None, latent=None):
    """Bind the ragged metadata into the ``attend(q, kp, vp)`` callable
    ``generation.step_ragged`` expects. ``attention_path`` selects what
    implements it (``shard`` is the engine's tensor-parallel annotator,
    None on a single chip); the kernel takes the rows of each page-table
    slot as one query block, which is how ``_pack_plan`` packs them.
    Either way it runs under the scope ``paged_attention``: the device
    time of the step's attention is found by that name.

    ``attend(q, kf, vf, first_page)`` reads one cache entry out of pools
    that hold every entry's pages one after another (``[E * P, kvh, bs,
    D]``, entry ``e`` at pages ``e * P ..``): every decoder's step threads
    its stacked pools through the layers in that form
    (``generation._entry_seams`` binds ``first_page`` a layer). The page
    tables are shifted by ``first_page``, so neither path copies the entry
    out. Without ``first_page`` the pools are one entry's, read as they
    are. ``scale`` and ``latent`` are the decoder's (``attn_scale``,
    ``latent_dim``): a latent pool is one pool, ``vp`` is not read and the
    rows come back ``latent`` wide."""
    from ..kernels import ragged_pallas as _rp
    with jax.named_scope("paged_attention"):
        # once a step, not once a layer, and outside any loop the step
        # makes; the reference does not read them and the compiler drops them
        meta = _rp.seq_meta(slot_ids, positions, valid, page_tables.shape[0])

    @jax.named_scope("paged_attention")
    def attend(q, kp, vp, first_page=None):
        tables = page_tables if first_page is None else jnp.where(
            page_tables >= 0, page_tables + first_page, -1)
        if attention_path(shard, kp.shape, kp.dtype) == "reference":
            return ragged_paged_attention(q, kp, vp, tables, slot_ids,
                                          positions, valid, rep,
                                          scale=scale, latent=latent)
        return _rp.paged_attention(q, kp, vp, tables, *meta, rep=rep,
                                   scale=scale, latent=latent)

    return attend


__all__ = ["ragged_paged_attention", "attention_path", "write_path",
           "make_attend"]
