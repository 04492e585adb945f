"""Pallas TPU ragged paged attention: the serving step's attention kernel.

Reference capability: Ragged Paged Attention (PAPERS.md, arxiv
2604.15464) — one kernel serving mixed prefill+decode batches over
ragged page tables. ``serving.ragged.make_attend`` runs it on a single
TPU chip; the pure-JAX ``serving.ragged.ragged_paged_attention`` is the
numerics oracle and the path everywhere else.

The kernel works per scheduled SEQUENCE. The engine packs a sequence's
rows contiguously with consecutive positions (``ServingEngine._pack_plan``),
so a sequence is a block of query rows ``q[start:start+count]``, one
page-table row and a context length (last position + 1). ``seq_meta``
derives the three per-slot numbers from the packed ``slot_ids``,
``positions`` and ``valid``; with the page tables they ride in
scalar-prefetch memory. One grid cell a slot:

  * a slot with no rows this step costs nothing;
  * its rows go through in tiles of ``TQ`` rows; the ``rep`` query heads
    of one KV head fold into the row dimension of one product (``TQ x
    rep`` rows), so K and V are read once a KV head;
  * a tile walks ``ceil((its last position + 1) / block_size)`` pages in
    blocks of ``pages_per_block`` and stops there: pages past the
    context and ``-1`` entries cost no DMA. A page goes HBM -> VMEM
    straight from the pool's ``[P, kvh, bs, D]`` layout (``kvh`` tiles of
    ``(bs, D)``), the next block's copies in flight while this one is
    computed;
  * scores and the weighted sum take the operands in their own dtype
    with float32 accumulation; running max, sum and accumulator are
    float32 (online softmax). A slot position is visible when it is
    ``<=`` the query's position, which is today's causal rule and also
    hides rejected drafts' K/V.

No copy of K or V exists outside the two VMEM blocks. ``tiles`` says
which geometries Mosaic takes (head size a multiple of 128, page size a
multiple of the dtype's sublane tile); tests run the kernel off the chip
through the TPU interpreter (``_INTERPRET``), which takes any.

A LATENT pool (``v_pool`` None, ``latent`` the value's width) is the fold
taken to its end: one KV head whose row is a token's compressed latent and
its roped key side by side, padded to whole lane tiles (512 + 64 -> 640),
every query head folded into the rows of one product, and the value the
row's first ``latent`` columns, so a page is read once and serves both
products. ``_latent_kernel`` is the same walk with one pool; a slot with a
single row (a decode row) goes through as a tile of one row (``heads``
rows of the product), a chunk of rows in tiles of ``TQ``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.jax_compat import tpu_compiler_params as _tpu_compiler_params

NEG_INF = -1e30
TQ = 16                 # query rows a tile: one bfloat16 sublane tile
KV_BLOCK = 256          # K/V slots a block: two lane tiles of scores
VMEM_LIMIT = 64 << 20   # of a v5e core's 128 MiB; the default scope is 16
_INTERPRET = False      # tests flip this to run the kernel off-TPU


def _sublanes(dtype) -> int:
    return 8 * (4 // jnp.dtype(dtype).itemsize)


def tiles(pool_shape, dtype) -> bool:
    """Can Mosaic tile this pool? ``[..., kvh, bs, D]`` pages land in VMEM
    as ``(bs, D)`` tiles, so D fills lanes and bs whole sublane tiles."""
    bs, d = pool_shape[-2:]
    return (jnp.dtype(dtype).itemsize in (2, 4) and d % 128 == 0
            and bs % _sublanes(dtype) == 0)


def seq_meta(slot_ids, positions, valid, num_slots):
    """Per page-table row: (first packed row, row count, context length)
    of the rows ``slot_ids`` sends to it, int32 ``[num_slots]`` each. The
    rows of one slot are contiguous and their positions consecutive, as
    ``_pack_plan`` packs them; a slot with no valid row counts 0."""
    mine = (slot_ids[None, :] == jnp.arange(num_slots)[:, None]) \
        & valid[None, :]
    counts = mine.sum(1).astype(jnp.int32)
    starts = jnp.argmax(mine, axis=1).astype(jnp.int32)
    ctx = jnp.max(jnp.where(mine, positions[None, :] + 1, 0),
                  axis=1).astype(jnp.int32)
    return starts, counts, ctx


def _kernel(tabs_ref, start_ref, count_ref, ctx_ref, q_ref, k_hbm, v_hbm,
            o_ref, kbuf, vbuf, sems, q_scr, m_scr, l_scr, acc_scr, out_scr,
            *, rep, bs, npb, tq, hg, scale):
    s = pl.program_id(0)
    t_total, h, d = q_ref.shape
    kvh = h // rep
    rows = tq * rep
    blk = npb * bs
    mp = tabs_ref.shape[1]

    @pl.when(s == 0)
    def _first():
        # rows no sequence owns read zero; a slot of the K/V blocks that
        # no copy has filled yet holds zeros, never an uninitialised NaN
        out_scr[...] = jnp.zeros_like(out_scr)
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)

    n, start, ctx = count_ref[s], start_ref[s], ctx_ref[s]

    def page_id(col):
        return tabs_ref[s, jnp.minimum(col, mp - 1)]

    def block_copies(b, slot, pages, act):
        """``act`` (start or wait) on the copies of block ``b``'s live
        pages into half ``slot`` of the K/V blocks."""
        def page(j, _):
            pid = page_id(b * npb + j)

            @pl.when(pid >= 0)
            def _():
                dst = (slot, slice(None),
                       pl.ds(pl.multiple_of(j * bs, bs), bs))
                for pool, buf, which in ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1)):
                    act(pltpu.make_async_copy(
                        pool.at[pid], buf.at[dst], sems.at[which, slot]))

        jax.lax.fori_loop(0, jnp.minimum(npb, pages - b * npb), page, None)

    def tile(i, _):
        row0 = start + i * tq
        at = jnp.minimum(row0, t_total - tq)      # the tile stays inside q
        here = jnp.minimum(n - i * tq, tq)
        pos0 = ctx - n + (at - start)             # position of tile row 0
        last = ctx - n + i * tq + here - 1        # of the tile's last row
        pages = jnp.minimum(last // bs + 1, mp)
        nblk = (pages + npb - 1) // npb
        block_copies(0, 0, pages, lambda c: c.start())
        for g in range(kvh):
            qg = q_ref[pl.ds(at, tq), g * rep:(g + 1) * rep, :]
            if rep % (_sublanes(qg.dtype) // 8):
                qg = qg.astype(jnp.float32)       # whole sublanes to fold
            q_scr[g] = qg.reshape(rows, d).astype(q_scr.dtype)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

        def block(b, _):
            slot = jax.lax.rem(b, 2)

            @pl.when(b + 1 < nblk)
            def _():
                block_copies(b + 1, 1 - slot, pages, lambda c: c.start())

            block_copies(b, slot, pages, lambda c: c.wait())
            lane = jax.lax.broadcasted_iota(jnp.int32, (1, blk), 1)
            # a -1 entry inside the context: its slots are nobody's
            paged = jax.lax.fori_loop(
                0, npb, lambda j, ok: jnp.where(
                    (lane // bs == j) & (page_id(b * npb + j) < 0), 0, ok),
                jnp.ones((1, blk), jnp.int32))
            q_pos = pos0 + jax.lax.broadcasted_iota(
                jnp.int32, (rows, blk), 0) // rep
            visible = ((b * blk + lane) <= q_pos) & (paged > 0)

            def heads(c, _):
                g0 = pl.multiple_of(c * hg, hg)
                qc = q_scr[pl.ds(g0, hg)]                  # [hg, rows, d]
                kc = kbuf[slot, pl.ds(g0, hg)]             # [hg, blk, d]
                vc = vbuf[slot, pl.ds(g0, hg)]
                sc = jax.lax.dot_general(
                    qc, kc, (((2,), (2,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32) * scale
                sc = jnp.where(visible[None], sc, NEG_INF)
                m_prev = m_scr[pl.ds(g0, hg)]              # [hg, rows, 1]
                m_new = jnp.maximum(m_prev,
                                    jnp.max(sc, axis=2, keepdims=True))
                p = jnp.exp(sc - m_new)
                alpha = jnp.exp(m_prev - m_new)
                l_scr[pl.ds(g0, hg)] = alpha * l_scr[pl.ds(g0, hg)] \
                    + jnp.sum(p, axis=2, keepdims=True)
                acc_scr[pl.ds(g0, hg)] = acc_scr[pl.ds(g0, hg)] * alpha \
                    + jax.lax.dot_general(
                        p.astype(vc.dtype), vc, (((2,), (1,)), ((0,), (0,))),
                        preferred_element_type=jnp.float32)
                m_scr[pl.ds(g0, hg)] = m_new

            jax.lax.fori_loop(0, kvh // hg, heads, None)

        jax.lax.fori_loop(0, nblk, block, None)
        row = at + jax.lax.broadcasted_iota(jnp.int32, (tq, 1, 1), 0)
        mine = (row >= row0) & (row < start + n)
        for g in range(kvh):
            l = l_scr[g]
            out = (acc_scr[g] / jnp.where(l == 0.0, 1.0, l)) \
                .reshape(tq, rep, d)
            dst = (pl.ds(at, tq), slice(g * rep, (g + 1) * rep))
            out_scr[dst] = jnp.where(mine, out, out_scr[dst])

    @pl.when(n > 0)
    def _():
        jax.lax.fori_loop(0, (n + tq - 1) // tq, tile, None)

    @pl.when(s == pl.num_programs(0) - 1)
    def _last():
        o_ref[...] = out_scr[...].astype(o_ref.dtype)


def paged_attention(q, k_pool, v_pool, page_tables, starts, counts, ctx,
                    rep=1, scale=None, latent=None):
    """q: [T, H, D] packed queries; k_pool/v_pool: [P, kvh, bs, D];
    page_tables: [S, MP] int32 (-1 = unassigned); starts/counts/ctx:
    ``seq_meta``'s. Returns [T, H, D] in q.dtype; rows no slot owns are
    zero. ``scale`` multiplies the scores (``D ** -0.5`` unless given).
    With ``latent`` the pool is a latent one, ``[P, 1, bs, D]``, ``v_pool``
    is not read, every head attends to the one row a token keeps, the value
    is that row's first ``latent`` columns and the result is ``[T, H,
    latent]``."""
    tables = page_tables.astype(jnp.int32)
    scale = float(q.shape[-1] ** -0.5 if scale is None else scale)
    if latent is not None:
        with jax.named_scope("latent_attention"):
            return _latent_call(q, k_pool, tables, starts, counts, ctx,
                                latent=int(latent), scale=scale,
                                interpret=_INTERPRET)
    return _call(q, k_pool, v_pool, tables, starts, counts, ctx, rep=rep,
                 scale=scale, interpret=_INTERPRET)


@functools.partial(jax.jit, static_argnames=("rep", "scale", "interpret"))
def _call(q, k_pool, v_pool, page_tables, starts, counts, ctx, *, rep, scale,
          interpret):
    """A jit of its own: the layers of a step program share one trace and
    one lowering of the kernel, which would otherwise cost every process
    a second a layer, however warm its compile cache."""
    t, h, d = q.shape
    _, kvh, bs, _ = k_pool.shape
    s, mp = page_tables.shape
    tq = min(TQ, t)
    rows = tq * rep
    npb = min(mp, max(1, KV_BLOCK // bs))
    hg = max(g for g in range(1, kvh + 1)
             if kvh % g == 0 and g * rows <= max(rows, 128))
    f32 = jnp.float32
    whole = pl.BlockSpec((t, h, d), lambda i, *_: (0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(s,),
        in_specs=[whole, pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=whole,
        scratch_shapes=[
            pltpu.VMEM((2, kvh, npb * bs, d), k_pool.dtype),
            pltpu.VMEM((2, kvh, npb * bs, d), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((kvh, rows, d), q.dtype),
            pltpu.VMEM((kvh, rows, 1), f32),
            pltpu.VMEM((kvh, rows, 1), f32),
            pltpu.VMEM((kvh, rows, d), f32),
            pltpu.VMEM((t, h, d), f32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, rep=rep, bs=bs, npb=npb, tq=tq, hg=hg,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, h, d), q.dtype),
        compiler_params=_tpu_compiler_params(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="paged_attention",
    )(page_tables, starts, counts, ctx, q, k_pool, v_pool)

LATENT_VMEM_LIMIT = 100 << 20   # q and the result stay whole in VMEM


def _latent_kernel(tabs_ref, start_ref, count_ref, ctx_ref, q_ref, k_hbm,
                   o_ref, kbuf, sems, q_scr, m_scr, l_scr, acc_scr,
                   *, bs, npb, tq, dv, scale):
    """``_kernel`` for a latent pool: one pool, one row a token, all ``h``
    query heads folded into the rows of the two products, the value the
    first ``dv`` columns of the key block already in VMEM."""
    s = pl.program_id(0)
    t_total, h, d = q_ref.shape
    blk = npb * bs
    mp = tabs_ref.shape[1]

    @pl.when(s == 0)
    def _first():
        o_ref[...] = jnp.zeros_like(o_ref)
        kbuf[...] = jnp.zeros_like(kbuf)

    n, start, ctx = count_ref[s], start_ref[s], ctx_ref[s]

    def page_id(col):
        return tabs_ref[s, jnp.minimum(col, mp - 1)]

    def block_copies(b, slot, pages, act):
        def page(j, _):
            pid = page_id(b * npb + j)

            @pl.when(pid >= 0)
            def _():
                act(pltpu.make_async_copy(
                    k_hbm.at[pid, 0],
                    kbuf.at[slot, pl.ds(pl.multiple_of(j * bs, bs), bs)],
                    sems.at[slot]))

        jax.lax.fori_loop(0, jnp.minimum(npb, pages - b * npb), page, None)

    def tile(i, tq):
        """Rows ``i * tq ..`` of the slot, ``tq`` (static) at a time."""
        rows = tq * h
        row0 = start + i * tq
        at = jnp.minimum(row0, t_total - tq)      # the tile stays inside q
        here = jnp.minimum(n - i * tq, tq)
        pos0 = ctx - n + (at - start)             # position of tile row 0
        last = ctx - n + i * tq + here - 1        # of the tile's last row
        pages = jnp.minimum(last // bs + 1, mp)
        nblk = (pages + npb - 1) // npb
        block_copies(0, 0, pages, lambda c: c.start())
        q_scr[pl.ds(0, rows)] = q_ref[pl.ds(at, tq)].reshape(rows, d)
        m_scr[pl.ds(0, rows)] = jnp.full((rows, 1), NEG_INF, jnp.float32)
        l_scr[pl.ds(0, rows)] = jnp.zeros((rows, 1), jnp.float32)
        acc_scr[pl.ds(0, rows)] = jnp.zeros((rows, dv), jnp.float32)

        def block(b, _):
            slot = jax.lax.rem(b, 2)

            @pl.when(b + 1 < nblk)
            def _():
                block_copies(b + 1, 1 - slot, pages, lambda c: c.start())

            block_copies(b, slot, pages, lambda c: c.wait())
            lane = jax.lax.broadcasted_iota(jnp.int32, (1, blk), 1)
            paged = jax.lax.fori_loop(
                0, npb, lambda j, ok: jnp.where(
                    (lane // bs == j) & (page_id(b * npb + j) < 0), 0, ok),
                jnp.ones((1, blk), jnp.int32))
            q_pos = pos0 + jax.lax.broadcasted_iota(
                jnp.int32, (rows, blk), 0) // h
            visible = ((b * blk + lane) <= q_pos) & (paged > 0)
            kc = kbuf[slot]                                   # [blk, d]
            sc = jax.lax.dot_general(
                q_scr[pl.ds(0, rows)], kc, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            sc = jnp.where(visible, sc, NEG_INF)
            m_prev = m_scr[pl.ds(0, rows)]
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
            p = jnp.exp(sc - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[pl.ds(0, rows)] = alpha * l_scr[pl.ds(0, rows)] \
                + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[pl.ds(0, rows)] = acc_scr[pl.ds(0, rows)] * alpha \
                + jax.lax.dot_general(
                    p.astype(kc.dtype), kc[:, :dv], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            m_scr[pl.ds(0, rows)] = m_new

        jax.lax.fori_loop(0, nblk, block, None)
        l = l_scr[pl.ds(0, rows)]
        out = (acc_scr[pl.ds(0, rows)] / jnp.where(l == 0.0, 1.0, l)) \
            .reshape(tq, h, dv).astype(o_ref.dtype)
        row = at + jax.lax.broadcasted_iota(jnp.int32, (tq, 1, 1), 0)
        mine = (row >= row0) & (row < start + n)
        o_ref[pl.ds(at, tq)] = jnp.where(mine, out, o_ref[pl.ds(at, tq)])

    @pl.when(n == 1)
    def _():
        tile(0, 1)

    @pl.when(n > 1)
    def _():
        jax.lax.fori_loop(0, (n + tq - 1) // tq,
                          lambda i, _: tile(i, tq), None)


@functools.partial(jax.jit, static_argnames=("latent", "scale", "interpret"))
def _latent_call(q, k_pool, page_tables, starts, counts, ctx, *, latent,
                 scale, interpret):
    """See ``_call``. q: [T, H, D]; k_pool: [P, 1, bs, D]; the result is
    [T, H, latent]."""
    t, h, d = q.shape
    _, _, bs, _ = k_pool.shape
    s, mp = page_tables.shape
    tq = min(TQ, t)
    rows = tq * h
    npb = min(mp, max(1, KV_BLOCK // bs))
    f32 = jnp.float32

    def whole(width):
        return pl.BlockSpec((t, h, width), lambda i, *_: (0, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(s,),
        in_specs=[whole(d), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=whole(latent),
        scratch_shapes=[
            pltpu.VMEM((2, npb * bs, d), k_pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((rows, d), q.dtype),
            pltpu.VMEM((rows, 1), f32),
            pltpu.VMEM((rows, 1), f32),
            pltpu.VMEM((rows, latent), f32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_latent_kernel, bs=bs, npb=npb, tq=tq, dv=latent,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, h, latent), q.dtype),
        compiler_params=_tpu_compiler_params(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=LATENT_VMEM_LIMIT),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="latent_paged_attention",
    )(page_tables, starts, counts, ctx, q, k_pool)


__all__ = ["paged_attention", "seq_meta", "tiles"]
