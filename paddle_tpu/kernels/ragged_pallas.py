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
derives the three per-slot numbers and the chain of live slots from the
packed ``slot_ids``, ``positions`` and ``valid``; with the page tables they
ride in scalar-prefetch memory. One grid cell a slot:

  * a slot with no rows this step costs nothing;
  * its rows go through in tiles of ``TQ`` rows; the ``rep`` query heads
    of one KV head fold into the row dimension of one product (``TQ x
    rep`` rows), so K and V are read once a KV head;
  * a tile walks ``ceil((its last position + 1) / block_size)`` pages in
    blocks of ``pages_per_block`` and stops there: pages past the
    context and ``-1`` entries cost no DMA. A page goes HBM -> VMEM
    straight from the pool's ``[P, kvh, bs, D]`` layout (``kvh`` tiles of
    ``(bs, D)``). The copies are one chain over the whole call
    (``_walk``): while a block is computed the next ``RING - 1`` blocks'
    are in flight into the other buffers of a ring, be they this tile's,
    the slot's next tile's or the next live slot's first, so only a
    call's first tile waits for copies it started itself;
  * scores and the weighted sum take the operands in their own dtype
    with float32 accumulation; running max, sum and accumulator are
    float32 (online softmax). A slot position is visible when it is
    ``<=`` the query's position, which is today's causal rule and also
    hides rejected drafts' K/V.

No copy of K or V exists outside the ring of VMEM blocks. ``tiles`` says
which geometries Mosaic takes (head size a multiple of 128, page size a
multiple of the dtype's sublane tile); tests run the kernel off the chip
through the TPU interpreter (``_INTERPRET``), which takes any.

A LATENT pool (``v_pool`` None, ``latent`` the value's width) is the fold
taken to its end: one KV head whose row is a token's compressed latent and
its roped key side by side, padded to whole lane tiles (512 + 64 -> 640),
every query head folded into the rows of one product, and the value the
row's first ``latent`` columns, so a page is read once and serves both
products. ``_latent_kernel`` makes the same walk with one pool; a slot with
a single row (a decode row) goes through as a tile of one row (``heads``
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
RING = 2                # K/V blocks in VMEM: one computed, the next on its way
PAGES_A_TURN = 8        # page copies started a turn of the fetch loop
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
    of the rows ``slot_ids`` sends to it, int32 ``[num_slots]`` each, and
    the chain of live rows, int32 ``[num_slots + 1]``: entry ``k`` is the
    first row ``>= k`` that has rows this step (``num_slots`` if none), so
    entry 0 is the step's first live row and entry ``s + 1`` the one after
    ``s``. The rows of one slot are contiguous and their positions
    consecutive, as ``_pack_plan`` packs them; a slot with no valid row
    counts 0."""
    slot = jnp.arange(num_slots, dtype=jnp.int32)
    mine = (slot_ids[None, :] == slot[:, None]) & valid[None, :]
    counts = mine.sum(1).astype(jnp.int32)
    starts = jnp.argmax(mine, axis=1).astype(jnp.int32)
    ctx = jnp.max(jnp.where(mine, positions[None, :] + 1, 0),
                  axis=1).astype(jnp.int32)
    live_from = jax.lax.cummin(jnp.where(counts > 0, slot, num_slots),
                               reverse=True)
    return starts, counts, ctx, jnp.append(live_from, jnp.int32(num_slots))


def _walk(meta, state, *, ring, t_total, tq, fold, bs, npb, copies, begin,
          products, finish, one_row=False):
    """The walk both kernels make, grid cell ``s`` of it: the slot's rows
    in tiles, a tile's live pages in blocks of ``npb``, and ONE chain of
    page copies over all of a call's blocks. The blocks land in a ring of
    ``ring`` buffers, and a cursor kept in ``state`` (SMEM, which outlives
    a grid cell) runs ``ring - 1`` blocks ahead of the products: while a
    block is computed the next ones' copies are in flight, be they this
    tile's, the first of the slot's next tile or of the next slot that
    has rows (``meta``'s chain; empty slots cost it nothing). Only the
    call's first tile starts copies it then waits for.

    What differs between the kernels comes in as functions:
    ``copies(buf, j, k, pid)`` the descriptors that bring page ``pid``
    (or, without it, stand for ``k`` pages' worth of bytes) to pages ``j
    ..`` of a buffer of the ring; ``begin(at, width)`` loads the tile's
    queries and resets its running softmax; ``products(buf, visible,
    width)`` one block's update of it; ``finish(at, width, mine)`` writes
    the rows out. ``fold`` query heads share a row of the cache; with
    ``one_row`` a slot with a single row goes as a tile of one."""
    tabs_ref, start_ref, count_ref, ctx_ref, next_ref = meta
    s = pl.program_id(0)
    slots, mp = tabs_ref.shape
    blk = npb * bs
    n, start, ctx = count_ref[s], start_ref[s], ctx_ref[s]
    # state: the buffer the coming block is in, the buffer the next fetch
    # fills, the cursor (slot, tile, block), the pages started a buffer
    head, tail, f_slot, f_tile, f_block, live_of = range(6)
    turn = min(PAGES_A_TURN, npb)

    def pages_of(s, i):
        """Pages tile ``i`` of slot ``s`` walks: up to its last row's."""
        n = count_ref[s]
        last = ctx_ref[s] - n + jnp.minimum((i + 1) * tq, n) - 1
        return jnp.minimum(last // bs + 1, mp)

    def after(buf):
        return jnp.where(buf + 1 == ring, 0, buf + 1)

    def fetch(*_):
        """Start the copies of the cursor's block, if the call has one
        left, and move the cursor on."""
        fs = state[f_slot]

        @pl.when(fs < slots)
        def _():
            fi, fb, buf = state[f_tile], state[f_block], state[tail]
            pages = pages_of(fs, fi)
            count = jnp.minimum(npb, pages - fb * npb)

            def some(live, first):
                """``PAGES_A_TURN`` pages from ``first`` (static) on."""
                for j in range(first, min(first + turn, npb)):
                    pid = tabs_ref[fs, jnp.minimum(fb * npb + j, mp - 1)]
                    go = (j < count) & (pid >= 0)

                    @pl.when(go)
                    def _():
                        for c in copies(buf, j, 1, pid):
                            c.start()

                    live += go.astype(jnp.int32)
                return live

            live = jnp.int32(0)
            for first in range(0, npb, turn):
                live = jax.lax.cond(
                    first < count, functools.partial(some, first=first),
                    lambda live: live, live)
            state[live_of + buf] = live
            state[tail] = after(buf)
            same_tile = (fb + 1) * npb < pages
            same_slot = same_tile | ((fi + 1) * tq < count_ref[fs])
            state[f_slot] = jnp.where(same_slot, fs, next_ref[fs + 1])
            state[f_tile] = jnp.where(same_tile, fi,
                                      jnp.where(same_slot, fi + 1, 0))
            state[f_block] = jnp.where(same_tile, fb + 1, 0)

    def tile(i, width):
        """Rows ``i * width ..`` of the slot, ``width`` (static) of them."""
        rows = width * fold
        row0 = start + i * width
        at = jnp.minimum(row0, t_total - width)   # the tile stays inside q
        pos0 = ctx - n + (at - start)             # position of tile row 0
        pages = pages_of(s, i)

        @pl.when((i == 0) & (s == next_ref[0]))
        def _():
            state[head] = state[tail] = state[f_tile] = state[f_block] = 0
            state[f_slot] = s
            jax.lax.fori_loop(0, ring - 1, fetch, None)

        begin(at, width)

        def block(b, _):
            buf = state[head]
            fetch()
            # semaphores count bytes: the started pages are waited for in
            # power-of-two runs, not one by one
            live = state[live_of + buf]
            for bit in range(npb.bit_length()):
                @pl.when((live >> bit) & 1 == 1)
                def _():
                    for c in copies(buf, 0, 1 << bit, None):
                        c.wait()

            lane = jax.lax.broadcasted_iota(jnp.int32, (1, blk), 1)
            # a -1 entry inside the context: its slots are nobody's. Fewer
            # pages started than the block has says there is one
            holed = live < jnp.minimum(npb, pages - b * npb)
            paged = jax.lax.fori_loop(
                0, jnp.where(holed, npb, 0), lambda j, ok: jnp.where(
                    (lane // bs == j) & (tabs_ref[s, jnp.minimum(
                        b * npb + j, mp - 1)] < 0), 0, ok),
                jnp.ones((1, blk), jnp.int32))
            q_pos = pos0 + jax.lax.broadcasted_iota(
                jnp.int32, (rows, blk), 0) // fold
            products(buf, ((b * blk + lane) <= q_pos) & (paged > 0), width)
            state[head] = after(buf)

        jax.lax.fori_loop(0, (pages + npb - 1) // npb, block, None)
        row = at + jax.lax.broadcasted_iota(jnp.int32, (width, 1, 1), 0)
        finish(at, width, (row >= row0) & (row < start + n))

    if one_row:
        @pl.when(n == 1)
        def _():
            tile(0, 1)

    @pl.when(n > (1 if one_row else 0))
    def _():
        jax.lax.fori_loop(0, (n + tq - 1) // tq,
                          lambda i, _: tile(i, tq), None)


def _kernel(tabs_ref, start_ref, count_ref, ctx_ref, next_ref, q_ref, k_hbm,
            v_hbm, o_ref, kbuf, vbuf, sems, state, q_scr, m_scr, l_scr,
            acc_scr, out_scr, *, rep, bs, npb, tq, hg, scale):
    t_total, h, d = q_ref.shape
    kvh = h // rep

    @pl.when(pl.program_id(0) == 0)
    def _first():
        # rows no sequence owns read zero; a slot of the K/V blocks that
        # no copy has filled yet holds zeros, never an uninitialised NaN
        out_scr[...] = jnp.zeros_like(out_scr)
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)

    def copies(buf, j, k, pid):
        at = (buf, slice(None), pl.ds(pl.multiple_of(j * bs, bs), k * bs))
        return [pltpu.make_async_copy(
            ring.at[at] if pid is None else pool.at[pid], ring.at[at],
            sems.at[which, buf])
            for pool, ring, which in ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1))]

    def begin(at, tq):
        for g in range(kvh):
            qg = q_ref[pl.ds(at, tq), g * rep:(g + 1) * rep, :]
            if rep % (_sublanes(qg.dtype) // 8):
                qg = qg.astype(jnp.float32)       # whole sublanes to fold
            q_scr[g] = qg.reshape(tq * rep, d).astype(q_scr.dtype)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def products(buf, visible, tq):
        def heads(c, _):
            g0 = pl.multiple_of(c * hg, hg)
            qc = q_scr[pl.ds(g0, hg)]                  # [hg, rows, d]
            kc = kbuf[buf, pl.ds(g0, hg)]             # [hg, blk, d]
            vc = vbuf[buf, pl.ds(g0, hg)]
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

    def finish(at, tq, mine):
        for g in range(kvh):
            l = l_scr[g]
            out = (acc_scr[g] / jnp.where(l == 0.0, 1.0, l)) \
                .reshape(tq, rep, d)
            dst = (pl.ds(at, tq), slice(g * rep, (g + 1) * rep))
            out_scr[dst] = jnp.where(mine, out, out_scr[dst])

    _walk((tabs_ref, start_ref, count_ref, ctx_ref, next_ref), state,
          ring=kbuf.shape[0], t_total=t_total, tq=tq, fold=rep, bs=bs,
          npb=npb, copies=copies, begin=begin, products=products,
          finish=finish)

    @pl.when(pl.program_id(0) == pl.num_programs(0) - 1)
    def _last():
        o_ref[...] = out_scr[...].astype(o_ref.dtype)


def paged_attention(q, k_pool, v_pool, page_tables, starts, counts, ctx,
                    live_from, rep=1, scale=None, latent=None):
    """q: [T, H, D] packed queries; k_pool/v_pool: [P, kvh, bs, D];
    page_tables: [S, MP] int32 (-1 = unassigned); starts/counts/ctx/
    live_from: ``seq_meta``'s. Returns [T, H, D] in q.dtype; rows no slot
    owns are zero. ``scale`` multiplies the scores (``D ** -0.5`` unless
    given). With ``latent`` the pool is a latent one, ``[P, 1, bs, D]``,
    ``v_pool`` is not read, every head attends to the one row a token
    keeps, the value is that row's first ``latent`` columns and the result
    is ``[T, H, latent]``."""
    tables = page_tables.astype(jnp.int32)
    scale = float(q.shape[-1] ** -0.5 if scale is None else scale)
    meta = (tables, starts, counts, ctx, live_from)
    if latent is not None:
        with jax.named_scope("latent_attention"):
            return _latent_call(q, k_pool, meta, latent=int(latent),
                                scale=scale, interpret=_INTERPRET)
    return _call(q, k_pool, v_pool, meta, rep=rep, scale=scale,
                 interpret=_INTERPRET)


def _pallas(kernel, name, meta, operands, out_shape, scratch, vmem_limit,
            interpret):
    """One grid cell a page-table slot, ``meta`` in scalar memory, q and
    the result whole in VMEM, the pools left in HBM."""
    def whole(a):
        return pl.BlockSpec(a.shape, lambda i, *_: (0,) * len(a.shape))

    q, *pools = operands
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(meta),
            grid=(meta[0].shape[0],),
            in_specs=[whole(q)] + [pl.BlockSpec(memory_space=pl.ANY)
                                   for _ in pools],
            out_specs=whole(out_shape),
            scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=_tpu_compiler_params(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_limit),
        interpret=pltpu.InterpretParams() if interpret else False,
        name=name,
    )(*meta, *operands)


@functools.partial(jax.jit, static_argnames=("rep", "scale", "interpret"))
def _call(q, k_pool, v_pool, meta, *, rep, scale, interpret):
    """A jit of its own: the layers of a step program share one trace and
    one lowering of the kernel, which would otherwise cost every process
    a second a layer, however warm its compile cache."""
    t, h, d = q.shape
    _, kvh, bs, _ = k_pool.shape
    mp = meta[0].shape[1]
    tq = min(TQ, t)
    rows = tq * rep
    npb = min(mp, max(1, KV_BLOCK // bs))
    hg = max(g for g in range(1, kvh + 1)
             if kvh % g == 0 and g * rows <= max(rows, 128))
    f32 = jnp.float32
    return _pallas(
        functools.partial(_kernel, rep=rep, bs=bs, npb=npb, tq=tq, hg=hg,
                          scale=scale),
        "paged_attention", meta, (q, k_pool, v_pool),
        jax.ShapeDtypeStruct((t, h, d), q.dtype),
        [pltpu.VMEM((RING, kvh, npb * bs, d), k_pool.dtype),
         pltpu.VMEM((RING, kvh, npb * bs, d), v_pool.dtype),
         pltpu.SemaphoreType.DMA((2, RING)),
         pltpu.SMEM((5 + RING,), jnp.int32),
         pltpu.VMEM((kvh, rows, d), q.dtype),
         pltpu.VMEM((kvh, rows, 1), f32),
         pltpu.VMEM((kvh, rows, 1), f32),
         pltpu.VMEM((kvh, rows, d), f32),
         pltpu.VMEM((t, h, d), f32)],
        VMEM_LIMIT, interpret)


LATENT_VMEM_LIMIT = 100 << 20   # q and the result stay whole in VMEM


def _latent_kernel(tabs_ref, start_ref, count_ref, ctx_ref, next_ref, q_ref,
                   k_hbm, o_ref, kbuf, sems, state, q_scr, m_scr, l_scr,
                   acc_scr, *, bs, npb, tq, dv, scale):
    """``_kernel`` for a latent pool: one pool, one row a token, all ``h``
    query heads folded into the rows of the two products, the value the
    first ``dv`` columns of the key block already in VMEM."""
    t_total, h, d = q_ref.shape

    @pl.when(pl.program_id(0) == 0)
    def _first():
        o_ref[...] = jnp.zeros_like(o_ref)
        kbuf[...] = jnp.zeros_like(kbuf)

    def copies(buf, j, k, pid):
        dst = kbuf.at[buf, pl.ds(pl.multiple_of(j * bs, bs), k * bs)]
        return [pltpu.make_async_copy(
            dst if pid is None else k_hbm.at[pid, 0], dst, sems.at[buf])]

    def begin(at, tq):
        rows = tq * h
        q_scr[pl.ds(0, rows)] = q_ref[pl.ds(at, tq)].reshape(rows, d)
        m_scr[pl.ds(0, rows)] = jnp.full((rows, 1), NEG_INF, jnp.float32)
        l_scr[pl.ds(0, rows)] = jnp.zeros((rows, 1), jnp.float32)
        acc_scr[pl.ds(0, rows)] = jnp.zeros((rows, dv), jnp.float32)

    def products(buf, visible, tq):
        mine = pl.ds(0, tq * h)
        kc = kbuf[buf]                                    # [blk, d]
        sc = jax.lax.dot_general(
            q_scr[mine], kc, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        sc = jnp.where(visible, sc, NEG_INF)
        m_prev = m_scr[mine]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        p = jnp.exp(sc - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[mine] = alpha * l_scr[mine] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[mine] = acc_scr[mine] * alpha + jax.lax.dot_general(
            p.astype(kc.dtype), kc[:, :dv], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[mine] = m_new

    def finish(at, tq, mine):
        rows = pl.ds(0, tq * h)
        l = l_scr[rows]
        out = (acc_scr[rows] / jnp.where(l == 0.0, 1.0, l)) \
            .reshape(tq, h, dv).astype(o_ref.dtype)
        o_ref[pl.ds(at, tq)] = jnp.where(mine, out, o_ref[pl.ds(at, tq)])

    _walk((tabs_ref, start_ref, count_ref, ctx_ref, next_ref), state,
          ring=kbuf.shape[0], t_total=t_total, tq=tq, fold=h, bs=bs, npb=npb,
          copies=copies, begin=begin, products=products, finish=finish,
          one_row=True)


@functools.partial(jax.jit, static_argnames=("latent", "scale", "interpret"))
def _latent_call(q, k_pool, meta, *, latent, scale, interpret):
    """See ``_call``. q: [T, H, D]; k_pool: [P, 1, bs, D]; the result is
    [T, H, latent]."""
    t, h, d = q.shape
    _, _, bs, _ = k_pool.shape
    mp = meta[0].shape[1]
    tq = min(TQ, t)
    rows = tq * h
    npb = min(mp, max(1, KV_BLOCK // bs))
    f32 = jnp.float32
    return _pallas(
        functools.partial(_latent_kernel, bs=bs, npb=npb, tq=tq, dv=latent,
                          scale=scale),
        "latent_paged_attention", meta, (q, k_pool),
        jax.ShapeDtypeStruct((t, h, latent), q.dtype),
        [pltpu.VMEM((RING, npb * bs, d), k_pool.dtype),
         pltpu.SemaphoreType.DMA((RING,)),
         pltpu.SMEM((5 + RING,), jnp.int32),
         pltpu.VMEM((rows, d), q.dtype),
         pltpu.VMEM((rows, 1), f32),
         pltpu.VMEM((rows, 1), f32),
         pltpu.VMEM((rows, latent), f32)],
        LATENT_VMEM_LIMIT, interpret)


__all__ = ["paged_attention", "seq_meta", "tiles"]
