"""Pallas TPU page write: the serving step's new K/V rows put into the pools
in place, one call a layer visit for K and V together.

``generation._kv_write_pages`` is the one write of every decoder. Off the
chip, under a mesh and where Mosaic cannot tile the pool it runs in XLA
(``generation._write_pages``: a page gather, a fill and a page scatter a
pool), which is also the oracle; on one TPU chip it runs here, chosen by
the rule that picks the attention kernel (``serving.ragged.write_path``).

The step's plan is made once a step (``plan``): the distinct pages its rows
land in, each ONCE whatever the number of rows in it, in order of their
first row, and for each page the rows it takes and their slots (the first
row to land in a slot wins, as in the XLA write). It rides in scalar memory.
One call:

  * copies the step's rows ``[T, kvh, D]`` of each pool into VMEM (so its
    VMEM grows with T: ``vmem_bytes``, and ``serving.ragged.write_path``
    keeps XLA's write where that passes ``VMEM_MOST``);
  * walks the pages: a page comes HBM -> VMEM whole (``[kvh, bs, D]``, one
    DMA), its rows are selected into their slots (a select, so the bits
    are the rows' and NaN stays where it was), and it goes back to where
    it came from; the next page's copy is in flight while a page is
    merged, and a page's write-back while the next is waited for;
  * takes the pools in HBM (``memory_space=ANY``) and hands them back
    aliased (``input_output_aliases``): nothing else of a pool is read,
    written or copied, and rows that write nothing cost nothing.

A latent pool is one pool with no V: the same call with one pool. Tests run
the kernel off the chip through the TPU interpreter, switched on by
``ragged_pallas._INTERPRET`` like the attention kernel it runs beside.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.jax_compat import tpu_compiler_params as _tpu_compiler_params
from . import ragged_pallas as _rp

AHEAD = 4               # page reads in flight ahead of the page merged
LAG = 2                 # page write-backs left in flight behind it
RING = AHEAD + LAG + 1  # page buffers a pool
VMEM_LIMIT = 32 << 20   # the least a call asks for: the serving cells' rows
#                         and ring take 6.5 MiB at most
VMEM_MOST = 100 << 20   # the most, of a v5e core's 128 MiB (as the latent
#                         attention kernel beside it takes)
VMEM_SLACK = 4 << 20    # Mosaic's own scratch beside ours: under 1.3 MiB at
#                         2,048 rows of 32 heads


class WritePlan(NamedTuple):
    """One step's page write, by distinct page (``plan``), and the first
    page of the cache entry a layer visit writes (``base`` [1] int32):
    ``count`` [1] the pages, ``pages`` [T] each page within the entry
    (the first ``count`` are real), ``starts`` [T + 1] where each page's
    hits begin in ``rows`` / ``slots`` [T] (the row a hit takes, the slot
    it goes to)."""
    count: jax.Array
    pages: jax.Array
    starts: jax.Array
    rows: jax.Array
    slots: jax.Array
    base: jax.Array


def vmem_bytes(rows, pool_shape, dtype, n_pools=2):
    """VMEM a call takes, at most: each pool's ``rows`` new rows [T, kvh, D]
    and its ring of pages [kvh, bs, D], the last two dims of each laid in
    tiles of 8 32-bit sublanes by 128 lanes (Mosaic pads no more than
    that), and its own scratch. ``pool_shape`` ends in [kvh, bs, D]."""
    kvh, bs, d = pool_shape[-3:]
    item = jnp.dtype(dtype).itemsize
    sub = 32 // item                    # a tile's rows: 8 of f32, 16 of bf16

    def tiled(n):                       # bytes of [n, D] in whole tiles
        return -(-n // sub) * sub * (-(-d // 128) * 128) * item

    return VMEM_SLACK + n_pools * (rows * tiled(kvh) + RING * kvh * tiled(bs))


def fits(rows, pool_shape, dtype) -> bool:
    """Does a call for ``rows`` new rows a step, K and V, fit the VMEM a
    call may take (``VMEM_MOST``)? Where not, the write stays XLA's."""
    return vmem_bytes(rows, pool_shape, dtype) <= VMEM_MOST


def first_rows(pages, dropped):
    """[T] bool: the rows that are the first (in row order) to land in
    their page, ``pages >= dropped`` a row that writes nothing. Both writes
    take the distinct pages in this order (``plan``, and
    ``generation._page_plan``'s windows)."""
    earlier = jnp.tril(pages[:, None] == pages[None, :], -1).any(1)
    return (pages < dropped) & ~earlier


def plan(pages, offs, dropped) -> WritePlan:
    """pages, offs: [T] int32, each row's page within an entry and slot in
    it, ``pages >= dropped`` a row that writes nothing. The distinct pages
    the rows land in, in order of their first row; in each the slots some
    row lands in, the first such row's. ``base`` is 0 (``at_entry``)."""
    t = pages.shape[0]
    i32 = jnp.int32
    opens = first_rows(pages, dropped)
    same_page = pages[:, None] == pages[None, :]               # [T, T]
    # the first row to land in a slot wins it
    keep = (pages < dropped) & ~jnp.tril(
        same_page & (offs[:, None] == offs[None, :]), -1).any(1)
    window = jnp.cumsum(opens.astype(i32)) - 1
    # each kept row's page's window (its first row's), T for the others
    win = jnp.where(keep, window[jnp.argmax(same_page, axis=1)], t)
    order = jnp.argsort(win, stable=True).astype(i32)
    starts = (win[None, :] < jnp.arange(t + 1, dtype=i32)[:, None]).sum(1)
    return WritePlan(
        opens.sum(dtype=i32).reshape(1),
        pages[jnp.argsort(~opens, stable=True)].astype(i32),
        starts.astype(i32), order, offs[order].astype(i32),
        jnp.zeros((1,), i32))


def at_entry(write_plan: WritePlan, first_page) -> WritePlan:
    """The plan for the cache entry whose pages start at ``first_page``
    (an int or a traced scalar) of the joined pools."""
    return write_plan._replace(
        base=jnp.reshape(jnp.asarray(first_page, jnp.int32), (1,)))


def write_pages(pools, news, write_plan: WritePlan):
    """pools: one or two (K, V) pools [N, kvh, bs, D]; news: the step's rows
    [T, kvh, D] of each, in the pool's dtype; write_plan: ``plan``'s, at
    its entry. Returns the pools with each planned page's slots holding
    their rows, every other element as it was (the same buffers, aliased)."""
    return _call(write_plan, tuple(news), tuple(pools),
                 interpret=_rp._INTERPRET)


def _kernel(count_ref, page_ref, start_ref, row_ref, slot_ref, base_ref,
            *refs, n_pools):
    news = refs[:n_pools]
    pools = refs[n_pools:2 * n_pools]
    outs = refs[2 * n_pools:3 * n_pools]
    rows = refs[3 * n_pools:4 * n_pools]
    bufs, row_sems, in_sems, out_sems = refs[4 * n_pools:]
    count, base = count_ref[0], base_ref[0]
    _, kvh, bs, d = bufs.shape[1:]

    def take(w, s):
        return [pltpu.make_async_copy(pools[p].at[base + page_ref[w]],
                                      bufs.at[s, p], in_sems.at[p, s])
                for p in range(n_pools)]

    def give(w, s):
        return [pltpu.make_async_copy(bufs.at[s, p],
                                      outs[p].at[base + page_ref[w]],
                                      out_sems.at[p, s])
                for p in range(n_pools)]

    def each(copies, how):
        for c in copies:
            getattr(c, how)()

    got = [pltpu.make_async_copy(news[p], rows[p], row_sems.at[p])
           for p in range(n_pools)]
    each(got, "start")
    for w in range(AHEAD):
        @pl.when(w < count)
        def _(w=w):
            each(take(w, w), "start")

    each(got, "wait")
    slot_of = jax.lax.broadcasted_iota(jnp.int32, (kvh, bs, d), 1)

    def page(w, _):
        s = w % RING

        @pl.when(w > LAG)
        def _():               # the buffer the next read takes is free
            gone = w - LAG - 1
            each(give(gone, gone % RING), "wait")

        @pl.when(w + AHEAD < count)
        def _():
            each(take(w + AHEAD, (w + AHEAD) % RING), "start")

        each(take(w, s), "wait")

        def hit(k, merged):
            row, slot = row_ref[k], slot_ref[k]
            return tuple(
                jnp.where(slot_of == slot, rows[p][row][:, None, :], m)
                for p, m in enumerate(merged))

        merged = jax.lax.fori_loop(
            start_ref[w], start_ref[w + 1], hit,
            tuple(bufs[s, p] for p in range(n_pools)))
        for p in range(n_pools):
            bufs[s, p] = merged[p]
        each(give(w, s), "start")

    jax.lax.fori_loop(0, count, page, None)
    for back in range(LAG + 1):          # the write-backs not waited for
        @pl.when(count > back)
        def _(back=back):
            gone = count - 1 - back
            each(give(gone, gone % RING), "wait")


@functools.partial(jax.jit, static_argnames=("interpret",))
def _call(write_plan, news, pools, *, interpret):
    """A jit of its own: the layer visits of a step program share one trace
    and one lowering of the kernel."""
    n_pools = len(pools)
    t, kvh, d = news[0].shape
    _, _, bs, _ = pools[0].shape
    dtype = pools[0].dtype
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    meta = (write_plan.count, write_plan.pages, write_plan.starts,
            write_plan.rows, write_plan.slots, write_plan.base)
    return pl.pallas_call(
        functools.partial(_kernel, n_pools=n_pools),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(meta),
            grid=(1,),
            in_specs=[anywhere] * (2 * n_pools),
            out_specs=[anywhere] * n_pools,
            # a pool's rows in a buffer of their own: Mosaic pads a latent
            # row's one head to a sublane pair and cannot slice it back
            scratch_shapes=[
                *(pltpu.VMEM((t, kvh, d), dtype) for _ in pools),
                pltpu.VMEM((RING, n_pools, kvh, bs, d), dtype),
                pltpu.SemaphoreType.DMA((n_pools,)),
                pltpu.SemaphoreType.DMA((n_pools, RING)),
                pltpu.SemaphoreType.DMA((n_pools, RING))]),
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools],
        input_output_aliases={len(meta) + n_pools + p: p
                              for p in range(n_pools)},
        compiler_params=_tpu_compiler_params(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=max(VMEM_LIMIT, vmem_bytes(
                t, pools[0].shape, dtype, n_pools))),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="kv_write",
    )(*meta, *news, *pools)


__all__ = ["WritePlan", "vmem_bytes", "fits", "first_rows", "plan",
           "at_entry", "write_pages"]
