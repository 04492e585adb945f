"""Pallas TPU grouped matmul (megablocks-style dropless MoE FFN).

Reference parity: the MoE expert-FFN compute path
(incubate/nn/functional/fused_moe.py capability, expert kernels under
phi/kernels — number_count/assign_pos route tokens, then per-expert
GEMMs). The reference's capacity-based dispatch drops tokens when an
expert overflows; this kernel implements the DROPLESS formulation
(MegaBlocks, arXiv:2211.15841): tokens sort by expert id and a grouped
matmul runs each contiguous group against its expert's weights — no
capacity, no dropped tokens, no [t, e, c] one-hot dispatch arrays.

TPU-native design: one `pallas_call` whose grid walks (n-block,
work-item); a work item is a (row-tile, expert) pair precomputed on the
host side of the trace (make_group_metadata, all jnp — runs under jit).
Scalar prefetch feeds the per-item tile/expert/row-range tables to the
BlockSpec index maps, so each kernel instance loads the right x row-tile
and the right expert's weight block; a row mask handles group boundaries
inside a tile. Work items for the same row tile are consecutive in the
grid (groups are contiguous in sorted rows), so the output window
persists across the boundary revisit — the second group's rows overwrite
only its masked slice. The backward runs on the same machinery: dx is a
grouped matmul against w^T, dw is the transposed grouped matmul (tgmm)
accumulating row-tiles per expert.

The jnp oracle (`_gmm_reference`) is the numerics contract; interpret
mode validates on CPU, the same kernel lowers via Mosaic on TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import default_platform, fused_pallas as _fp


def _interpret() -> bool:
    """The dropless MoE path has no jnp twin, so off-TPU it runs the same
    kernels through the Pallas interpreter — chosen because the platform
    IS cpu (or the tests' switch), never because a device probe failed."""
    return _fp._INTERPRET or default_platform() == "cpu"


def make_group_metadata(group_sizes, t: int, bt: int):
    """Work-item tables for a [t]-row, bt-tiled grouped matmul.

    Static item count W = t//bt + E (each group adds at most one partial
    tile beyond its full tiles). Returns int32 arrays of length W:
    (tile_ids, group_ids, first_flags, row_start_in_tile, row_end_in_tile).
    Invalid (unused) items keep the last valid tile id with an empty row
    range, so their grid steps rewrite an already-final tile unchanged.
    """
    e = group_sizes.shape[0]
    num_tiles = t // bt
    w = num_tiles + e
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    start_tile = starts // bt
    end_tile = (ends + bt - 1) // bt
    touches = jnp.where(group_sizes > 0, end_tile - start_tile, 0)
    item_ends = jnp.cumsum(touches)
    item_starts = item_ends - touches
    total = item_ends[-1]

    i = jnp.arange(w, dtype=jnp.int32)
    g = jnp.searchsorted(item_ends, i, side="right").astype(jnp.int32)
    g = jnp.minimum(g, e - 1)
    local = i - item_starts[g]
    tile = (start_tile[g] + local).astype(jnp.int32)
    valid = i < total
    # clamp invalid items onto the last valid item's tile
    last_tile = jnp.where(total > 0, tile[jnp.maximum(total - 1, 0)], 0)
    tile = jnp.where(valid, tile, last_tile).astype(jnp.int32)
    row_s = jnp.clip(starts[g] - tile * bt, 0, bt)
    row_e = jnp.clip(ends[g] - tile * bt, 0, bt)
    row_s = jnp.where(valid, row_s, 0).astype(jnp.int32)
    row_e = jnp.where(valid, row_e, 0).astype(jnp.int32)
    prev_tile = jnp.concatenate([jnp.asarray([-1], jnp.int32), tile[:-1]])
    first = (valid & (tile != prev_tile)).astype(jnp.int32)
    # first item per GROUP (for tgmm accumulation)
    gfirst = (valid & (local == 0)).astype(jnp.int32)
    return tile, g.astype(jnp.int32), first, row_s, row_e, gfirst


def _gmm_kernel(tiles, groups, first, row_s, row_e, _gf,
                x_ref, w_ref, o_ref, *, bt):
    i = pl.program_id(1)
    rows = jax.lax.broadcasted_iota(jnp.int32, (bt, 1), 0)
    mask = (rows >= row_s[i]) & (rows < row_e[i])
    contrib = jnp.dot(x_ref[...].astype(jnp.float32),
                      w_ref[0].astype(jnp.float32),
                      preferred_element_type=jnp.float32)

    @pl.when(first[i] == 1)
    def _init():
        o_ref[...] = jnp.where(mask, contrib, 0.0)

    @pl.when(first[i] == 0)
    def _merge():
        o_ref[...] = jnp.where(mask, contrib, o_ref[...])


@functools.partial(jax.jit, static_argnames=("bt", "bn"))
def _gmm_call(x, w, group_sizes, bt: int = 128, bn: int = 128):
    t, k = x.shape
    e, k2, n = w.shape
    assert k == k2 and t % bt == 0 and n % bn == 0
    meta = make_group_metadata(group_sizes, t, bt)
    tiles, groups, first, row_s, row_e, gfirst = meta
    nw = tiles.shape[0]
    out = pl.pallas_call(
        functools.partial(_gmm_kernel, bt=bt),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(n // bn, nw),
            in_specs=[
                pl.BlockSpec((bt, k), lambda j, i, tl, gr, *_: (tl[i], 0)),
                pl.BlockSpec((1, k, bn),
                             lambda j, i, tl, gr, *_: (gr[i], 0, j)),
            ],
            out_specs=pl.BlockSpec((bt, bn),
                                   lambda j, i, tl, gr, *_: (tl[i], j)),
        ),
        out_shape=jax.ShapeDtypeStruct((t, n), jnp.float32),
        interpret=_interpret(),
    )(tiles, groups, first, row_s, row_e, gfirst, x, w)
    return out.astype(x.dtype)


def _tgmm_kernel(tiles, groups, _first, row_s, row_e, gfirst,
                 x_ref, dy_ref, o_ref, *, bt):
    i = pl.program_id(2)
    rows = jax.lax.broadcasted_iota(jnp.int32, (bt, 1), 0)
    mask = (rows >= row_s[i]) & (rows < row_e[i])
    xm = jnp.where(mask, x_ref[...].astype(jnp.float32), 0.0)
    contrib = jnp.dot(xm.T, dy_ref[...].astype(jnp.float32),
                      preferred_element_type=jnp.float32)

    @pl.when(gfirst[i] == 1)
    def _init():
        o_ref[0] = contrib

    @pl.when(gfirst[i] == 0)
    def _acc():
        o_ref[0] = o_ref[0] + contrib


@functools.partial(jax.jit, static_argnames=("bt", "bk", "bn"))
def _tgmm_call(x, dy, group_sizes, bt: int = 128, bk: int = 128,
               bn: int = 128):
    """dw[e] = x_rows(e)^T @ dy_rows(e): [t,k] x [t,n] -> [e,k,n] f32."""
    t, k = x.shape
    t2, n = dy.shape
    e = group_sizes.shape[0]
    assert t == t2 and t % bt == 0 and k % bk == 0 and n % bn == 0
    meta = make_group_metadata(group_sizes, t, bt)
    tiles, groups, first, row_s, row_e, gfirst = meta
    nw = tiles.shape[0]
    out = pl.pallas_call(
        functools.partial(_tgmm_kernel, bt=bt),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(k // bk, n // bn, nw),
            in_specs=[
                pl.BlockSpec((bt, bk),
                             lambda kb, j, i, tl, gr, *_: (tl[i], kb)),
                pl.BlockSpec((bt, bn),
                             lambda kb, j, i, tl, gr, *_: (tl[i], j)),
            ],
            out_specs=pl.BlockSpec(
                (1, bk, bn), lambda kb, j, i, tl, gr, *_: (gr[i], kb, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((e, k, n), jnp.float32),
        interpret=_interpret(),
    )(tiles, groups, first, row_s, row_e, gfirst, x, dy)
    # groups with zero rows are never visited: their windows are
    # uninitialized memory, not zeros
    return jnp.where((group_sizes > 0)[:, None, None], out, 0.0)


@functools.lru_cache(maxsize=16)
def _gmm_with_blocks(bt: int, target: int):
    """custom_vjp grouped matmul closed over the row tile and the
    lane-block target (column blocks are fitted per matrix)."""

    def _fit(n):
        return _fp._best_block(n, target)

    @jax.custom_vjp
    def gmm_fn(x, w, group_sizes):
        return _gmm_call(x, w, group_sizes, bt=bt, bn=_fit(w.shape[-1]))

    def fwd(x, w, group_sizes):
        return gmm_fn(x, w, group_sizes), (x, w, group_sizes)

    def bwd(res, dy):
        x, w, group_sizes = res
        dx = _gmm_call(dy, jnp.swapaxes(w, 1, 2), group_sizes, bt=bt,
                       bn=_fit(w.shape[1])).astype(x.dtype)
        dw = _tgmm_call(x, dy, group_sizes, bt=bt, bk=_fit(x.shape[-1]),
                        bn=_fit(dy.shape[-1])).astype(w.dtype)
        return dx, dw, np.zeros(group_sizes.shape, jax.dtypes.float0)

    gmm_fn.defvjp(fwd, bwd)
    return gmm_fn


def gmm(x, w, group_sizes, bt: int = 128, block: int = 128):
    """Grouped matmul: rows of `x` (sorted by group, group g owning
    `group_sizes[g]` consecutive rows) multiply `w[g]`. [t,k]x[e,k,n]->[t,n].
    Rows beyond sum(group_sizes) are left untouched (slice them off).
    t must be a multiple of bt (pad with zeros). Differentiable in x and
    w; the backward runs the dx grouped matmul and the dw tgmm on the
    same work-item machinery."""
    return _gmm_with_blocks(bt, block)(x, w, group_sizes)


def topk_route(logits, top_k: int, normalize: bool = True):
    """Shared routing prologue (capacity AND dropless paths): softmax in
    f32, top-k, optional renormalization. One home so the two MoE
    formulations cannot drift numerically."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    topv, topi = jax.lax.top_k(probs, top_k)
    if normalize and top_k > 1:
        topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    return probs, topv, topi


def load_balance_aux(probs, topi):
    """Switch/GShard load-balance loss: e * sum_e mean(P_e) * mean(f_e)."""
    e = probs.shape[-1]
    first = jax.nn.one_hot(topi[:, 0], e)
    return (probs.mean(0) * first.mean(0)).sum() * float(e)


def _gmm_reference(x, w, group_sizes):
    """jnp oracle: per-group dense matmul with boundary masking."""
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    t = x.shape[0]
    rows = jnp.arange(t)
    out = jnp.zeros((t, w.shape[-1]), jnp.float32)
    for g in range(w.shape[0]):
        m = ((rows >= starts[g]) & (rows < ends[g]))[:, None]
        out = out + jnp.where(
            m, x.astype(jnp.float32) @ w[g].astype(jnp.float32), 0.0)
    return out.astype(x.dtype)


def moe_dropless_ffn(x2, logits, top_k: int, w1, b1, w2, b2, *,
                     act=jax.nn.gelu, normalize: bool = True,
                     bt: int = 128, block: int = 128):
    """Dropless MoE FFN over raw arrays: top-k route, sort tokens by
    expert, run both FFN matmuls as grouped matmuls, unsort, combine.

    x2 [t, d]; logits [t, e]; w1 [e, d, h]; w2 [e, h, d]. Returns
    ([t, d] output, aux load-balance loss — same Switch/GShard aux as
    top_k_gating). No token is ever dropped, whatever the routing skew
    (MegaBlocks semantics); weights are used replicated (no ep-axis
    manual sharding in this path)."""
    t, d = x2.shape
    e = logits.shape[-1]
    probs, topv, topi = topk_route(logits, top_k, normalize)

    flat_e = topi.reshape(-1)                       # [t*k]
    order = jnp.argsort(flat_e, stable=True)
    src_tok = order // top_k                        # token of each slot
    tk = t * top_k
    pad = (-tk) % bt
    xs = x2[src_tok]
    if pad:
        xs = jnp.concatenate(
            [xs, jnp.zeros((pad, d), x2.dtype)], axis=0)
    es = flat_e[order]
    group_sizes = jnp.bincount(flat_e, length=e)

    h = gmm(xs, w1, group_sizes, bt=bt, block=block)
    es_pad = jnp.concatenate(
        [es, jnp.zeros((pad,), es.dtype)]) if pad else es
    h = h + b1[es_pad].astype(h.dtype)
    h = act(h.astype(jnp.float32)).astype(h.dtype)
    y = gmm(h, w2, group_sizes, bt=bt, block=block)
    y = y + b2[es_pad].astype(y.dtype)
    y = y[:tk]
    # unsort and combine with the routing weights
    inv = jnp.argsort(order, stable=True)
    y = y[inv].reshape(t, top_k, d)
    out = jnp.einsum("tk,tkd->td", topv.astype(y.dtype), y)
    aux = load_balance_aux(probs, topi)
    return out.astype(x2.dtype), aux


__all__ = ["gmm", "make_group_metadata", "moe_dropless_ffn",
           "_gmm_reference"]
