"""The Mamba-2 state update of the serving step: one recurrent state a
sequence, kept in a pool by the sequence's slot beside the K/V pages, read
and written in place once a step and layer.

    S_t = decay_t S_(t-1) + (dt_t x_t) (x) B_t        y_t = S_t C_t

for every head, float32 throughout. A step packs the rows of each scheduled
sequence side by side and in order (``ServingEngine._pack_plan``): one row
for a sequence that decodes, a chunk of its prompt for one that prefills.
``ssm_scan`` advances each scheduled sequence's state over its rows of this
step, starting from what its slot holds, or from zero for a sequence whose
first row stands at position 0 (decided here, from ``scan_meta``: nobody
clears a slot), and leaves the new state in the slot. The slots of sequences
the step does not schedule are not read, not written and not copied.

The pool is ``[layers, slots, tiles, state, lanes]`` float32: the heads of a
layer laid ``lane_heads`` a tile side by side in the lanes (two heads of 64
fill the 128 lanes; a head alone would leave half of every vector register
and of every tile in HBM empty), the state's index along the sublanes, so
that ``S C`` is a sum over sublanes and ``B`` enters as a column. The heads
of one tile share a group, hence ``B`` and ``C``.

The kernel runs on a grid (block of tiles, scheduled sequence): the row
arrays of a block stay in VMEM while the sequences pass, a sequence's state
block comes through the pipeline once, is advanced row by row where it lies
and goes back to the slot it came from (``input_output_aliases``); the grid
steps past the scheduled sequences repeat the last one's block, so nothing
is fetched or written for them. Off the chip, and where the shapes do not
tile, the same call goes through ``jnp`` (``_reference``), which is also
the oracle.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.jax_compat import tpu_compiler_params as _tpu_compiler_params
from .ragged_pallas import seq_meta

LANES = 128
BLOCK_BYTES = 512 << 10  # of one sequence's state a grid step (one group's)
VMEM_LIMIT = 64 << 20   # three row blocks and the state's, double-buffered:
#                         20 MiB at 320 rows of 2048 lanes
_INTERPRET = False      # tests flip this to run the kernel off-TPU


def lane_heads(heads: int, groups: int, head_dim: int) -> int:
    """Heads a lane tile: the most that share a group and fit the lanes."""
    per_group = heads // groups
    return max(d for d in range(1, per_group + 1)
               if per_group % d == 0 and (d == 1 or d * head_dim <= LANES))


def pool_shape(heads: int, groups: int, head_dim: int, state: int):
    """One sequence's state of one layer as the pool keeps it: (tiles,
    state, lanes)."""
    hp = lane_heads(heads, groups, head_dim)
    return heads // hp, state, hp * head_dim


def to_pool(state, groups: int):
    """[heads, head_dim, state] (the model's) -> the pool's layout."""
    h, p, n = state.shape
    hp = lane_heads(h, groups, p)
    return state.reshape(h // hp, hp * p, n).transpose(0, 2, 1)


def from_pool(tiles, head_dim: int):
    """The pool's layout -> [heads, head_dim, state]."""
    q, n, w = tiles.shape
    return tiles.transpose(0, 2, 1).reshape(q * (w // head_dim), head_dim, n)


class ScanMeta(NamedTuple):
    """Which rows are whose, once a step: by row (``slots`` [T], -1 for a
    row that is nobody's; ``first`` [T], a row at position 0) and by
    scheduled sequence, the scheduled ones first (``order`` [S] their
    slots, ``starts``, ``counts``, ``resets`` [S]; ``n_live`` [])."""
    slots: jax.Array
    first: jax.Array
    order: jax.Array
    starts: jax.Array
    counts: jax.Array
    resets: jax.Array
    n_live: jax.Array


def scan_meta(slot_ids, positions, valid, num_slots: int) -> ScanMeta:
    """slot_ids, positions, valid: [T] as the step program gets them. The
    rows of a slot are contiguous and in order of position."""
    i32 = jnp.int32
    starts, counts, ctx, _ = seq_meta(slot_ids, positions, valid, num_slots)
    live = counts > 0
    order = jnp.argsort(~live, stable=True).astype(i32)
    return ScanMeta(
        jnp.where(valid, slot_ids, -1).astype(i32), valid & (positions == 0),
        order, starts[order], counts[order],
        (live & (ctx == counts))[order].astype(i32), live.sum().astype(i32))


def _rows(x, b, c, dt, decay):
    """The row arrays both paths take: (dtx, decay) [T, heads * head_dim]
    float32, lane for lane what the pool's tiles hold, and (b, c) [T, groups
    * state] float32 (a row at any offset is read whole only at 32 bits)."""
    t, _, p = x.shape
    dtx = (dt[:, :, None] * x.astype(jnp.float32)).reshape(t, -1)
    return (dtx, jnp.repeat(decay, p, axis=1),
            b.reshape(t, -1).astype(jnp.float32),
            c.reshape(t, -1).astype(jnp.float32))


def _reference(pool, layer, dtx, decay, b, c, meta: ScanMeta):
    """``ssm_scan`` in ``jnp``: the rows one after another, each on its
    slot's state."""
    f32 = jnp.float32
    q, n, w = pool.shape[2:]
    tpg = q // (b.shape[1] // n)              # tiles a group

    def cols(v):                              # [G * N] -> [Q, N, 1]
        return jnp.repeat(v.astype(f32).reshape(-1, n), tpg, axis=0)[..., None]

    def one(states, row):
        slot, first, u, d, bt, ct = row
        at = jnp.maximum(slot, 0)
        s = jnp.where(first, 0.0, states[at])
        s = d.reshape(q, 1, w) * s + cols(bt) * u.reshape(q, 1, w)
        y = jnp.sum(s * cols(ct), axis=1).reshape(-1)
        keep = slot >= 0
        return (states.at[at].set(jnp.where(keep, s, states[at])),
                jnp.where(keep, y, 0.0))

    states, y = jax.lax.scan(one, pool[layer],
                             (meta.slots, meta.first, dtx, decay, b, c))
    return y, pool.at[layer].set(states)


def tiles(pool, rows: int) -> bool:
    """Can Mosaic tile this update? Whole lane tiles, a square transpose
    for the columns, whole sublane tiles of rows."""
    _, _, _, n, w = pool.shape
    return pool.dtype == jnp.float32 and w == LANES and n == LANES \
        and rows % 8 == 0


def ssm_scan(pool, layer, x, b, c, dt, decay, meta: ScanMeta,
             kernel: bool = True):
    """pool: [layers, slots, tiles, state, lanes] float32 (``pool_shape``);
    layer: which of them, [] int32 or an int; x: [T, heads, head_dim]; b, c:
    [T, groups, state]; dt, decay: [T, heads] float32 (``decay = exp(dt
    A)``); meta: ``scan_meta``'s. Returns (y [T, heads * head_dim] float32,
    ``S_t C_t`` of every row, nought for a row that is nobody's; the pool
    with the scheduled sequences' states advanced, every other slot as it
    was). The Pallas kernel where the backend is a TPU and the shapes tile,
    else ``jnp``; ``kernel`` False is ``jnp`` everywhere (the oracle)."""
    from . import on_tpu
    rows = _rows(x, b, c, dt, decay)
    if not (kernel and (_INTERPRET or (on_tpu() and tiles(pool, x.shape[0])))):
        return _reference(pool, layer, *rows, meta)
    i32 = jnp.int32
    return _call(meta.order, meta.starts, meta.counts, meta.resets,
                 jnp.reshape(meta.n_live, (1,)).astype(i32),
                 jnp.reshape(jnp.asarray(layer, i32), (1,)), *rows, pool,
                 interpret=_INTERPRET, block_bytes=BLOCK_BYTES)


def _column(ref, t, g, width):
    """Row ``t``, group ``g`` of ``ref`` [T, a block's groups, state] as a column,
    [state, width] float32: entry n in every lane."""
    row = ref[t, g:g + 1, :]
    return jnp.broadcast_to(row, (width, row.shape[1])).T


def _kernel(order_ref, start_ref, count_ref, reset_ref, nl_ref, layer_ref,
            dtx_ref, dec_ref, b_ref, c_ref, s_ref, y_ref, o_ref, *, tpg):
    del order_ref, layer_ref                  # the index maps read them
    i = pl.program_id(1)
    bt, n, w = o_ref.shape[2:]

    @pl.when(i == 0)
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)    # rows that are nobody's

    def advance(t, src_ref, fresh):
        """Row ``t`` on the block's state: from ``src_ref`` into o_ref."""
        for g in range(bt // tpg):
            b_col = _column(b_ref, t, g, w)
            c_col = _column(c_ref, t, g, w)
            for k in range(g * tpg, (g + 1) * tpg):
                s = src_ref[0, 0, k]
                if fresh is not None:
                    s = jnp.where(fresh, 0.0, s)
                s = dec_ref[t, k:k + 1, :] * s + b_col * dtx_ref[t, k:k + 1, :]
                o_ref[0, 0, k] = s
                y_ref[t, k:k + 1, :] = jnp.sum(s * c_col, axis=0,
                                               keepdims=True)

    @pl.when(i < nl_ref[0])
    def _():
        start = start_ref[i]
        advance(start, s_ref, reset_ref[i] != 0)

        def more(r, carry):
            advance(start + r, o_ref, None)
            return carry

        jax.lax.fori_loop(1, count_ref[i], more, 0)

    @pl.when((i == 0) & (nl_ref[0] == 0))
    def _():
        o_ref[...] = s_ref[...]               # nothing scheduled: as it was


@functools.partial(jax.jit, static_argnames=("interpret", "block_bytes"))
def _call(order, starts, counts, resets, n_live, layer, dtx, decay, b, c,
          pool, *, interpret, block_bytes):
    _, slots, q, n, w = pool.shape
    t = dtx.shape[0]
    groups = b.shape[1] // n
    tpg = q // groups
    # whole groups a block, as many as ``block_bytes`` of state hold
    gpb = max(d for d in range(1, groups + 1) if groups % d == 0
              and (d == 1 or d * tpg * n * w * 4 <= block_bytes))
    bt = gpb * tpg

    def rows(j, i, *_):
        return 0, j, 0

    def block_groups(j, i, *_):
        return 0, j, 0, 0

    def state(j, i, order, starts, counts, resets, nl, layer):
        at = jnp.maximum(jnp.minimum(i, nl[0] - 1), 0)
        return layer[0], order[at], j, 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(q // bt, slots),
        in_specs=[pl.BlockSpec((t, bt, w), rows),
                  pl.BlockSpec((t, bt, w), rows),
                  pl.BlockSpec((t, None, gpb, n), block_groups),
                  pl.BlockSpec((t, None, gpb, n), block_groups),
                  pl.BlockSpec((1, 1, bt, n, w), state)],
        out_specs=[pl.BlockSpec((t, bt, w), rows),
                   pl.BlockSpec((1, 1, bt, n, w), state)],
    )
    y, pool = pl.pallas_call(
        functools.partial(_kernel, tpg=tpg),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((t, q, w), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={10: 1},
        compiler_params=_tpu_compiler_params(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="ssm_scan",
    )(order, starts, counts, resets, n_live, layer, dtx.reshape(t, q, w),
      decay.reshape(t, q, w), b.reshape(t, groups // gpb, gpb, n),
      c.reshape(t, groups // gpb, gpb, n), pool)
    return y.reshape(t, q * w), pool


__all__ = ["ssm_scan", "scan_meta", "pool_shape", "to_pool", "from_pool",
           "lane_heads", "tiles"]
