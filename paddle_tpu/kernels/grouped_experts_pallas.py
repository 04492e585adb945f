"""Grouped expert FFN for the serving step: top-k dispatch over the experts
one chip holds, at fixed shapes whatever the routing.

The step's (token, expert) pairs that fall on a held expert are laid out in
row tiles of ``tm`` rows, every tile one expert's: expert ``e``'s pairs fill
``ceil(size_e / tm)`` tiles, the experts one after another. ``group_plan``
computes the layout from the pairs' expert ids with one sort and one running
count, no scatter; its shapes depend on the number of pairs and of experts
alone (``ceil(pairs / tm) + experts`` tiles: a sum of ceilings is under the
ceiling of the sum plus one a group), so nothing is ever dropped and a
routing that sends every pair to one expert runs the same program as an even
one. ``grouped_experts`` is the product: a Pallas kernel on a grid (tile,
block of the expert width) that reads tile ``t``'s expert through the
scalar-prefetched tile table, computes ``down(silu(gate x) * up x)`` (three
banks) or, without a gate bank, ``down(relu(up x) ** 2)`` (two), a block of
the expert's width at a time into a float32 accumulator, and skips the
tiles past the live ones (their block indices repeat the last live step's,
so nothing is fetched for them). A held expert no pair chose is never read.
The block of the width is sized from the shapes (``width_block``).
Off the chip, and under a mesh, the same tiles go through ``jnp``
(``_reference``), which is also the oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.jax_compat import tpu_compiler_params as _tpu_compiler_params

TM = 64                 # rows a tile
BANK_BYTES = 40 << 20   # of weight blocks in flight, all banks, both buffers
VMEM_LIMIT = 96 << 20   # three weight blocks, double-buffered: 38 MiB at
#                         6144 x 512 bfloat16
_INTERPRET = False      # tests flip this to run the kernel off-TPU


def group_plan(keys, n_groups: int, tm: int = TM):
    """The layout of ``keys`` ([N] int32: a pair's group ``0..n_groups-1``,
    or ``n_groups`` for a pair no group here takes) in tiles of ``tm`` rows.
    Returns

      sizes       [G]  pairs a group
      tile_group  [NT] the group of each tile, ``G`` for a tile past the
                  live ones; ``NT = ceil(N / tm) + G``
      n_live      []   live tiles (they come first)
      row_pair    [NT * tm] the pair in each row, -1 for a padding row
      pair_row    [N]  the row of each pair, -1 for a pair no group takes

    A group's pairs keep their order."""
    n = keys.shape[0]
    nt = -(-n // tm) + n_groups
    i32 = jnp.int32
    groups = jnp.arange(n_groups, dtype=i32)
    mine = keys[:, None] == groups[None, :]                     # [N, G]
    sizes = mine.sum(0).astype(i32)
    first = jnp.cumsum(sizes) - sizes            # in the sorted order
    tiles = (sizes + tm - 1) // tm
    tile_end = jnp.cumsum(tiles)
    tile_first = tile_end - tiles
    t = jnp.arange(nt, dtype=i32)
    tile_group = (t[:, None] >= tile_end[None, :]).sum(1).astype(i32)
    g = jnp.minimum(tile_group, n_groups - 1)
    rank = (t - tile_first[g])[:, None] * tm + jnp.arange(tm, dtype=i32)
    live = (tile_group < n_groups)[:, None] & (rank < sizes[g][:, None])
    order = jnp.argsort(keys, stable=True).astype(i32)
    row_pair = jnp.where(
        live, order[jnp.where(live, first[g][:, None] + rank, 0)], -1)
    # a pair's rank in its group: how many of the group came before it
    before = (jnp.cumsum(mine, axis=0) - mine).astype(i32)
    held = keys < n_groups
    kg = jnp.minimum(keys, n_groups - 1)
    pair_rank = jnp.take_along_axis(before, kg[:, None], 1)[:, 0]
    pair_row = jnp.where(held, tile_first[kg] * tm + pair_rank, -1)
    return sizes, tile_group, tile_end[-1], row_pair.reshape(-1), pair_row


def _silu_mul(g, u, dtype):
    return (g * jax.nn.sigmoid(g) * u).astype(dtype)


def _relu2(u, dtype):
    r = jnp.maximum(u, 0.0)
    return (r * r).astype(dtype)


def width_block(hidden: int, width: int, banks: int, itemsize: int) -> int:
    """Columns of the expert width a grid step: the largest whole number of
    lane tiles that divides the width and keeps the banks' blocks, double
    buffered, inside ``BANK_BYTES`` (512 of 2048 at three banks of 6144
    rows; all 2688 at two banks of 1024 rows, where 512 does not divide);
    the whole width where no lane tile divides it."""
    fit = [c for c in range(128, width + 1, 128) if width % c == 0
           and 2 * banks * hidden * c * itemsize <= BANK_BYTES]
    return max(fit) if fit else width


def _reference(xs, tile_group, w_gate, w_up, w_down):
    """``grouped_experts`` in ``jnp``: each tile against its expert's
    weights, gathered a tile; a tile past the live ones gives zeros."""
    e = w_up.shape[0]
    nt = tile_group.shape[0]
    x = xs.reshape(nt, -1, xs.shape[-1])
    g = jnp.minimum(tile_group, e - 1)
    f32 = jnp.float32

    def mm(a, b):
        return jnp.einsum("tmk,tkn->tmn", a, b, preferred_element_type=f32)

    h = _relu2(mm(x, w_up[g]), xs.dtype) if w_gate is None \
        else _silu_mul(mm(x, w_gate[g]), mm(x, w_up[g]), xs.dtype)
    y = mm(h, w_down[g]).astype(xs.dtype)
    return jnp.where((tile_group < e)[:, None, None], y, 0).reshape(xs.shape)


def _kernel(tg_ref, nl_ref, x_ref, *refs):
    """refs: the banks' blocks (gate, up, down; or up, down), the output's,
    the accumulator."""
    *w_refs, wd_ref, o_ref, acc_ref = refs
    t, f = pl.program_id(0), pl.program_id(1)

    @pl.when(t < nl_ref[0])
    def _():
        @pl.when(f == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        x = x_ref[...]
        f32 = jnp.float32
        into = [jnp.dot(x, w[0], preferred_element_type=f32) for w in w_refs]
        h = _silu_mul(*into, x.dtype) if len(into) == 2 \
            else _relu2(*into, x.dtype)
        acc_ref[...] += jnp.dot(h, wd_ref[0], preferred_element_type=f32)

        @pl.when(f == pl.num_programs(1) - 1)
        def _():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def tiles(xs, w_up, tm) -> bool:
    """Can Mosaic tile this product? Whole sublane tiles of rows, whole
    lane tiles of both widths (``width_block`` then finds a block of the
    expert width, be it 2048 or 2688 = 21 x 128)."""
    sub = 8 * (4 // jnp.dtype(xs.dtype).itemsize)
    return (jnp.dtype(xs.dtype).itemsize in (2, 4) and tm % sub == 0
            and xs.shape[-1] % 128 == 0 and w_up.shape[-1] % 128 == 0)


def grouped_experts(xs, tile_group, n_live, w_gate, w_up, w_down,
                    kernel: bool = True):
    """xs: [NT * tm, H], the pairs' inputs as ``group_plan`` lays them out;
    tile_group: [NT]; n_live: []; w_gate, w_up: [E, H, F]; w_down: [E, F,
    H]. Returns [NT * tm, H] in xs.dtype: ``down(silu(x gate) * (x up))`` of
    each row by its tile's expert, or with ``w_gate`` None (experts of two
    banks) ``down(relu(x up) ** 2)``; the rows of tiles past the live ones are
    not defined (mask them with ``row_pair >= 0``). The Pallas kernel
    where the backend is a TPU and the shapes tile, else ``jnp``;
    ``kernel`` False is ``jnp`` everywhere (a mesh cannot partition a bare
    ``pallas_call``; the oracle)."""
    from . import on_tpu
    tm = xs.shape[0] // tile_group.shape[0]
    if not (kernel and (_INTERPRET or (on_tpu() and tiles(xs, w_up, tm)))):
        return _reference(xs, tile_group, w_gate, w_up, w_down)
    return _call(xs, tile_group, jnp.reshape(n_live, (1,)).astype(jnp.int32),
                 w_gate, w_up, w_down, interpret=_INTERPRET)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _call(xs, tile_group, n_live, w_gate, w_up, w_down, *, interpret):
    nt = tile_group.shape[0]
    m, h = xs.shape
    tm = m // nt
    e, _, width = w_up.shape
    banks = [w for w in (w_gate, w_up) if w is not None]
    tf = width_block(h, width, len(banks) + 1, jnp.dtype(xs.dtype).itemsize)
    nf = width // tf

    def tile(t, nl):
        return jnp.maximum(jnp.minimum(t, nl[0] - 1), 0)

    def rows(t, f, tg, nl):
        return tile(t, nl), 0

    def block(t, f, tg, nl):
        """The expert and the block of its width a step reads: a step past
        the live tiles repeats the last live step's."""
        return (jnp.minimum(tg[tile(t, nl)], e - 1),
                jnp.where(t < nl[0], f, nf - 1))

    def up(t, f, tg, nl):
        ex, fb = block(t, f, tg, nl)
        return ex, 0, fb

    def down(t, f, tg, nl):
        ex, fb = block(t, f, tg, nl)
        return ex, fb, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nt, nf),
        in_specs=[pl.BlockSpec((tm, h), rows)]
        + [pl.BlockSpec((1, h, tf), up) for _ in banks]
        + [pl.BlockSpec((1, tf, h), down)],
        out_specs=pl.BlockSpec((tm, h), rows),
        scratch_shapes=[pltpu.VMEM((tm, h), jnp.float32)],
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, h), xs.dtype),
        compiler_params=_tpu_compiler_params(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="grouped_experts",
    )(tile_group, n_live, xs, *banks, w_down)


__all__ = ["grouped_experts", "group_plan", "tiles", "width_block", "TM"]
