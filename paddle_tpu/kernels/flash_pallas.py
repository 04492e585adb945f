"""Pallas TPU flash-attention kernels (forward + backward).

Reference parity: the capability of paddle's FA2 integration
(paddle/phi/kernels/gpu/flash_attn_kernel.cu:673 forward,
phi/kernels/gpu/flash_attn_grad_kernel.cu:673 backward). Design:

  forward: 3-D sequential grid (batch*heads, q_blocks, kv_blocks) with running
  (m, l, acc) carried in VMEM scratch across the innermost kv dimension — the
  standard TPU online-softmax pattern. Also emits the logsumexp per row so the
  backward can recompute probabilities tile-by-tile without rematerializing
  the full [s, s] score matrix.

  backward: two kernels (the FA2 split). dq: grid (bh, q_blocks, kv_blocks),
  accumulating dq tiles in VMEM while sweeping kv. dk/dv: grid
  (bh, kv_blocks, q_blocks), accumulating dk/dv tiles while sweeping q. Each
  tile recomputes p = exp(s - lse) from q/k and the saved lse (no softmax
  storage), and uses delta = rowsum(dO * O) for the softmax jacobian.

MXU notes: all dots keep the input dtype (bf16 stays bf16) and accumulate in
fp32 via preferred_element_type — casting inputs to fp32 first would run the
MXU at a fraction of its bf16 rate. Probabilities are cast back to the value
dtype before the p@v / p^T@dO dots for the same reason.

Tiles: a grid step covers up to 1024 x 1024 of the score matrix. Each kernel
sizes (block_q, block_k) for itself from the call's lengths, head size and
item size (``choose_tiles``: the largest that divide the lengths and fit a
VMEM budget), because on a v5e the time of a call on small tiles is the
number of grid steps and not the arithmetic; an explicit block size and an
autotuned one come first (``_resolve_blocks``).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.jax_compat import tpu_compiler_params as _tpu_compiler_params

NEG_INF = -1e30
# Row statistics (lse, delta) are stored broadcast over a trailing lane dim:
# Pallas TPU requires the last two block dims to be (8, 128)-divisible or
# equal to the array dims, so a [rows] vector can't use a (1, block) spec.
# A trailing dim of 8 satisfies "equal to the array dim" while costing 16x
# less HBM than the 128-lane layout used by jax's reference flash kernel.
LANES = 8

_INTERPRET = False  # tests flip this to run the kernels off-TPU

# -- tiles --------------------------------------------------------------------
# On 128 x 128 tiles a call at [128, 2048, 128] is 32,768 grid steps and takes
# 11-14 ms in each kernel, for 0.7-1.4 ms of arithmetic; at 1024 x 1024 it is
# 512 steps and 1.7-2.9 ms (PERF.md section 6, PR 32: the ladder). So the tiles
# are as large as VMEM allows, not the 128 x 128 the MXU would be content
# with, even where a causal diagonal then runs through a large tile.
KERNELS = ("fwd", "dq", "dkv")
VMEM_BUDGET_BYTES = 20 * 2 ** 20    # what choose_tiles lets a grid step hold
VMEM_LIMIT_BYTES = 96 * 2 ** 20     # Mosaic's scoped limit for these calls
MAX_BLOCK = 1024    # no rung of the ladder longer than this on a side won
_ROW_BYTES = 128 * 4    # a [rows, 1] or [rows, LANES] float32 block in VMEM


class Tiles(NamedTuple):
    block_q: int
    block_k: int
    grid_steps: int     # of the whole call: batch*heads x q blocks x kv blocks


def tile_vmem_bytes(kernel: str, bq: int, bk: int, head_dim: int,
                    itemsize: int) -> int:
    """Bytes one grid step of ``kernel`` keeps in VMEM at tiles (bq, bk):
    its operand and result blocks twice (the pipeline double-buffers them),
    the float32 score-sized temporaries, the float32 accumulators. The
    three kernels hold different things, so each is sized for itself."""
    if kernel == "fwd":      # q, o | k, v | lse; acc, m, l; s, p
        blocks = (2 * bq + 2 * bk) * head_dim * itemsize + bq * _ROW_BYTES
        kept = bq * head_dim * 4 + 2 * bq * _ROW_BYTES
        scores = 2
    elif kernel == "dq":     # q, dO, dq | k, v | lse, delta; acc; p, dp, ds
        blocks = (3 * bq + 2 * bk) * head_dim * itemsize \
            + 2 * bq * _ROW_BYTES
        kept = bq * head_dim * 4
        scores = 3
    elif kernel == "dkv":    # q, dO | k, v, dk, dv | lse, delta; two accs
        blocks = (2 * bq + 4 * bk) * head_dim * itemsize \
            + 2 * bq * _ROW_BYTES
        kept = 2 * bk * head_dim * 4
        scores = 3           # p, dp, ds
    else:
        raise ValueError(f"kernel {kernel!r} is not one of {KERNELS}")
    return 2 * blocks + kept + scores * bq * bk * 4


def _block_options(length: int) -> list:
    """Multiples of 128 up to MAX_BLOCK that divide ``length``; a length
    that has none is one block if it is short, else 128 (which the launcher
    refuses)."""
    return [b for b in range(128, min(length, MAX_BLOCK) + 1, 128)
            if length % b == 0] or [min(length, 128)]


def choose_tiles(kernel: str, sq: int, sk: int, head_dim: int, itemsize: int,
                 batch_heads: int = 1) -> Tiles:
    """The (block_q, block_k) a call runs on when nobody pinned or tuned
    them, and the grid steps that makes: the tiles of the largest area, up
    to MAX_BLOCK a side, that divide the lengths and whose working set fits
    VMEM_BUDGET_BYTES; of equal areas the squarer, then the one longer in
    keys (all three kernels lose more to a short block_k than to a short
    block_q). Whether the call is causal is not asked: a diagonal through a
    1024-wide tile costs less than the steps that smaller tiles add."""
    *_, bk, bq = max(
        (bq * bk, min(bq, bk), bk, bq)
        for bq in _block_options(sq) for bk in _block_options(sk)
        if tile_vmem_bytes(kernel, bq, bk, head_dim, itemsize)
        <= VMEM_BUDGET_BYTES)
    return Tiles(bq, bk, batch_heads * -(-sq // bq) * -(-sk // bk))


def call_tiles(q, k) -> dict:
    """{kernel: Tiles} of a call on q, k [batch, heads, seq, head_dim] that
    pins and tunes nothing: what the tools print beside their timings."""
    b, h, sq, d = q.shape
    return {kernel: choose_tiles(kernel, sq, k.shape[2], d, q.dtype.itemsize,
                                 batch_heads=b * h) for kernel in KERNELS}


def _compiler_params():
    return _tpu_compiler_params(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


def _causal_mask(iq, ik, block_q, block_k, offset):
    """Bottom-right-aligned causal mask (query i attends keys <= i + sk - sq),
    matching the XLA reference paths and the kv-cache decode convention;
    offset = sk - sq (0 for self-attention)."""
    q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 0)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 1)
    return q_pos + offset >= k_pos


def _flashmask_visible(iq, ik, block_q, block_k, bounds, causal, window):
    """FlashMask column-wise sparse mask for one [Bq, Bk] tile.

    bounds: [4, Bk] int32 rows = (LTS, LTE, UTS, UTE) for this kv block's
    columns — the canonical form of the reference's startend_row_indices
    (python/paddle/nn/functional/flash_attention.py:1299): in the strict
    lower triangle (i > j) rows LTS[j] <= i < LTE[j] are masked; in the
    strict upper triangle (i < j) rows UTS[j] <= i < UTE[j] are masked
    (causal masks the whole upper triangle instead). The O(S) bounds replace
    the O(S^2) dense mask — this is the point of flashmask. window (wl, wr)
    additionally restricts query i to keys in [i - wl, i + wr]."""
    i = iq * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                (block_q, block_k), 0)
    j = ik * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                (block_q, block_k), 1)
    lts, lte = bounds[0][None, :], bounds[1][None, :]
    masked_low = (i > j) & (i >= lts) & (i < lte)
    if causal:
        masked_up = i < j
    else:
        uts, ute = bounds[2][None, :], bounds[3][None, :]
        masked_up = (i < j) & (i >= uts) & (i < ute)
    masked = masked_low | masked_up
    if window is not None:
        wl, wr = window
        if wl is not None:
            masked = masked | (i > j + wl)
        if not causal and wr is not None:
            masked = masked | (i < j - wr)
    return ~masked


# -- forward ------------------------------------------------------------------

def _fa_kernel(q_ref, k_ref, v_ref, *rest, scale, causal, block_q, block_k,
               nk, offset, masked=False, window=None):
    bounds_ref = rest[0] if masked else None
    o_ref, lse_ref, m_scratch, l_scratch, acc_scratch = rest[masked:]
    ik = pl.program_id(2)
    iq = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_scratch[:] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[:] = jnp.zeros_like(l_scratch)
        acc_scratch[:] = jnp.zeros_like(acc_scratch)

    def _compute(vis=None, apply_causal=True):
        q = q_ref[0]                                 # [Bq, d] (input dtype)
        k = k_ref[0]                                 # [Bk, d]
        v = v_ref[0]                                 # [Bk, d]
        s = _dot(q, k, ((1,), (1,))) * scale         # [Bq, Bk] fp32
        if vis is not None:
            s = jnp.where(vis, s, NEG_INF)
        elif causal and apply_causal:
            s = jnp.where(_causal_mask(iq, ik, block_q, block_k, offset), s,
                          NEG_INF)
        m_prev = m_scratch[:]                        # [Bq, 1]
        l_prev = l_scratch[:]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                       # [Bq, Bk] fp32
        alpha = jnp.exp(m_prev - m_new)              # [Bq, 1]
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_scratch[:] = acc_scratch[:] * alpha + _dot(
            p.astype(v.dtype), v, ((1,), (0,)))
        m_scratch[:] = m_new
        l_scratch[:] = l_new

    if masked:
        # Dynamic block skip — the flashmask win: a tile whose columns mask
        # out every row (from the O(S) bounds, VPU-only work) never touches
        # the MXU. Causal full-upper tiles fall out of the same test.
        vis = _flashmask_visible(iq, ik, block_q, block_k, bounds_ref[0],
                                 causal, window)

        @pl.when(jnp.any(vis))
        def _():
            _compute(vis)
    elif causal:
        # Three tile kinds: fully masked (skip; the clamped index maps in
        # the launcher make their k/v DMA a no-op as well), diagonal
        # (apply the mask), fully visible interior (no mask work at all —
        # the common case for long sequences).
        visible = ik * block_k <= iq * block_q + (block_q - 1) + offset
        interior = (ik + 1) * block_k - 1 <= iq * block_q + offset

        @pl.when(visible & jnp.logical_not(interior))
        def _():
            _compute()

        @pl.when(interior)
        def _():
            _compute(apply_causal=False)
    else:
        _compute()

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_scratch[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scratch[:] / l_safe).astype(o_ref.dtype)
        lse = jnp.where(l == 0.0, NEG_INF, m_scratch[:] + jnp.log(l_safe))
        lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])


def _check_divisible(sq, sk, bq, bk, causal=False):
    if sq % bq or sk % bk:
        raise ValueError(
            f"flash_attention requires seq lengths divisible by the block "
            f"sizes (q {sq}%{bq}, kv {sk}%{bk}); pad or use the XLA path")
    if causal and sq > sk:
        # bottom-right alignment: rows i < sq-sk can attend NO keys; their
        # softmax is undefined (would silently emit uniform attention)
        raise ValueError(
            f"causal flash_attention requires q_len <= kv_len "
            f"(got {sq} > {sk}): leading rows would have empty masks")


def _flash_forward(q, k, v, causal, scale, block_q, block_k, bounds=None,
                   window=None):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    _check_divisible(sq, sk, bq, bk, causal)
    nq = sq // bq
    nk = sk // bk
    bh = b * h
    q_r = q.reshape(bh, sq, d)
    k_r = k.reshape(bh, sk, d)
    v_r = v.reshape(bh, sk, d)
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    masked = bounds is not None
    offset = sk - sq
    if causal:
        # Clamp the kv block index at the last visible block for this q
        # block: grid steps past the diagonal then re-request the SAME
        # block, and the Pallas pipeline elides the copy — causal skips
        # save the HBM traffic, not just the MXU work. SAFE for flashmask
        # too: a beyond-diagonal tile is invisible from the causal test
        # alone (i < j everywhere), whatever bounds data the clamped
        # fetch delivers.
        def kv_idx(ibh, iq, ik):
            last = jnp.clip((iq * bq + bq - 1 + offset) // bk, 0, nk - 1)
            return (ibh, jnp.minimum(ik, last), 0)
    else:
        def kv_idx(ibh, iq, ik):
            return (ibh, ik, 0)
    inputs = [q_r, k_r, v_r]
    in_specs = [
        pl.BlockSpec((1, bq, d), lambda ibh, iq, ik: (ibh, iq, 0)),
        pl.BlockSpec((1, bk, d), kv_idx),
        pl.BlockSpec((1, bk, d), kv_idx),
    ]
    if masked:
        # [b, h, sk, 4] -> [bh, 4, sk] (component-major for the kernel);
        # kv-block index clamped exactly like k/v under causal
        def bounds_idx(ibh, iq, ik):
            kidx = kv_idx(ibh, iq, ik)
            return (kidx[0], 0, kidx[1])

        inputs.append(jnp.swapaxes(bounds.reshape(bh, sk, 4), 1, 2))
        in_specs.append(pl.BlockSpec((1, 4, bk), bounds_idx))

    kernel = functools.partial(_fa_kernel, scale=s, causal=causal, block_q=bq,
                               block_k=bk, nk=nk, offset=sk - sq,
                               masked=masked, window=window)
    out, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=(bh, nq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda ibh, iq, ik: (ibh, iq, 0)),
            pl.BlockSpec((1, bq, LANES), lambda ibh, iq, ik: (ibh, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=_INTERPRET,
    )(*inputs)
    return out.reshape(b, h, sq, d), lse


# -- backward -----------------------------------------------------------------

def _fa_dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, *rest, scale,
                  causal, block_q, block_k, nk, offset, masked=False,
                  window=None):
    bounds_ref = rest[0] if masked else None
    dq_ref, acc_scratch = rest[masked:]
    ik = pl.program_id(2)
    iq = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        acc_scratch[:] = jnp.zeros_like(acc_scratch)

    def _compute(vis=None, apply_causal=True):
        q = q_ref[0]                                    # [Bq, d]
        k = k_ref[0]                                    # [Bk, d]
        v = v_ref[0]                                    # [Bk, d]
        g = g_ref[0]                                    # [Bq, d]
        lse = lse_ref[0][:, :1]                         # [Bq, 1] fp32
        delta = delta_ref[0][:, :1]                     # [Bq, 1] fp32
        s = _dot(q, k, ((1,), (1,))) * scale            # [Bq, Bk] fp32
        if vis is not None:
            s = jnp.where(vis, s, NEG_INF)
        elif causal and apply_causal:
            s = jnp.where(_causal_mask(iq, ik, block_q, block_k, offset), s,
                          NEG_INF)
        p = jnp.exp(s - lse)                            # [Bq, Bk] fp32
        dp = _dot(g, v, ((1,), (1,)))                   # [Bq, Bk] fp32
        ds = p * (dp - delta) * scale
        acc_scratch[:] += _dot(ds.astype(k.dtype), k, ((1,), (0,)))

    if masked:
        vis = _flashmask_visible(iq, ik, block_q, block_k, bounds_ref[0],
                                 causal, window)

        @pl.when(jnp.any(vis))
        def _():
            _compute(vis)
    elif causal:
        visible = ik * block_k <= iq * block_q + (block_q - 1) + offset
        interior = (ik + 1) * block_k - 1 <= iq * block_q + offset

        @pl.when(visible & jnp.logical_not(interior))
        def _():
            _compute()

        @pl.when(interior)
        def _():
            _compute(apply_causal=False)
    else:
        _compute()

    @pl.when(ik == nk - 1)
    def _finalize():
        dq_ref[0] = acc_scratch[:].astype(dq_ref.dtype)


def _fa_dkv_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, *rest,
                   scale, causal, block_q, block_k, nq, offset, masked=False,
                   window=None):
    bounds_ref = rest[0] if masked else None
    dk_ref, dv_ref, dk_scratch, dv_scratch = rest[masked:]
    iq = pl.program_id(2)
    ik = pl.program_id(1)

    @pl.when(iq == 0)
    def _init():
        dk_scratch[:] = jnp.zeros_like(dk_scratch)
        dv_scratch[:] = jnp.zeros_like(dv_scratch)

    def _compute(vis=None, apply_causal=True):
        # Same orientation as the dq kernel ([Bq, Bk] tiles); dk/dv contract
        # over the q dim (dim 0) instead, so no in-kernel transposes.
        q = q_ref[0]                                    # [Bq, d]
        k = k_ref[0]                                    # [Bk, d]
        v = v_ref[0]                                    # [Bk, d]
        g = g_ref[0]                                    # [Bq, d]
        lse = lse_ref[0][:, :1]                         # [Bq, 1] fp32
        delta = delta_ref[0][:, :1]                     # [Bq, 1] fp32
        s = _dot(q, k, ((1,), (1,))) * scale            # [Bq, Bk] fp32
        if vis is not None:
            s = jnp.where(vis, s, NEG_INF)
        elif causal and apply_causal:
            s = jnp.where(_causal_mask(iq, ik, block_q, block_k, offset), s,
                          NEG_INF)
        p = jnp.exp(s - lse)                            # [Bq, Bk] fp32
        dv_scratch[:] += _dot(p.astype(g.dtype), g, ((0,), (0,)))
        dp = _dot(g, v, ((1,), (1,)))                   # [Bq, Bk] fp32
        ds = p * (dp - delta) * scale
        dk_scratch[:] += _dot(ds.astype(q.dtype), q, ((0,), (0,)))

    if masked:
        vis = _flashmask_visible(iq, ik, block_q, block_k, bounds_ref[0],
                                 causal, window)

        @pl.when(jnp.any(vis))
        def _():
            _compute(vis)
    elif causal:
        # Skip q blocks entirely before this kv block; interior q blocks
        # (every query row past the kv block) need no mask work.
        visible = iq * block_q + (block_q - 1) + offset >= ik * block_k
        interior = iq * block_q + offset >= (ik + 1) * block_k - 1

        @pl.when(visible & jnp.logical_not(interior))
        def _():
            _compute()

        @pl.when(interior)
        def _():
            _compute(apply_causal=False)
    else:
        _compute()

    @pl.when(iq == nq - 1)
    def _finalize():
        dk_ref[0] = dk_scratch[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scratch[:].astype(dv_ref.dtype)


def _flash_backward(q, k, v, out, lse, g, causal, scale, dq_blocks,
                    dkv_blocks, bounds=None, window=None):
    """dq and dk/dv from the saved lse: two kernels, each on its own tiles
    (``dq_blocks`` and ``dkv_blocks`` are (block_q, block_k) pairs)."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bh = b * h
    rows = (q.reshape(bh, sq, d), k.reshape(bh, sk, d), v.reshape(bh, sk, d),
            g.reshape(bh, sq, d))
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).reshape(bh, sq)
    delta = jnp.broadcast_to(delta[:, :, None], (bh, sq, LANES))
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    bounds_r = None if bounds is None else \
        jnp.swapaxes(bounds.reshape(bh, sk, 4), 1, 2)
    dq = _flash_dq(*rows, lse, delta, bounds_r, causal, s, window,
                   *dq_blocks)
    dk, dv = _flash_dkv(*rows, lse, delta, bounds_r, causal, s, window,
                        *dkv_blocks)
    return (dq.reshape(b, h, sq, d), dk.reshape(b, h, sk, d),
            dv.reshape(b, h, sk, d))


def _flash_dq(q_r, k_r, v_r, g_r, lse, delta, bounds_r, causal, scale,
              window, block_q, block_k):
    """[bh, s, d] operands, grid (bh, q blocks, kv blocks): a dq tile
    accumulates in VMEM while the kv blocks sweep past it."""
    bh, sq, d = q_r.shape
    sk = k_r.shape[1]
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    _check_divisible(sq, sk, bq, bk, causal)
    nq = sq // bq
    nk = sk // bk
    masked = bounds_r is not None
    offset = sk - sq
    q_spec = pl.BlockSpec((1, bq, d), lambda ibh, i, j: (ibh, i, 0))
    row_spec = pl.BlockSpec((1, bq, LANES), lambda ibh, i, j: (ibh, i, 0))

    if causal:
        # causal DMA elision (see _flash_forward; safe for flashmask —
        # beyond-diagonal tiles are invisible from the causal test
        # alone): skipped kv blocks re-request the last visible block,
        # so their copies are no-ops
        def kv_idx_dq(ibh, iq, ik):
            last = jnp.clip((iq * bq + bq - 1 + offset) // bk, 0, nk - 1)
            return (ibh, jnp.minimum(ik, last), 0)
    else:
        def kv_idx_dq(ibh, iq, ik):
            return (ibh, ik, 0)

    dq_inputs = [q_r, k_r, v_r, g_r, lse, delta]
    dq_in_specs = [
        q_spec,
        pl.BlockSpec((1, bk, d), kv_idx_dq),
        pl.BlockSpec((1, bk, d), kv_idx_dq),
        q_spec, row_spec, row_spec,
    ]
    if masked:
        def bounds_idx_dq(ibh, iq, ik):
            kidx = kv_idx_dq(ibh, iq, ik)
            return (kidx[0], 0, kidx[1])

        dq_inputs.append(bounds_r)
        dq_in_specs.append(pl.BlockSpec((1, 4, bk), bounds_idx_dq))
    return pl.pallas_call(
        functools.partial(_fa_dq_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, nk=nk, offset=offset,
                          masked=masked, window=window),
        name="flash_dq",
        grid=(bh, nq, nk),
        in_specs=dq_in_specs,
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q_r.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=_INTERPRET,
    )(*dq_inputs)


def _flash_dkv(q_r, k_r, v_r, g_r, lse, delta, bounds_r, causal, scale,
               window, block_q, block_k):
    """[bh, s, d] operands, grid (bh, kv blocks, q blocks): a dk and a dv
    tile accumulate in VMEM while the q blocks sweep past them."""
    bh, sq, d = q_r.shape
    sk = k_r.shape[1]
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    _check_divisible(sq, sk, bq, bk, causal)
    nq = sq // bq
    nk = sk // bk
    masked = bounds_r is not None
    offset = sk - sq
    kv_spec = pl.BlockSpec((1, bk, d), lambda ibh, ik, iq: (ibh, ik, 0))
    if causal:
        # mirror of the dq clamp (safe for flashmask for the same
        # reason): q blocks entirely before this kv block are skipped, so
        # clamp the q-side index maps at the first visible q block and
        # their DMA elides
        def q_pos(ik, iq):
            first = jnp.clip((ik * bk - offset) // bq, 0, nq - 1)
            return jnp.maximum(iq, first)

        q_spec2 = pl.BlockSpec(
            (1, bq, d), lambda ibh, ik, iq: (ibh, q_pos(ik, iq), 0))
        row_spec2 = pl.BlockSpec(
            (1, bq, LANES), lambda ibh, ik, iq: (ibh, q_pos(ik, iq), 0))
    else:
        q_spec2 = pl.BlockSpec((1, bq, d),
                               lambda ibh, ik, iq: (ibh, iq, 0))
        row_spec2 = pl.BlockSpec((1, bq, LANES),
                                 lambda ibh, ik, iq: (ibh, iq, 0))
    dkv_inputs = [q_r, k_r, v_r, g_r, lse, delta]
    dkv_in_specs = [q_spec2, kv_spec, kv_spec, q_spec2, row_spec2, row_spec2]
    if masked:
        dkv_inputs.append(bounds_r)
        dkv_in_specs.append(
            pl.BlockSpec((1, 4, bk), lambda ibh, ik, iq: (ibh, 0, ik)))
    return pl.pallas_call(
        functools.partial(_fa_dkv_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, nq=nq, offset=offset,
                          masked=masked, window=window),
        name="flash_dkv",
        grid=(bh, nk, nq),
        in_specs=dkv_in_specs,
        out_specs=[kv_spec, kv_spec],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), k_r.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), v_r.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=_INTERPRET,
    )(*dkv_inputs)


# -- public op ----------------------------------------------------------------

def _reference_bhsd(q, k, v, causal, scale):
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    scores = jnp.einsum("bhsd,bhtd->bhst", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * s
    if causal:
        sq, sk = scores.shape[-2], scores.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), jnp.bool_), k=sk - sq)
        scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhst,bhtd->bhsd", probs, v.astype(jnp.float32)) \
        .astype(q.dtype)


def _resolve_blocks(which: str, kernel: str, q, k, causal, block_q,
                    block_k):
    """(block_q, block_k) of one kernel of a call: what the caller pinned,
    else the autotune cache's winner for ``which`` (in-process or its disk
    file), else ``choose_tiles`` for ``kernel`` — so a hardware-tuned
    decision reaches every call site without threading config (reference
    switch_autotune cache role)."""
    if block_q is not None and block_k is not None:
        return block_q, block_k
    from . import autotune
    sig = (q.shape[2], k.shape[2], q.shape[3], str(q.dtype), bool(causal))
    # fallback chain: flashmask inherits the dense-causal winner (same
    # tile geometry), and an untuned backward inherits the forward's
    # blocks (runtime tune_blocks only times the forward); the shapes
    # decide only when nothing was ever tuned
    chain = {"flashmask_fwd": ("flashmask_fwd", "flash_fwd"),
             "flashmask_bwd": ("flashmask_bwd", "flash_bwd", "flash_fwd"),
             "flash_bwd": ("flash_bwd", "flash_fwd")}.get(which, (which,))
    for key in chain:
        hit = autotune.cached(key, sig)
        if hit is not None:
            bq, bk = hit
            break
    else:
        bq, bk, _ = choose_tiles(kernel, *sig[:3], q.dtype.itemsize)
    return (block_q or bq), (block_k or bk)


def _forward(which, q, k, v, causal, scale, block_q, block_k, **mask):
    bq, bk = _resolve_blocks(which, "fwd", q, k, causal, block_q, block_k)
    return _flash_forward(q, k, v, causal, scale, bq, bk, **mask)


def _backward(which, q, k, v, out, lse, g, causal, scale, block_q, block_k,
              **mask):
    dq_blocks, dkv_blocks = (
        _resolve_blocks(which, kernel, q, k, causal, block_q, block_k)
        for kernel in ("dq", "dkv"))
    return _flash_backward(q, k, v, out, lse, g, causal, scale, dq_blocks,
                           dkv_blocks, **mask)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal=False, scale=None,
                    block_q=None, block_k=None):
    """q,k,v: [batch, heads, seq, head_dim]. block_q/block_k None =
    autotune-cached, else sized from the shapes (``choose_tiles``)."""
    return _forward("flash_fwd", q, k, v, causal, scale, block_q,
                    block_k)[0]


def _fa_fwd(q, k, v, causal, scale, block_q, block_k):
    out, lse = _forward("flash_fwd", q, k, v, causal, scale, block_q,
                        block_k)
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, scale, block_q, block_k, res, g):
    return _backward("flash_bwd", *res, g, causal, scale, block_q, block_k)


flash_attention.defvjp(_fa_fwd, _fa_bwd)


# -- flashmask ----------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def flashmask_attention(q, k, v, bounds, causal=False, scale=None,
                        window=None, block_q=None, block_k=None):
    """FlashMask attention: q,k,v [batch, heads, seq, head_dim]; bounds
    [batch, heads, kv_seq, 4] int32 canonical (LTS, LTE, UTS, UTE) column
    bounds (see _flashmask_visible). The sparse mask costs O(seq) memory and
    fully-masked tiles skip the MXU — the capability of the reference's
    flashmask_attention (flash_attention.py:1299) without a dense mask."""
    return _forward("flashmask_fwd", q, k, v, causal, scale, block_q,
                    block_k, bounds=bounds, window=window)[0]


def _fm_fwd(q, k, v, bounds, causal, scale, window, block_q, block_k):
    out, lse = _forward("flashmask_fwd", q, k, v, causal, scale, block_q,
                        block_k, bounds=bounds, window=window)
    return out, (q, k, v, bounds, out, lse)


def _fm_bwd(causal, scale, window, block_q, block_k, res, g):
    q, k, v, bounds, out, lse = res
    dq, dk, dv = _backward("flashmask_bwd", q, k, v, out, lse, g, causal,
                           scale, block_q, block_k, bounds=bounds,
                           window=window)
    return dq, dk, dv, None


flashmask_attention.defvjp(_fm_fwd, _fm_bwd)
