#!/usr/bin/env python
"""Does each Pallas kernel compile on this chip, and does it match its oracle?

One process on a TPU; arguments, if any, are prefixes of the kernels' names
to keep (``paged_attention``). Every Pallas kernel in
``paddle_tpu/kernels`` is run once, compiled (never interpreted), at one
production shape, against the jnp implementation the tests use as its
oracle (``chip_smoke.py`` gates the default-path flash forward and backward
at a smaller batch; here they run at the training cell's shape, and the
row names the tiles and grid steps each kernel took). The others sit behind
a flag that is off (``FLAGS_use_pallas_fused``) or behind an explicit
MoE/packing option, and this survey is the record of which of them the installed Mosaic accepts
(ROADMAP S7, D2). It turns no flag on. The serving step's paged attention
kernel is on the engine's path on one chip; it is here at the serving
cells' geometries against the gather-based reference.

Prints one JSON line per kernel — ``"verdict": "compiles and matches"``
or the compiler's own words — and a final summary line; the same goes to
``chiprun_out/kernel_check.json``. A refused kernel does not stop the
survey (reporting the refusal is its job), but the exit code is 1 unless
every kernel matched.
"""
from __future__ import annotations

import functools
import json
import os
import sys
import time
import traceback

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from paddle_tpu.utils import chip  # noqa: E402

REL_L2 = 2e-2          # bf16 kernels against a float32 oracle
REL_L2_F32 = 1e-5      # float32 kernels (the AdamW update)


def rel_l2(got, want):
    got = jnp.asarray(got, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def rand(key, shape, dtype=jnp.bfloat16, scale=1.0):
    return (jax.random.normal(jax.random.PRNGKey(key), shape) * scale) \
        .astype(dtype)


def out_and_grads(fn, args, cotangent):
    """(fn(*args), *d fn/d args) under one jit, so Mosaic sees what a
    model would hand it. The arrays are jit ARGUMENTS: closed over, they
    would be baked into the executable and its cache entry."""
    def run(args, cotangent):
        out, vjp = jax.vjp(fn, *args)
        return (out, *vjp(cotangent.astype(out.dtype)))
    return jax.jit(run)(args, cotangent)


def flash_tiles(q, k):
    """{kernel: [block_q, block_k, grid steps]} the flash kernels run this
    call on when nothing is pinned or tuned."""
    from paddle_tpu.kernels import flash_pallas as fp
    return {"tiles": {n: list(t) for n, t in fp.call_tiles(q, k).items()}}


# Each case returns its shape and {output name: (kernel result, oracle
# result)}; a third item, if any, is merged into the case's printed row.
def case_flash_attention():
    """Plain causal attention at cgpt13-train-2k's shape, default tiles."""
    from paddle_tpu.kernels import flash_pallas as fp
    shape = (8, 16, 2048, 128)
    q, k, v, g = (rand(i, shape) for i in range(4))
    got = out_and_grads(lambda q, k, v: fp.flash_attention(q, k, v, True),
                        (q, k, v), g)
    # the oracle holds [batch, 16, 2048, 2048] float32 scores and their
    # gradients: half the batch at a time
    halves = [out_and_grads(
        lambda q, k, v: fp._reference_bhsd(q, k, v, True, None),
        tuple(t[i:i + 4] for t in (q, k, v)), g[i:i + 4]) for i in (0, 4)]
    want = [jnp.concatenate(pair) for pair in zip(*halves)]
    return shape, dict(zip(("out", "dq", "dk", "dv"), zip(got, want))), \
        flash_tiles(q, k)


def case_flashmask():
    """Packed documents of 256 tokens: causal within a document."""
    from paddle_tpu.kernels import flash_pallas as fp
    from paddle_tpu.nn.functional.attention import (_flashmask_dense_visible,
                                                    _sdpa_reference)
    b, h, s, d, doc = 2, 16, 2048, 128, 256
    q, k, v, g = (rand(i, (b, h, s, d)) for i in range(4))
    j = jnp.arange(s)
    lts = ((j // doc + 1) * doc).astype(jnp.int32)
    bounds = jnp.broadcast_to(
        jnp.stack([lts, jnp.full((s,), s, jnp.int32),
                   jnp.zeros((s,), jnp.int32),
                   jnp.zeros((s,), jnp.int32)], -1)[None, None],
        (b, h, s, 4))

    def oracle(q, k, v):
        vis = _flashmask_dense_visible(bounds, s, s, True, None)
        to_bshd = lambda t: jnp.swapaxes(t, 1, 2)
        return to_bshd(_sdpa_reference(to_bshd(q), to_bshd(k), to_bshd(v),
                                       mask=vis))

    got = out_and_grads(
        lambda q, k, v: fp.flashmask_attention(q, k, v, bounds, True),
        (q, k, v), g)
    want = out_and_grads(oracle, (q, k, v), g)
    return (b, h, s, d), dict(zip(("out", "dq", "dk", "dv"),
                                  zip(got, want))), flash_tiles(q, k)


def case_fused_rope():
    from paddle_tpu.kernels.fused_pallas import fused_rope_pallas
    from paddle_tpu.models.llama import apply_rope, build_rope_cache
    b, s, h, d = 4, 2048, 16, 128
    q, k = rand(0, (b, s, h, d)), rand(1, (b, s, h, d))
    cos, sin = build_rope_cache(s, d)
    got = jax.jit(lambda q, k: fused_rope_pallas(q, k, cos, sin))(q, k)
    want = jax.jit(lambda q, k: apply_rope(q, k, cos, sin))(q, k)
    return (b, s, h, d), {"q": (got[0], want[0]), "k": (got[1], want[1])}


def case_fused_rms_norm():
    from paddle_tpu.kernels.fused_pallas import fused_rms_norm_pallas
    b, s, hid = 4, 2048, 2048
    x, r = rand(0, (b, s, hid)), rand(1, (b, s, hid))
    w = rand(2, (hid,))

    def oracle(x, r):
        xf = x.astype(jnp.float32) + r.astype(jnp.float32)
        return (xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True)
                                   + 1e-6) * w.astype(jnp.float32))

    got = jax.jit(lambda x, r: fused_rms_norm_pallas(
        x, w, eps=1e-6, residual=r))(x, r)
    got_plain = jax.jit(lambda x: fused_rms_norm_pallas(x, w, eps=1e-6))(x)
    return (b, s, hid), {
        "norm_residual": (got, oracle(x, r)),
        "norm": (got_plain, oracle(x, jnp.zeros_like(x)))}


def case_fused_adamw():
    from paddle_tpu.kernels import optimizer_pallas as op
    from paddle_tpu.optimizer import _adam_update
    shapes = [(2048, 5632), (2048, 2048), (2048,), (1000,)]
    ps = [rand(i, s, jnp.float32) for i, s in enumerate(shapes)]
    gs = [p * 0.01 for p in ps]
    ms = [jnp.zeros_like(p) for p in ps]
    vs = [jnp.zeros_like(p) for p in ps]
    hp = dict(lr=1e-3, beta1=0.9, beta2=0.95, eps=1e-8, step=2.0)
    got = jax.jit(lambda ps, gs, ms, vs: op.multi_tensor_adamw_pallas(
        ps, gs, ms, vs, wds=[0.1] * len(ps), **hp))(ps, gs, ms, vs)
    f = jnp.float32
    want = [_adam_update(p, g, m, v, f(hp["lr"]), f(hp["beta1"]),
                         f(hp["beta2"]), f(hp["eps"]), f(hp["step"]),
                         f(0.1), True)
            for p, g, m, v in zip(ps, gs, ms, vs)]
    pairs = {}
    for i in range(len(ps)):
        for j, name in enumerate(("p", "m", "v")):
            pairs[f"{name}{i}"] = (got[j][i], want[i][j])
    return shapes, pairs


def case_gmm():
    """Dropless-MoE grouped matmul at OLMoE-like widths: forward, and the
    backward's dx (gmm on w^T) and dw (tgmm)."""
    from paddle_tpu.kernels import gmm_pallas as G
    t, dm, dff, e = 4096, 2048, 1024, 8
    x = rand(0, (t, dm), scale=0.1)
    w = rand(1, (e, dm, dff), scale=0.1)
    ct = rand(2, (t, dff))
    sizes = jnp.asarray([700, 0, 1300, 512, 37, 1035, 256, 256], jnp.int32)

    got = out_and_grads(lambda x, w: G.gmm(x, w, sizes), (x, w), ct)
    want = out_and_grads(lambda x, w: G._gmm_reference(x, w, sizes),
                         (x, w), ct)
    return (t, dm, dff, e), dict(zip(("out", "dx", "dw"), zip(got, want)))


def packed_plan(entries, pages, rows, slots, table, plan, seed=0,
                first_slot=1):
    """(tables, slot_ids, positions, valid) of one packed serving step, as
    ``_pack_plan`` lays it out: ``plan`` is (rows, context) per scheduled
    sequence, one page-table slot each from ``first_slot`` on, live pages
    drawn from a shuffled entry of ``pages`` pages (drawn again from its
    start if the plan holds more); the rest of the budget is padding rows.
    The tables point into the LAST of ``entries`` entries joined into one
    pool, the farthest page offset ``make_attend`` applies."""
    bs = 16
    perm = np.random.default_rng(seed).permutation(pages)
    tables = np.full((slots, table), -1, np.int32)
    slot = np.zeros(rows, np.int32)
    pos = np.zeros(rows, np.int32)
    valid = np.zeros(rows, bool)
    at, row = 0, 0
    for s_, (n, ctx) in enumerate(plan, start=first_slot):
        need = -(-ctx // bs)
        tables[s_, :need] = (entries - 1) * pages \
            + perm[np.arange(at, at + need) % pages]
        at += need
        slot[row:row + n] = s_
        pos[row:row + n] = np.arange(ctx - n, ctx)
        valid[row:row + n] = True
        row += n
    return tuple(jnp.asarray(a) for a in (tables, slot, pos, valid))


def paged_step(entries, pages, kvh, rep, rows, slots, table, plan, seed=0,
               d=128, pools=2):
    """``packed_plan`` with its operands: (q, k_pool, v_pool, the plan).
    The pools are the ``entries`` cache entries' pages joined into one
    run, as the step program threads them; with ``pools`` 1 (a latent
    cache) ``v_pool`` is None."""
    q = rand(seed, (rows, kvh * rep, d))
    # under jit: a pool of gigabytes is drawn with no float32 copy beside it
    kp, vp = (jax.jit(rand, static_argnums=(0, 1))(
        seed + k, (entries * pages, kvh, 16, d)) if k <= pools else None
        for k in (1, 2))
    return q, kp, vp, packed_plan(entries, pages, rows, slots, table, plan,
                                  seed)


# (cache entries, pages an entry, KV heads, query heads a KV head, token
# budget, slots, table width, plan) of the three serving cells,
# `bench/traffic/`'s engines. Every decoder's step joins its entries' pages
# into one pool and the kernel reads one entry at a page offset
PAGED_GEOMETRIES = {
    # cgpt67-serve-decode: MHA; 13 decodes, a 40-row prompt chunk, a
    # verify chunk of 1 + 3 drafts
    "decode": (16, 256, 32, 1, 64, 16, 128,
               [(1, c) for c in range(70, 250, 14)] + [(40, 40), (4, 130)]),
    # mistral7b-serve-chat: GQA 32/8; 20 decodes, a chunk that continues a
    # 700-token prompt from mid-page, a short first chunk
    "chat": (20, 1280, 8, 4, 128, 32, 64,
             [(1, c) for c in range(60, 1000, 47)] + [(90, 700), (18, 18)]),
    # ouro26-serve-decode: MHA 16 x 128; 192 entries (3.2 GB a pool: page
    # offsets pass 2**31 bytes); the decode cell's plan
    "looped": (192, 256, 16, 1, 64, 16, 128,
               [(1, c) for c in range(70, 250, 14)] + [(40, 40), (4, 130)]),
}


def case_paged_attention(cell):
    """The step's attention kernel against the gather-based reference."""
    from paddle_tpu.kernels import ragged_pallas as rp
    from paddle_tpu.serving.ragged import ragged_paged_attention
    entries, pages, kvh, rep, rows, slots, table, plan = \
        PAGED_GEOMETRIES[cell]
    q, kp, vp, args = paged_step(entries, pages, kvh, rep, rows, slots,
                                 table, plan)
    got = jax.jit(lambda q, kp, vp, tables, slot, pos, valid:
                  rp.paged_attention(
                      q, kp, vp, tables,
                      *rp.seq_meta(slot, pos, valid, tables.shape[0]),
                      rep=rep))(q, kp, vp, *args)
    want = jax.jit(functools.partial(ragged_paged_attention, rep=rep))(
        q, kp, vp, *args)
    return (rows, kvh * rep, 128, entries * pages, 16), {"out": (got, want)}


def timed_ms(fn, *args, n=20):
    """Milliseconds a call of the jitted ``fn``: ``n`` calls queued one
    behind the other, the last one waited for, on an idle device."""
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return round((time.perf_counter() - t0) / n * 1e3, 4)


# longcat560-serve-batch: 8 latent entries of 18,944 pages, 64 heads on one
# row of 640 (512 of latent, 64 of roped key, 64 of padding), 320 rows, 256
# slots, tables of 74 pages: 244 decodes over contexts of 40..1,012, a
# 50-row prompt chunk and a 26-row chunk that continues a prompt mid-page
LATENT_GEOMETRY = (8, 18944, 64, 640, 512, 320, 256, 74,
                   [(1, c) for c in range(40, 1180, 4)][:244]
                   + [(50, 50), (26, 150)])


def case_latent_paged_attention():
    """The latent path of the step's attention kernel against the
    gather-based reference, with the milliseconds a call."""
    from paddle_tpu.kernels import ragged_pallas as rp
    from paddle_tpu.serving.ragged import ragged_paged_attention
    entries, pages, heads, d, latent, rows, slots, table, plan = \
        LATENT_GEOMETRY
    q, kp, _, args = paged_step(entries, pages, 1, heads, rows, slots, table,
                                plan, d=d, pools=1)
    kw = dict(scale=192 ** -0.5, latent=latent)
    kernel = jax.jit(lambda q, kp, tables, slot, pos, valid:
                     rp.paged_attention(
                         q, kp, None, tables,
                         *rp.seq_meta(slot, pos, valid, tables.shape[0]),
                         rep=heads, **kw))
    got = kernel(q, kp, *args)
    want = jax.jit(lambda q, kp, *a: ragged_paged_attention(
        q, kp, None, *a, rep=heads, **kw))(q, kp, *args)
    live = sum(c for _, c in plan)
    jax.block_until_ready(want)
    return ((rows, heads, d, latent, entries * pages, 16),
            {"out": (got, want)},
            {"ms_a_call": timed_ms(kernel, q, kp, *args),
             "live_rows": live, "live_bytes": live * d * 2})


def grouped_run(kernel, e, tm, x, keys, *banks):
    """The grouped product of ``x``'s pairs by their experts ``keys`` over
    two banks (no gate) or three, the padding rows nought."""
    from paddle_tpu.kernels import grouped_experts_pallas as ge
    _, tile_group, n_live, row_pair, _ = ge.group_plan(keys, e, tm)
    xs = jnp.where((row_pair >= 0)[:, None], x[jnp.maximum(row_pair, 0)], 0)
    ys = ge.grouped_experts(xs, tile_group, n_live,
                            *(None,) * (3 - len(banks)), *banks,
                            kernel=kernel)
    return jnp.where((row_pair >= 0)[:, None], ys, 0)


def case_grouped_experts():
    """The held experts' grouped product at the cell's shapes (3,840 pairs
    of 320 rows x 12, 16 experts of 6144 x 2048) under uneven routing: one
    expert takes 300 pairs, one none, the rest 1 to 9; the pairs no expert
    here takes are the other 3,470."""
    from paddle_tpu.kernels import grouped_experts_pallas as ge
    e, h, f, pairs = 16, 6144, 2048, 320 * 12
    sizes = [300, 0, 1, 2, 3, 4, 5, 6, 7, 8, 9, 5, 5, 5, 5, 5]
    keys = np.full(pairs, e, np.int32)
    keys[:sum(sizes)] = np.repeat(np.arange(e), sizes)
    keys = jnp.asarray(np.random.default_rng(0).permutation(keys))
    tm = ge.TM
    x = rand(0, (pairs, h))
    banks = [jax.jit(rand, static_argnums=(0, 1, 2, 3))(
        k, s, jnp.bfloat16, 0.02) for k, s in
        ((1, (e, h, f)), (2, (e, h, f)), (3, (e, f, h)))]

    fast = jax.jit(functools.partial(grouped_run, True, e, tm))
    got = fast(x, keys, *banks)
    want = jax.jit(functools.partial(grouped_run, False, e, tm))(
        x, keys, *banks)
    jax.block_until_ready(want)
    even = jnp.asarray(np.random.default_rng(1).permutation(
        np.where(np.arange(pairs) < 64, np.arange(pairs) % e, e)
        .astype(np.int32)))
    return ((pairs, e, h, f, tm), {"out": (got, want)},
            {"ms_a_call_uneven": timed_ms(fast, x, keys, *banks),
             "ms_a_call_4_a_expert": timed_ms(fast, x, even, *banks),
             "touched_bytes_4_a_expert": e * 3 * h * f * 2})



def case_grouped_experts_two_banks():
    """The two-bank ``relu2`` product at nemotron120-serve-batch's shapes
    (7,040 pairs of 320 rows x 22, 128 held experts of 1024 x 2688, a width
    512 does not divide): every expert touched, 14 pairs each but one that
    takes 200; 5,062 pairs fall on experts held elsewhere."""
    from paddle_tpu.kernels import grouped_experts_pallas as ge
    e, h, f, pairs = 128, 1024, 2688, 320 * 22
    sizes = [200] + [14] * (e - 1)
    keys = np.full(pairs, e, np.int32)
    keys[:sum(sizes)] = np.repeat(np.arange(e), sizes)
    keys = jnp.asarray(np.random.default_rng(0).permutation(keys))
    tm = ge.TM
    x = rand(0, (pairs, h))
    banks = [jax.jit(rand, static_argnums=(0, 1, 2, 3))(
        k, s, jnp.bfloat16, 0.02) for k, s in
        ((1, (e, h, f)), (2, (e, f, h)))]

    fast = jax.jit(functools.partial(grouped_run, True, e, tm))
    got = fast(x, keys, *banks)
    want = jax.jit(functools.partial(grouped_run, False, e, tm))(
        x, keys, *banks)
    jax.block_until_ready(want)
    return ((pairs, e, h, f, tm, ge.width_block(h, f, 2, 2)),
            {"out": (got, want)},
            {"ms_a_call": timed_ms(fast, x, keys, *banks),
             "touched_bytes": e * 2 * h * f * 2})


def case_ssm_scan():
    """The Mamba-2 state update at nemotron120-serve-batch's shapes but 2
    layers of its 5 (the oracle keeps a second pool): 192 slots of 128
    heads x 64 x 128 float32, 320 rows: 188
    sequences that decode one row each, a 100-row chunk that begins a
    prompt (from nothing, whatever its slot holds) and a 28-row chunk that
    continues one; two slots unscheduled, which must come back bit for
    bit. The milliseconds are one layer's call on a pool the call keeps."""
    from paddle_tpu.kernels import ssm_pallas as ssm
    layers, slots, heads, groups, p, n, rows = 2, 192, 128, 8, 64, 128, 320
    plan = [(1, 40 + 3 * i) for i in range(188)] + [(100, 100), (28, 150)]
    slot_ids, positions = [], []
    free = list(np.random.default_rng(0).permutation(slots))
    for count, ctx in plan:
        s = int(free.pop())
        slot_ids += [s] * count
        positions += list(range(ctx - count, ctx))
    valid = np.arange(rows) < len(slot_ids)
    pad = rows - len(slot_ids)
    slot_ids = jnp.asarray(slot_ids + [0] * pad, jnp.int32)
    positions = jnp.asarray(positions + [0] * pad, jnp.int32)
    f32 = jnp.float32
    pool = jax.jit(lambda k: jax.random.normal(
        k, (layers, slots) + ssm.pool_shape(heads, groups, p, n), f32))(
        jax.random.PRNGKey(0))
    x = rand(1, (rows, heads, p))
    b, c = rand(2, (rows, groups, n)), rand(3, (rows, groups, n))
    dt = jax.random.uniform(jax.random.PRNGKey(4), (rows, heads), f32,
                            0.05, 1.5)
    decay = jnp.exp(-dt * 2.0)

    def run(kernel, pool, layer):
        meta = ssm.scan_meta(slot_ids, positions, jnp.asarray(valid), slots)
        return ssm.ssm_scan(pool, layer, x, b, c, dt, decay, meta,
                            kernel=kernel)

    fast = jax.jit(functools.partial(run, True))
    y, new = fast(pool, 1)
    y_want, want = jax.jit(functools.partial(run, False))(pool, 1)
    idle = jnp.asarray([int(s) for s in free], jnp.int32)
    kept = bool(jnp.array_equal(new[1, idle], pool[1, idle])
                & jnp.array_equal(new[0], pool[0]))
    moved = 2 * len(plan) * heads * p * n * 4
    ms = timed_ms(lambda pool: fast(pool, 1)[1], pool)
    return ((layers, slots, heads, p, n, rows), {"y": (y, y_want),
                                                 "state": (new[1], want[1])},
            {"ms_a_layer": ms, "state_bytes_moved": moved,
             "share_of_819_GB_s": round(moved / 819e9 / (ms / 1e3), 4),
             "unscheduled_slots_bit_for_bit": kept})


CASES = (
    ("flash_attention", "F.scaled_dot_product_attention on a TPU",
     case_flash_attention, REL_L2),
    ("flashmask", "attn_startend_row_indices", case_flashmask, REL_L2),
    ("fused_rope", "FLAGS_use_pallas_fused", case_fused_rope, REL_L2),
    ("fused_rms_norm", "FLAGS_use_pallas_fused", case_fused_rms_norm,
     REL_L2),
    ("fused_adamw", "FLAGS_use_pallas_fused", case_fused_adamw, REL_L2_F32),
    ("gmm", "MoE dropless=True", case_gmm, REL_L2),
    ("paged_attention/decode", "ServingEngine on one chip",
     functools.partial(case_paged_attention, "decode"), REL_L2),
    ("paged_attention/chat", "ServingEngine on one chip",
     functools.partial(case_paged_attention, "chat"), REL_L2),
    ("paged_attention/looped", "ServingEngine on one chip",
     functools.partial(case_paged_attention, "looped"), REL_L2),
    ("latent_paged_attention", "ServingEngine on one chip, a latent cache",
     case_latent_paged_attention, REL_L2),
    ("grouped_experts", "ServingEngine on one chip, an expert layer",
     case_grouped_experts, REL_L2),
    ("grouped_experts_two_banks", "ServingEngine on one chip, relu2 experts "
     "in a latent width", case_grouped_experts_two_banks, REL_L2),
    ("ssm_scan", "ServingEngine on one chip, a recurrent state beside the "
     "pages", case_ssm_scan, REL_L2_F32),
)


def main() -> int:
    device = chip.require_tpu()
    chip.enable_compile_cache()
    print(json.dumps({"device": device}), flush=True)
    rows = []
    only = tuple(sys.argv[1:])
    for name, reached_by, fn, tol in CASES:
        if only and not name.startswith(only):
            continue
        row = {"kernel": name, "reached_by": reached_by}
        t0 = time.perf_counter()
        try:
            shape, pairs, *extra = fn()
            row.update(*extra)
            errs = {k: round(rel_l2(a, b), 6) for k, (a, b) in pairs.items()}
            row.update(shape=shape, rel_l2=errs, tolerance=tol)
            bad = {k: e for k, e in errs.items() if not e <= tol}
            row["verdict"] = ("compiles and matches" if not bad else
                              f"compiles, WRONG: {bad}")
        except Exception as e:  # noqa: BLE001 — the refusal IS the result
            traceback.print_exc()
            row["verdict"] = f"{type(e).__name__}: {e}"[:1500]
        row["seconds"] = round(time.perf_counter() - t0, 1)
        rows.append(row)
        print(json.dumps(row), flush=True)
    ok = all(r["verdict"] == "compiles and matches" for r in rows)
    out_dir = os.path.join(REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "kernel_check.json"), "w") as f:
        json.dump({"device": device, "kernels": rows}, f, indent=1)
    print(json.dumps({"ok": ok, "device": device,
                      "kernels": {r["kernel"]: r["verdict"][:200]
                                  for r in rows}}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
