#!/usr/bin/env python
"""What does a live page-table slot cost the serving attention kernels, and
which part of it, on this chip?

One process on a TPU. For each of the four serving cells' geometries
(``kernel_check.py``'s: ``decode``, ``chat``, ``looped``, ``batch``, the
last the latent kernel) a ladder of rungs, a JSON line each:

``cell``      the whole kernel at the cell's own live slots and contexts;
``-products`` the same with the products (and the masks only they read)
              left out: copies and bookkeeping alone;
``-copies``   with no page copy started or waited for: the products on
              whatever the buffer holds;
``-both``     neither: the grid, the scalar operands, the page loops, the
              q and result blocks;
``ring=N``    the whole kernel with N K/V blocks in VMEM, N - 1 of them on
              their way while one is computed (2 is a double buffer);
``slots=N``   N live slots of the cell's pages a slot, the rest empty;
``pages=N``   every slot live with N pages.

A rung leaves a part out by handing ``ragged_pallas._walk`` a stand-in for
the kernels' ``copies`` or ``products``, and sets ``ragged_pallas.RING``; on
a tree without that walk only the whole-kernel rungs run, through public
names, so a copy of this file times an older tree. A timing is ONE jitted program of ``--calls`` kernel
calls, each one's queries waiting for an element of the one before (the
step program's layers do the same), ended by a host fetch of one element;
the least of ``--reps``, in microseconds a call and a live slot. Everything
also goes to ``chiprun_out/paged_ladder.json``.

    chiprun -- python tools/paged_ladder.py [decode chat looped batch]
        [--calls 100] [--reps 3] [--rungs cell ring ...]
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [REPO, os.path.join(REPO, "tools")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import kernel_check as kc  # noqa: E402
from paddle_tpu.kernels import ragged_pallas as rp  # noqa: E402
from paddle_tpu.utils import chip  # noqa: E402

# a cell's geometry with the live slots and pages a slot its traffic holds
# (`PERF.md` section 4): (geometry, slots live, pages a live slot, further
# chunks of (rows, context) beside the decode rows)
CELLS = {
    "decode": (kc.PAGED_GEOMETRIES["decode"], 16, 8, []),
    # an eighth of capacity: two decodes and a 90-row chunk of a prompt
    "chat": (kc.PAGED_GEOMETRIES["chat"], 2, 20, [(90, 400)]),
    "looped": (kc.PAGED_GEOMETRIES["looped"], 16, 8, []),
    "batch": (kc.LATENT_GEOMETRY, 253, 32, [(35, 35), (16, 120), (13, 90)]),
}
SLOT_RUNGS = (1, 4, 16, 64, 256)
PAGE_RUNGS = (1, 8, 16, 32)
RING_RUNGS = (2, 3, 4)


def plan_of(live, pages, chunks=()):
    """``live`` decode rows whose contexts end three tokens short of
    ``pages`` pages of 16, then the chunks."""
    return [(1, pages * 16 - 3)] * live + list(chunks)


def operands(cell):
    """(kernel call taking q first, (q, pools), what ``packed_plan`` needs)
    at the cell's geometry; the pools are drawn once a cell."""
    geometry = CELLS[cell][0]
    if cell == "batch":
        entries, pages, heads, d, latent, rows, slots, table, _ = geometry
        q, *pools, _ = kc.paged_step(entries, pages, 1, heads, rows, slots,
                                     table, [], d=d, pools=1)
        kw = dict(rep=heads, scale=192 ** -0.5, latent=latent)
    else:
        entries, pages, kvh, rep, rows, slots, table, _ = geometry
        q, *pools, _ = kc.paged_step(entries, pages, kvh, rep, rows, slots,
                                     table, [])
        kw = dict(rep=rep)
    return (lambda q, kp, vp, tables, *meta: rp.paged_attention(
        q, kp, vp, tables, *meta, **kw)), (q, *pools), \
        (entries, pages, rows, slots, table)


def planned(shape, plan):
    """The page tables and ``seq_meta`` of ``plan``, slot 0 live too."""
    tables, slot, pos, valid = kc.packed_plan(*shape, plan, first_slot=0)
    return (tables, *jax.jit(rp.seq_meta, static_argnums=3)(
        slot, pos, valid, tables.shape[0]))


@contextlib.contextmanager
def traced(parts=(), ring=None):
    """The kernels traced with a stand-in for each of ``parts`` and, if
    given, a ring of ``ring`` K/V blocks."""
    walk, blocks = rp._walk, rp.RING

    def walk_without(*a, copies, products, **kw):
        if "copies" in parts:
            copies = lambda *_: []                          # noqa: E731
        if "products" in parts:
            products = lambda *_: None                      # noqa: E731
        return walk(*a, copies=copies, products=products, **kw)

    rp._walk, rp.RING = walk_without, ring or blocks
    jax.clear_caches()
    try:
        yield
    finally:
        rp._walk, rp.RING = walk, blocks
        jax.clear_caches()


def chained(call, calls):
    """``calls`` kernel calls in one program, each waiting for the last."""
    def run(q, *rest):
        def body(_, carry):
            q, _ = carry
            out = call(q, *rest)
            seam = (q[:1, :1, :1] + out[:1, :1, :1] * 1e-9).astype(q.dtype)
            return jax.lax.dynamic_update_slice(q, seam, (0, 0, 0)), out
        out = jax.eval_shape(call, q, *rest)
        return jax.lax.fori_loop(
            0, calls, body, (q, jnp.zeros(out.shape, out.dtype)))[1]
    return jax.jit(run)


def us_a_call(fn, args, calls, reps):
    def once():
        t0 = time.perf_counter()
        # host fetch of ONE element: the whole result would time the transfer
        np.asarray(fn(*args).ravel()[:1])
        return (time.perf_counter() - t0) / calls * 1e6
    once()                                                  # compiles
    return min(once() for _ in range(reps))


def ladder(cell, calls, reps, only, emit):
    _, live, pages, chunks = CELLS[cell]
    call, arrays, shape = operands(cell)
    slots = shape[3]
    own = plan_of(live, pages, chunks)
    rungs = [("cell", own, {})]
    if hasattr(rp, "_walk"):
        rungs += [("-products", own, dict(parts=("products",))),
                  ("-copies", own, dict(parts=("copies",))),
                  ("-both", own, dict(parts=("copies", "products")))]
        rungs += [(f"ring={n}", own, dict(ring=n))
                  for n in RING_RUNGS if n != rp.RING]
    rungs += [(f"slots={n}", plan_of(n, pages), {})
              for n in SLOT_RUNGS if n <= slots]
    rungs += [(f"pages={n}", plan_of(slots, n), {}) for n in PAGE_RUNGS]
    for name, plan, how in rungs:
        if only and not name.startswith(only):
            continue
        with traced(**how) if how else contextlib.nullcontext():
            us = us_a_call(chained(call, calls),
                           (*arrays, *planned(shape, plan)), calls, reps)
        emit({"cell": cell, "rung": name, "live_slots": len(plan),
              "tiles": sum(1 if n == 1 else -(-n // rp.TQ) for n, _ in plan),
              "pages": sum(-(-c // 16) for _, c in plan),
              "us_a_call": round(us, 2),
              "us_a_live_slot": round(us / len(plan), 3)})


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("cells", nargs="*", default=list(CELLS))
    ap.add_argument("--calls", type=int, default=100)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--rungs", nargs="*", default=[],
                    help="prefixes of the rungs' names to keep (all)")
    a = ap.parse_args()
    device = chip.require_tpu()
    chip.enable_compile_cache()
    rows = []

    def emit(row):
        rows.append(row)
        print(json.dumps(row), flush=True)

    emit({"device": device, "calls": a.calls})
    for cell in a.cells:
        ladder(cell, a.calls, a.reps, tuple(a.rungs), emit)
    out_dir = os.path.join(REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "paged_ladder.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
