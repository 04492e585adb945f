#!/usr/bin/env python
"""What does one layer visit's page write cost, and which part of it, on
this chip?

One process on a TPU. For the serving cells' page-write geometries
(``kernel_check.py``'s ``kv_write`` cases: ``decode``, ``looped``,
``latent``) a ladder of rungs, a JSON line each, in microseconds a visit:

``launch``   the page kernel with a plan of no pages: the launch, the
             scalar plan and the copy of the step's rows into VMEM;
``copies``   the cell's pages, each read into VMEM and written back, with
             no row merged into any (every page's hits left out);
``merge``    the whole write at the cell's step: the copies and the merge;
``pages=N``  the whole write of N rows on N distinct pages;
``xla``      XLA's write (``generation._write_pages``) of the cell's step,
             in its ``page_windows`` windows.

A rung differs from the whole write only in the plan handed to the kernel
(``kv_write_pallas.WritePlan``), so it times the kernel the program runs.
A timing is ONE jitted program of ``--visits`` writes on the cell's whole
pools, donated, each into the next cache entry as a step's layers make
them; the least of ``--reps``. Everything also goes to
``chiprun_out/kv_write_ladder.json``.

    python tools/kv_write_ladder.py [decode looped latent]
        [--visits 192] [--reps 3]          # on a host with one TPU chip
"""
from __future__ import annotations

import argparse
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
from paddle_tpu.kernels import kv_write_pallas as kw  # noqa: E402
from paddle_tpu.utils import chip  # noqa: E402

PAGE_RUNGS = (1, 4, 16, 64)


def spread_rows(n, rows, pages):
    """``n`` rows on ``n`` distinct pages of an entry, slot 0 of each, the
    rest of ``rows`` writing nothing: (pages [rows], offs [rows])."""
    at = np.full(rows, pages, np.int32)
    at[:n] = np.random.default_rng(n).permutation(pages)[:n]
    return jnp.asarray(at), jnp.zeros(rows, jnp.int32)


def timed_visits(pools, news, how, entries, visits, reps):
    """(the pools handed back, the least microseconds a visit): ``how(e)``
    gives the write into entry ``e``."""
    loop = jax.jit(lambda ps: jax.lax.fori_loop(
        0, visits, lambda i, ps: kc.kv_write(ps, news, how(i % entries)),
        ps), donate_argnums=0)
    pools = loop(pools)                                     # compiles
    jax.block_until_ready(pools)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        pools = loop(pools)
        jax.block_until_ready(pools)
        best = min(best, time.perf_counter() - t0)
    return pools, round(best / visits * 1e6, 2)


def ladder(cell, visits, reps, emit):
    pools, news, at, offs, entries, pages, windows = kc.kv_write_step(cell)
    rows = at.shape[0]
    step = kw.plan(at, offs, pages)
    t = step.starts.shape[0] - 1

    def kernel(write_plan):
        return lambda e: kw.at_entry(write_plan, e * pages)

    rungs = [
        ("launch", int(step.count[0]),
         kernel(step._replace(count=jnp.zeros((1,), jnp.int32)))),
        ("copies", int(step.count[0]),
         kernel(step._replace(starts=jnp.zeros((t + 1,), jnp.int32)))),
        ("merge", int(step.count[0]), kernel(step)),
    ]
    for n in PAGE_RUNGS:
        if n <= rows:
            rungs.append((f"pages={n}", n,
                          kernel(kw.plan(*spread_rows(n, rows, pages),
                                         pages))))
    rungs.append(("xla", int(windows), lambda e: kc.xla_scatter(
        at, offs, windows, entries, pages, e)))
    for name, moved, how in rungs:
        pools, us = timed_visits(pools, news, how, entries, visits, reps)
        emit({"cell": cell, "rung": name, "rows": rows, "pages": moved,
              "page_bytes": int(np.prod(pools[0].shape[1:]))
              * pools[0].dtype.itemsize * len(pools),
              "us_a_visit": us})


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("cells", nargs="*", default=["decode", "looped", "latent"])
    ap.add_argument("--visits", type=int, default=192)
    ap.add_argument("--reps", type=int, default=3)
    a = ap.parse_args()
    device = chip.require_tpu()
    chip.enable_compile_cache()
    rows = []

    def emit(row):
        rows.append(row)
        print(json.dumps(row), flush=True)

    emit({"device": device, "visits": a.visits})
    for cell in a.cells:
        ladder(cell, a.visits, a.reps, emit)
    out_dir = os.path.join(REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "kv_write_ladder.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
