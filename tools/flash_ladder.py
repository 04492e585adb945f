#!/usr/bin/env python
"""How long does each flash kernel take at each tile size, on this chip?

One process on a TPU. Two parts, each a JSON line per timing:

``defaults``  ``flash_attention`` and ``flashmask_attention`` (packed
  documents of 256 tokens, ``kernel_check.py``'s case), forward and
  forward + backward, at the tiles the call resolves for itself. Uses the
  public ops only, so the same file times an older tree.

``ladder``  the three kernels ``flash_fwd``, ``flash_dq``, ``flash_dkv``
  each alone at each rung ``BQxBK`` given on the command line (default: the
  rungs of PERF.md, PR 32), with the grid steps of the call. A rung the
  compiler refuses prints its words and the ladder goes on.

A timing is a jitted call repeated ``--reps`` times and ended by a host
fetch, after one warm-up call; milliseconds a call. Everything also goes to
``chiprun_out/flash_ladder.json``.

    chiprun -- python tools/flash_ladder.py [--shape 8 16 2048 128]
        [--part defaults|ladder|both] [--reps 10] [512x512 ...]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from paddle_tpu.kernels import flash_pallas as fp  # noqa: E402
from paddle_tpu.utils import chip  # noqa: E402

RUNGS = ("128x128", "256x256", "512x512", "512x1024", "1024x512",
         "1024x1024", "2048x512", "512x2048")


def ms_a_call(fn, args, reps):
    np.asarray(jax.tree_util.tree_leaves(fn(*args))[0].ravel()[:1])  # warm
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    # host fetch of ONE element: the whole result would time the transfer
    np.asarray(jax.tree_util.tree_leaves(out)[0].ravel()[:1])
    return (time.perf_counter() - t0) / reps * 1e3


def rand(key, shape, dtype):
    return jax.random.normal(jax.random.PRNGKey(key), shape).astype(dtype)


def doc_bounds(b, h, s, doc):
    j = jnp.arange(s)
    lts = ((j // doc + 1) * doc).astype(jnp.int32)
    zero = jnp.zeros((s,), jnp.int32)
    return jnp.broadcast_to(
        jnp.stack([lts, jnp.full((s,), s, jnp.int32), zero, zero],
                  -1)[None, None], (b, h, s, 4))


def tiles_of(q, k):
    """The tiles the call sizes for itself; nothing on a tree without
    ``call_tiles`` (128 x 128 there)."""
    if not hasattr(fp, "call_tiles"):
        return {}
    return {"tiles": {n: list(t) for n, t in fp.call_tiles(q, k).items()}}


def part_defaults(shape, dtype, reps, emit):
    b, h, s, d = shape
    mask_shape = (2, h, s, d)       # kernel_check.py's flashmask case
    bounds = doc_bounds(*mask_shape[:3], 256)
    ops = {
        "flash_attention":
            (shape, lambda q, k, v: fp.flash_attention(q, k, v, True)),
        "flashmask_attention":
            (mask_shape,
             lambda q, k, v: fp.flashmask_attention(q, k, v, bounds, True)),
    }
    for name, (shape, op) in ops.items():
        q, k, v, g = (rand(i, shape, dtype) for i in range(4))

        def fwd_bwd(q, k, v, g, op=op):
            out, vjp = jax.vjp(op, q, k, v)
            return (out, *vjp(g))
        emit({"op": name, "shape": list(shape), **tiles_of(q, k),
              "fwd_ms": ms_a_call(jax.jit(op), (q, k, v), reps),
              "fwd_bwd_ms": ms_a_call(jax.jit(fwd_bwd), (q, k, v, g), reps)})


def part_ladder(shape, dtype, rungs, reps, emit):
    b, h, s, d = shape
    bh = b * h
    scale = d ** -0.5
    q, k, v, g = (rand(i, shape, dtype) for i in range(4))
    out, lse = jax.jit(lambda q, k, v: fp._flash_forward(
        q, k, v, True, None, 512, 512))(q, k, v)
    rows = tuple(t.reshape(bh, s, d) for t in (q, k, v, g))
    delta = jnp.broadcast_to(
        jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                -1).reshape(bh, s)[:, :, None], (bh, s, fp.LANES))
    for rung in rungs:
        bq, bk = (int(n) for n in rung.split("x"))
        calls = {
            "fwd": (lambda q, k, v: fp._flash_forward(
                q, k, v, True, None, bq, bk)[0], (q, k, v)),
            "dq": (lambda *a: fp._flash_dq(
                *a, None, True, scale, None, bq, bk), (*rows, lse, delta)),
            "dkv": (lambda *a: fp._flash_dkv(
                *a, None, True, scale, None, bq, bk), (*rows, lse, delta)),
        }
        for kernel, (fn, args) in calls.items():
            row = {"kernel": kernel, "tiles": [bq, bk],
                   "grid_steps": bh * (s // bq) * (s // bk),
                   "vmem_bytes": fp.tile_vmem_bytes(
                       kernel, bq, bk, d, jnp.dtype(dtype).itemsize)}
            try:
                row["ms"] = ms_a_call(jax.jit(fn), args, reps)
            except Exception as e:  # noqa: BLE001 — the refusal is the result
                row["refused"] = f"{type(e).__name__}: {e}"[:600]
            emit(row)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("rungs", nargs="*", default=list(RUNGS))
    ap.add_argument("--shape", nargs=4, type=int,
                    default=[8, 16, 2048, 128])
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--part", default="both",
                    choices=("defaults", "ladder", "both"))
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--vmem-limit-mib", type=int,
                    help="Mosaic's scoped VMEM limit for the ladder's calls "
                    "(default: the kernels' own)")
    a = ap.parse_args()
    if a.vmem_limit_mib:
        fp.VMEM_LIMIT_BYTES = a.vmem_limit_mib * 2 ** 20
    device = chip.require_tpu()
    chip.enable_compile_cache()
    rows = []

    def emit(row):
        rows.append(row)
        print(json.dumps(row), flush=True)

    emit({"device": device,
          "vmem_limit_bytes": getattr(fp, "VMEM_LIMIT_BYTES", None)})
    shape, dtype = tuple(a.shape), jnp.dtype(a.dtype)
    if a.part in ("defaults", "both"):
        part_defaults(shape, dtype, a.reps, emit)
    if a.part in ("ladder", "both"):
        part_ladder(shape, dtype, a.rungs, a.reps, emit)
    out_dir = os.path.join(REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "flash_ladder.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
