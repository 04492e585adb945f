"""Time the Mamba-2 state update (``kernels/ssm_pallas.ssm_scan``) alone on
the chip, at nemotron120-serve-batch's shapes: one layer's call on a donated
pool, in milliseconds and as a share of the 819 GB/s the scheduled states'
read and write would take, by mix of rows (decode rows alone, with a prefill
chunk, a chunk alone) and by the state block a grid step moves
(``BLOCK_BYTES``).

    chiprun -- python tools/ssm_ladder.py [block KiB ...]
"""
from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402
import numpy as np              # noqa: E402

from paddle_tpu.kernels import ssm_pallas as ssm   # noqa: E402
from paddle_tpu.utils import chip                  # noqa: E402

LAYERS, SLOTS, HEADS, GROUPS, P, N, ROWS = 2, 192, 128, 8, 64, 128, 320
MIXES = {"decode192": [(1, 40 + 3 * i) for i in range(192)],
         "decode188+chunks": [(1, 40 + 3 * i) for i in range(188)]
         + [(100, 100), (28, 150)],
         "decode96": [(1, 40 + 3 * i) for i in range(96)],
         "chunk128": [(128, 128)]}


def rows_of(plan):
    slot_ids, positions = [], []
    for s, (count, ctx) in enumerate(plan):
        slot_ids += [s] * count
        positions += list(range(ctx - count, ctx))
    pad = ROWS - len(slot_ids)
    valid = np.arange(ROWS) < len(slot_ids)
    return (jnp.asarray(slot_ids + [0] * pad, jnp.int32),
            jnp.asarray(positions + [0] * pad, jnp.int32), jnp.asarray(valid))


def main():
    device = chip.require_tpu()
    chip.enable_compile_cache()
    print(json.dumps({"device": device}), flush=True)
    f32 = jnp.float32
    key = jax.random.PRNGKey
    x = jax.random.normal(key(1), (ROWS, HEADS, P), jnp.bfloat16)
    b = jax.random.normal(key(2), (ROWS, GROUPS, N), jnp.bfloat16)
    c = jax.random.normal(key(3), (ROWS, GROUPS, N), jnp.bfloat16)
    dt = jax.random.uniform(key(4), (ROWS, HEADS), f32, 0.05, 1.5)
    decay = jnp.exp(-dt * 2.0)
    blocks = [int(a) << 10 for a in sys.argv[1:]] or [ssm.BLOCK_BYTES]
    for block in blocks:
        ssm.BLOCK_BYTES = block
        for name, plan in MIXES.items():
            slot_ids, positions, valid = rows_of(plan)

            def run(pool):
                meta = ssm.scan_meta(slot_ids, positions, valid, SLOTS)
                y, pool = ssm.ssm_scan(pool, 1, x, b, c, dt, decay, meta)
                return pool, y.sum()

            step = jax.jit(run, donate_argnums=0)
            pool = jnp.zeros((LAYERS, SLOTS)
                             + ssm.pool_shape(HEADS, GROUPS, P, N), f32)
            pool, _ = step(pool)
            jax.block_until_ready(pool)
            n = 20
            t0 = time.perf_counter()
            for _ in range(n):
                pool, s = step(pool)
            jax.block_until_ready(pool)
            ms = (time.perf_counter() - t0) / n * 1e3
            moved = 2 * len(plan) * HEADS * P * N * 4
            print(json.dumps({
                "block_KiB": block >> 10, "mix": name,
                "rows": int(valid.sum()), "sequences": len(plan),
                "ms_a_layer": round(ms, 4),
                "share_of_819_GB_s": round(moved / 819e9 / (ms / 1e3), 4)}),
                flush=True)
            del pool


if __name__ == "__main__":
    main()
