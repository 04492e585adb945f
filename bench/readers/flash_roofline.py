"""The flash kernels' share of their roofline over the traced steps: the
operations forward, dq and dkv need (compute-bound at these shapes: 0.35
FLOP/byte per key, 717 at 2,048 keys), over the peak, over the summed device
time of every Pallas call of the step (the forward recomputed under remat is
time, not work). No Pallas call in the trace: reads nothing."""
from bench.lib import flops, trace as T


def read(ctx, name):
    t, cell, m = ctx["trace"], ctx["cell"], ctx["measured"]
    us = T.time_where(t["ops"], T.is_pallas)
    steps = m.get("traced_steps")
    if us <= 0 or not steps:
        return None
    cfg, tr = cell.config, cell.traffic
    g = ctx["arch"].attention_geometry(cfg)
    need = steps * g["layers"] * flops.flash_flops(
        tr["batch"], g["heads"], tr["seq"], g["head_dim"]) / cell.chips
    return 100.0 * need / ctx["peaks"]["bf16_flops_per_s"] / (us / 1e6)
