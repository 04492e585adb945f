"""The latent attention kernel against its roofline: the live latent rows of
the scheduled sequences, read once a call (the adapter's
``kv_bytes_per_token`` over the summed live context: every block's row, as
stored without its padding), and absorbed attention's operations over each
row's live context (``attention_flops``), the larger of bytes over the peak
bandwidth and operations over the peak rate, over the device time of the
kernel the metric file names, over the steps the trace kept
(``bench.lib.trace.kept``). No such kernel in the trace: reads nothing."""
from bench.lib import spans as S, trace as T


def read(ctx, name):
    spec = ctx["cell"].metric_file(name)
    arch, cfg = ctx["arch"], ctx["cell"].config
    t = S.of_run(ctx)
    if not t:
        return None
    us = S.time_in(t, [spec["kernel"]])
    got = T.kept(ctx, spec["module"])
    runs = sum(1 for _, _, n in ctx["trace"]["modules"]
               if n.startswith(spec["module"]))
    if us <= 0 or got is None or not runs:
        return None
    steps = got["steps"]
    seconds = us / 1e6 * len(steps) / runs
    moved = arch.kv_bytes_per_token(cfg) * sum(s[5] for s in steps) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    compute = sum(arch.attention_flops(cfg, s[4]) for s in steps) \
        / ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * max(moved, compute) / seconds
