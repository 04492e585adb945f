"""The recurrence's state update against its roofline: the scheduled
sequences' states read once and written once (``state_slots`` x the adapter's
``state_bytes_per_sequence`` without the convolution's tails) and what the
update reads and writes a row beside them (the step's rows x
``scan_row_bytes``), over the peak bandwidth; the rows' operations
(``scan_flops_per_row``) over the peak rate; the larger of the two, over the
device time under the metric file's ``scope`` (the state update, whatever
implements it). Counters (the ``serve.run`` spans that carry ``state_slots``)
and time (the executions of ``module`` the trace kept) are each taken a
step, so a trace cut between the two lines does not skew the ratio. No
counter, no time under the scope, an adapter without a state: reads
nothing."""
from bench.lib import spans as S

ROWS = ["prefill_tokens", "decode_tokens"]


def read(ctx, name):
    spec = ctx["cell"].metric_file(name)
    arch, cfg = ctx["arch"], ctx["cell"].config
    t = S.of_run(ctx)
    if not t or not hasattr(arch, "state_bytes_per_sequence"):
        return None
    runs = {"spans": [s for s in S.named(t, "serve.run")
                      if {"state_slots", *ROWS} <= set(s[3])]}
    executions = sum(1 for _, _, n in ctx["trace"]["modules"]
                     if n.startswith(spec["module"]))
    us = S.time_in(t, [spec["scope"]])
    if not runs["spans"] or not executions or us <= 0:
        return None
    n = len(runs["spans"])
    slots = S.argument(runs, "serve.run", ["state_slots"]) / n
    rows = S.argument(runs, "serve.run", ROWS) / n
    moved = 2.0 * slots * arch.state_bytes_per_sequence(cfg, tails=False) \
        + rows * arch.scan_row_bytes(cfg)
    seconds = moved / ctx["peaks"]["hbm_bytes_per_s"]
    compute = rows * arch.scan_flops_per_row(cfg) \
        / ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * max(seconds, compute) / (us / 1e6 / executions)
