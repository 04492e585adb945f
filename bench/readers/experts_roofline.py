"""The held experts' grouped product against its roofline: the weights of the
held experts touched, read once (``moe_experts_touched`` x an expert's three
matrices), and the operations of the pairs that fell on them
(``moe_pairs_held`` x 2 x an expert's parameters), both from the counters the
program's ``serve.emit`` spans carry, the larger of bytes over the peak
bandwidth and operations over the peak rate, over the device time under the
metric file's ``scope`` (the grouped product and the weighted gather back,
whatever implements them). Counters and time are each taken a step (the
spans that carry the counters; the executions of ``module`` the trace kept)
so a trace cut between the two lines does not skew the ratio. No counter, no
time under the scope: reads nothing."""
from bench.lib import spans as S


def read(ctx, name):
    spec = ctx["cell"].metric_file(name)
    arch, cfg = ctx["arch"], ctx["cell"].config
    t = S.of_run(ctx)
    if not t or not hasattr(arch, "expert_params"):
        return None
    emits = {"spans": [s for s in S.named(t, "serve.emit")
                       if {"moe_pairs_held", "moe_experts_touched"} <= set(s[3])]}
    runs = sum(1 for _, _, n in ctx["trace"]["modules"]
               if n.startswith(spec["module"]))
    us = S.time_in(t, [spec["scope"]])
    if not emits["spans"] or not runs or us <= 0:
        return None
    n = len(emits["spans"])
    expert = arch.expert_params(cfg)
    seconds = 2.0 * expert * S.argument(emits, "serve.emit", ["moe_experts_touched"]) \
        / n / ctx["peaks"]["hbm_bytes_per_s"]
    compute = 2.0 * expert * S.argument(emits, "serve.emit", ["moe_pairs_held"]) \
        / n / ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * max(seconds, compute) / (us / 1e6 / runs)
