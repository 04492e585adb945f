"""The engine step against the chip's peaks, and the device's idle share, over
the steps the trace KEPT. The profiler's export keeps the earliest million
events (PERF.md section 7), so a slice may be cut: this reader counts the
executions of the step program (the metric file's ``module``) that the trace
holds, takes what the first that many steps of the slice needed, and divides
by their own device time; nothing is divided by the slice's length.

``bound`` is ``flops`` (operations needed over the peak rate), ``hbm`` (bytes
needed over the peak bandwidth) or ``idle``. The idle share reads TWO parts of
one run: the device's busy time a step from the traced slice's kept
operations (the profiler leaves it as it is), over the step's period from the
run's QUIET part, before the profiler started: the metric file's ``period``,
a host-clock field of what the kind measured (the mean gap between a
sequence's tokens, which is one step). The slice's own length is not used:
the profiler slows this cell's steps to half speed (PERF.md section 7), and
busy time over the slice measured the profiler. What a step needs, whatever
implements it: its rows through the dense path and the sampled rows through
the head; the (token, expert) pairs that fell on held experts through one
expert each and the weights of the held experts touched, once, both from the
counters on ``serve.emit`` where the program has them (the adapter's even
share and every held expert where it has not); absorbed attention over each
row's live context; every other weight once; the live latent rows of the
scheduled sequences once and the new rows once. An adapter without an expert
layer (no ``expert_params``): reads nothing.
"""
from bench.lib import spans as S, trace as T


def kept(ctx, module):
    """(the kept executions' device seconds, the steps they ran) or None."""
    t, m = ctx["trace"], ctx["measured"]
    first, last = m.get("slice", (None, None))
    if first is None or last is None or last <= first:
        return None
    runs = [d for _, d, n in t["modules"] if n.startswith(module)]
    steps = m["steps"][first:last][:len(runs)]
    if not steps:
        return None
    return sum(runs[:len(steps)]) / 1e6, steps


def expert_counts(ctx, steps):
    """(pairs on held experts, held experts touched) over ``steps`` steps,
    each summed over the layers: the counters' mean a step where the spans
    carry them."""
    spans = S.of_run(ctx)
    emits = [s for s in S.named(spans, "serve.emit")
             if "moe_pairs_held" in s[3]] if spans else []
    if emits:
        has = {"spans": emits}
        return tuple(S.argument(has, "serve.emit", [k]) / len(emits) * steps
                     for k in ("moe_pairs_held", "moe_experts_touched"))
    return None


def read(ctx, name):
    spec = ctx["cell"].metric_file(name)
    arch, cfg = ctx["arch"], ctx["cell"].config
    if not hasattr(arch, "expert_params"):
        return None
    got = kept(ctx, spec["module"])
    if got is None:
        return None
    seconds, steps = got
    if spec["bound"] == "idle":
        period = ctx["measured"].get(spec["period"])
        if not period:
            return None
        busy = T.busy_us(ctx["trace"]["ops"]) / 1e6 / len(steps)
        return 100.0 * (1.0 - busy / period)
    tokens = sum(s[2] for s in steps)
    layers = cfg["num_layers"]
    counts = expert_counts(ctx, len(steps))
    if counts is None:
        counts = (tokens * layers * cfg["moe_topk"] * arch.held_share(cfg),
                  len(steps) * layers * cfg["n_routed_experts"])
    pairs_held, touched = counts
    dense = layers * arch.dense_layer_params(cfg)
    if spec["bound"] == "flops":
        need = 2.0 * dense * tokens \
            + 2.0 * arch.head_params(cfg) * sum(s[3] for s in steps) \
            + 2.0 * arch.expert_params(cfg) * pairs_held \
            + sum(arch.attention_flops(cfg, s[4]) for s in steps)
        peak = ctx["peaks"]["bf16_flops_per_s"]
    else:
        kv = arch.kv_bytes_per_token(cfg)
        need = 2.0 * (dense + arch.head_params(cfg)) * len(steps) \
            + 2.0 * arch.expert_params(cfg) * touched \
            + kv * sum(s[5] for s in steps) + kv * tokens
        peak = ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * need / peak / seconds
