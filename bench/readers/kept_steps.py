"""The engine step of a model with an expert layer against the chip's peaks,
and the device's idle share, over the steps the trace KEPT
(``bench.lib.trace.kept``: the executions of the metric file's ``module`` the
trace holds and the first that many steps of the slice; nothing is divided by
the slice's length).

``bound`` is ``flops`` (operations needed over the peak rate), ``hbm`` (bytes
needed over the peak bandwidth) or ``idle`` (``serve_step.idle``: busy time a
kept step over the step's period in the run's quiet part). What a step needs,
whatever implements it: its rows through the dense path and the sampled rows
through the head; the (token, expert) pairs that fell on held experts through
one expert each and the weights of the held experts touched, once, both from
the counters on ``serve.emit`` where the program has them (the adapter's even
share and every held expert where it has not); absorbed attention over each
row's live context; every other weight once; the live latent rows of the
scheduled sequences once and the new rows once. An adapter without an expert
layer (no ``expert_params``): reads nothing (``serve_step`` reads it).
"""
from bench.lib import spans as S, trace as T
from bench.readers import serve_step


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
    got = T.kept(ctx, spec["module"])
    if got is None:
        return None
    if spec["bound"] == "idle":
        return serve_step.idle(ctx, spec, got)
    seconds, steps = got["seconds"], got["steps"]
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
