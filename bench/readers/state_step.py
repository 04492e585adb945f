"""The engine step of a model that keeps a state a sequence against the
chip's peaks, over the steps the trace KEPT (``bench.lib.trace.kept``, as
``kept_steps`` reads them; nothing is divided by the slice's length).

``bound`` is ``flops`` or ``hbm``. What a step needs, whatever implements
it: its rows through every matrix outside the routed experts and the sampled
rows through the head; the (token, expert) pairs that fell on held experts
through one expert each and the weights of the held experts touched, once
(``kept_steps.expert_counts``: the counters on ``serve.emit``, else the
adapter's even share and every held expert); attention over each row's live
context; the recurrence on every row; every other weight once; the live K/V
of the scheduled sequences once and the new rows once; and the state of
every scheduled sequence, tails and all, read once and written once
(``state_slots`` on ``serve.run``, else one a sampled row), with what the
recurrence reads and writes a row. An adapter without a state
(``state_bytes_per_sequence``): reads nothing."""
from bench.lib import spans as S, trace as T
from bench.readers import kept_steps


def state_slots(ctx, steps):
    """Sequences whose state the kept steps read and wrote, summed."""
    spans = S.of_run(ctx)
    runs = [s for s in S.named(spans, "serve.run")
            if "state_slots" in s[3]] if spans else []
    if runs:
        return S.argument({"spans": runs}, "serve.run",
                          ["state_slots"]) / len(runs) * len(steps)
    return None


def read(ctx, name):
    spec = ctx["cell"].metric_file(name)
    arch, cfg = ctx["arch"], ctx["cell"].config
    if not hasattr(arch, "state_bytes_per_sequence"):
        return None
    got = T.kept(ctx, spec["module"])
    if got is None:
        return None
    seconds, steps = got["seconds"], got["steps"]
    tokens = sum(s[2] for s in steps)
    expert_layers = arch.kinds(cfg)["E"]
    counts = kept_steps.expert_counts(ctx, len(steps))
    if counts is None:
        counts = (tokens * expert_layers * cfg["num_experts_per_tok"]
                  * arch.held_share(cfg),
                  len(steps) * expert_layers * cfg["n_routed_experts"])
    pairs_held, touched = counts
    slots = state_slots(ctx, steps)
    if slots is None:
        slots = sum(s[3] for s in steps)
    dense = arch.dense_params(cfg)
    if spec["bound"] == "flops":
        need = 2.0 * dense * tokens \
            + 2.0 * arch.head_params(cfg) * sum(s[3] for s in steps) \
            + 2.0 * arch.expert_params(cfg) * pairs_held \
            + sum(arch.attention_flops(cfg, s[4]) for s in steps) \
            + arch.scan_flops_per_row(cfg) * tokens
        peak = ctx["peaks"]["bf16_flops_per_s"]
    else:
        kv = arch.kv_bytes_per_token(cfg)
        need = 2.0 * (dense + arch.head_params(cfg)) * len(steps) \
            + 2.0 * arch.expert_params(cfg) * touched \
            + kv * sum(s[5] for s in steps) + kv * tokens \
            + 2.0 * arch.state_bytes_per_sequence(cfg) * slots \
            + arch.scan_row_bytes(cfg) * tokens
        peak = ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * need / peak / seconds
