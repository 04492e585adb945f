"""The whole training step's share of the chips' peak: operations the forward
and backward need per token (``lib.flops``; recomputation not counted) times
the tokens a second a chip completed over the window's steps, over the peak."""
from bench.lib import flops


def read(ctx, name):
    rate = ctx["measured"].get("step_tok_s_chip")
    if rate is None:
        return None
    per_token = flops.train_flops_per_token(
        ctx["arch"], ctx["cell"].config, ctx["cell"].traffic["seq"])
    return 100.0 * per_token * rate / ctx["peaks"]["bf16_flops_per_s"]
