"""The engine step against the chip's peaks over the traced steps: the metric
file's ``bound`` is ``flops`` (operations needed over the peak rate) or
``hbm`` (bytes needed over the peak bandwidth), each over the summed device
time of the step program (``module``) in the trace."""
from bench.lib import flops


def read(ctx, name):
    spec = ctx["cell"].metric_file(name)
    t, m = ctx["trace"], ctx["measured"]
    first, last = m.get("slice", (None, None))
    if first is None or last is None or last <= first:
        return None
    us = sum(d for _, d, n in t["modules"] if n.startswith(spec["module"]))
    if us <= 0:
        return None
    arch, cfg = ctx["arch"], ctx["cell"].config
    need = 0.0
    for _, _, tokens, sampled, attn, live, *_ in m["steps"][first:last]:
        if spec["bound"] == "flops":
            need += flops.serve_step_flops(arch, cfg, tokens, sampled, [attn])
        else:
            need += flops.serve_step_bytes(arch, cfg, tokens, live)
    peak = ctx["peaks"]["bf16_flops_per_s" if spec["bound"] == "flops"
                        else "hbm_bytes_per_s"]
    return 100.0 * need / peak / (us / 1e6)
