"""The engine step against the chip's peaks, and the device's idle share, over
the steps the trace KEPT (``bench.lib.trace.kept``: the executions of the
metric file's ``module`` the trace holds and the first that many steps of the
slice; nothing is divided by the slice's length). The metric file's ``bound``
is ``flops`` (operations the kept steps need over the peak rate), ``hbm``
(bytes they need over the peak bandwidth), each over the kept executions'
own device time, or ``idle``.

The idle share reads TWO parts of one run: the device's busy time a kept step
(the profiler leaves it as it is), over the step's period from the run's
QUIET part, before the profiler started: the metric file's ``period``, a
host-clock field of what the kind measured (the mean gap between a sequence's
tokens, which is one step). The slice's own length is in neither, so the
profiler's hold on the host is not read as idleness. No kept execution, no
period: reads nothing."""
from bench.lib import flops, trace as T


def idle(ctx, spec, got):
    period = ctx["measured"].get(spec["period"])
    if not period:
        return None
    return 100.0 * (1.0 - got["busy_s"] / len(got["steps"]) / period)


def read(ctx, name):
    spec = ctx["cell"].metric_file(name)
    got = T.kept(ctx, spec["module"])
    if got is None:
        return None
    if spec["bound"] == "idle":
        return idle(ctx, spec, got)
    arch, cfg = ctx["arch"], ctx["cell"].config
    need = 0.0
    for _, _, tokens, sampled, attn, live, *_ in got["steps"]:
        if spec["bound"] == "flops":
            need += flops.serve_step_flops(arch, cfg, tokens, sampled, [attn])
        else:
            need += flops.serve_step_bytes(arch, cfg, tokens, live)
    peak = ctx["peaks"]["bf16_flops_per_s" if spec["bound"] == "flops"
                        else "hbm_bytes_per_s"]
    return 100.0 * need / peak / got["seconds"]
