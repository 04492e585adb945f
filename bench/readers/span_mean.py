"""Host seconds a step of the program's own spans (``bench.lib.spans``): the
metric file's ``spans``, a list of span names whose durations are summed, or
``between``, one span name whose end-to-next-start gaps are summed; over the
number of ``serve.run`` spans of the traced slice, the steps that ran a plan.
No such span in the trace: reads nothing."""
from bench.lib import spans as S


def read(ctx, name):
    spec = ctx["cell"].metric_file(name)
    t = S.of_run(ctx)
    steps = len(S.named(t, "serve.run")) if t else 0
    if not steps:
        return None
    us = S.between(t, spec["between"]) if "between" in spec \
        else S.total(t, spec["spans"])
    return us / 1e6 / steps
