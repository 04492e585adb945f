"""A ratio of counts that only some programs' spans carry as arguments
(``bench.lib.spans``): as ``span_ratio``, over the spans called the metric
file's ``span`` the sum of the arguments ``num`` over the sum of the arguments
``den``, times 100 where the metric's unit is ``%``; but a span that lacks one
of the arguments (the program before the counter was added) is left out. No
span carries them all, or a zero denominator: reads nothing."""
from bench.lib import spans as S


def read(ctx, name):
    spec = ctx["cell"].metric_file(name)
    t = S.of_run(ctx)
    if not t:
        return None
    keys = set(spec["num"]) | set(spec["den"])
    has = {"spans": [s for s in S.named(t, spec["span"])
                     if keys <= set(s[3])]}
    den = S.argument(has, spec["span"], spec["den"])
    if den <= 0:
        return None
    scale = 100.0 if spec["unit"] == "%" else 1.0
    return scale * S.argument(has, spec["span"], spec["num"]) / den
