"""A ratio of the counts the program's spans carry as arguments
(``bench.lib.spans``): over the spans called the metric file's ``span``, the
sum of the arguments ``num`` over the sum of the arguments ``den``; times 100
where the metric's unit is ``%``. No such span, or a zero denominator: reads
nothing."""
from bench.lib import spans as S


def read(ctx, name):
    spec = ctx["cell"].metric_file(name)
    t = S.of_run(ctx)
    if not t or not S.named(t, spec["span"]):
        return None
    den = S.argument(t, spec["span"], spec["den"])
    if den <= 0:
        return None
    scale = 100.0 if spec["unit"] == "%" else 1.0
    return scale * S.argument(t, spec["span"], spec["num"]) / den
