"""What a recorded trace cannot feed. A trace cut from an older program lacks
the counters added since, so the ``span_counts`` metrics over them read
nothing on it. The tests that read a cell's per-layer metrics over a
recording expect exactly those, and the ones they name by hand, to read
nothing: a metric that a later PR puts on the cell's lists is then judged by
what the recording holds, not by a list of names written before it came."""
from bench.lib import spans as S


def uncounted(cell, path) -> set:
    """The cell's ``span_counts`` metrics whose arguments, ``num`` and ``den``
    together, no span of the metric's name in the recording at ``path``
    carries."""
    t = S.load(path)
    out = set()
    for m in cell.per_layer():
        f = cell.metric_file(m["name"])
        if f["reader"] != "span_counts":
            continue
        keys = set(f["num"]) | set(f["den"])
        if not any(keys <= set(s[3]) for s in S.named(t, f["span"])):
            out.add(m["name"])
    return out
