"""Share of the traced slice spent in the device operations emitted from
source lines under the metric file's ``fragment`` (a path). Finds none: reads
nothing."""
from bench.lib import trace as T


def read(ctx, name):
    fragment = ctx["cell"].metric_file(name)["fragment"]
    t = ctx["trace"]
    us = T.time_where(t["ops"], lambda op: T.from_source(op, fragment))
    return 100.0 * us / 1e6 / t["window_s"] if us > 0 else None
