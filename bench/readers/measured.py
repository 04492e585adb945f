"""A number the run's own host clock or counters already hold: the metric
file's ``field`` of what the kind measured. Nothing measured, nothing read."""


def read(ctx, name):
    spec = ctx["cell"].metric_file(name)
    return ctx["measured"].get(spec["field"])
