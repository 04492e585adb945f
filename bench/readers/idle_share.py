"""Share of the traced slice in which no operation ran on the device. For
training, whose steps leave 4,000 events each and are never cut by the
export; the serving cells read ``serve_step``'s ``idle`` over the kept steps."""


def read(ctx, name):
    t = ctx["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
