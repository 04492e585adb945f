"""Share of the traced slice in which no operation ran on the device."""


def read(ctx, name):
    t = ctx["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
