"""Pages of the KV pool in use over its size (``telemetry()["pool"]``), mean
of the samples taken after each step of the window."""


def read(ctx, name):
    samples = [s[6] for s in ctx["measured"].get("steps", ()) if s[6] is not None]
    return 100.0 * sum(samples) / len(samples) if samples else None
