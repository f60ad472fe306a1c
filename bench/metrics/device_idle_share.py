"""Share of the traced window in which no operation ran on the device."""
from bench import trace as tr


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    from bench.harness import window_bounds

    t0, t1 = window_bounds(ctx.trace)
    busy = sum(tr.busy_seconds(d.ops, t0, t1) for d in ctx.trace.devices) / len(ctx.trace.devices)
    return 100.0 * (1.0 - busy / (t1 - t0))
