"""The host's own time per engine tick: the mean over the window's
``engine.step`` spans of their milliseconds not covered by the
``engine.decode.wait`` spans inside them (``probe.step_host_ms``)."""
from bench import probe


def read(ctx):
    if ctx.trace is None:
        return None
    from bench.harness import window_bounds

    return probe.step_host_ms(ctx.trace.spans, *window_bounds(ctx.trace))
