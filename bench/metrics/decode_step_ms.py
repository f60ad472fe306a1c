"""Device time of the decode programs per decode step: the ``jit_dec``
programs' time in the trace over the steps the window's ticks decoded."""
from bench import steps
from bench import trace as tr

DECODE = r"^jit_dec\b"


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    n = sum(h for h, _ in steps.decode_steps(ctx))
    secs = sum(tr.module_seconds(d, DECODE) for d in ctx.trace.devices) / len(ctx.trace.devices)
    return 1e3 * secs / n if n and secs else None
