"""Device time of the prefill-chunk programs (``jit_chunk``) per thousand
prompt tokens first answered in the window."""
from bench import trace as tr

CHUNK = r"^jit_chunk\b"


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices or not ctx.prefill_tokens:
        return None
    secs = sum(tr.module_seconds(d, CHUNK) for d in ctx.trace.devices) / len(ctx.trace.devices)
    return 1e3 * secs / (ctx.prefill_tokens / 1000.0) if secs else None
