"""Rows in a decode step, mean over the window: the engine's row-ticks
counter over its tick counter (``PagedEngine.slot_steps`` / ``.t``)."""


def read(ctx):
    t = ctx.counters.get("t", 0)
    return ctx.counters["slot_steps"] / t if t else None
