"""KV blocks held over the pool's blocks, mean over engine ticks:
``PagedEngine.kv_row_ticks`` over ticks times the pool's rows."""


def read(ctx):
    t = ctx.counters.get("t", 0)
    return 100.0 * ctx.counters["kv_row_ticks"] / (t * ctx.pool_rows) if t else None
