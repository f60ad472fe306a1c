"""The decode steps of a window, rebuilt from what ``step()`` returned.

A tick that hands a request ``n`` decoded tokens ran ``n`` decode steps for
it (the engine fuses up to ``decode_chunk`` steps into one program call),
so a tick with rows decoding up to ``H`` tokens is ``H`` steps; step ``j``
holds the rows that decoded more than ``j`` tokens.  A row's context at a
step is the tokens before the query it decodes: its prompt and the tokens
it had produced, less the one it feeds.
"""
from __future__ import annotations


def decode_steps(ctx) -> list:
    """[(rows in the tick's step count H, [(uid, context), ...]) per step]
    over the window; the first element is 1 for each step so that summing
    it counts steps."""
    out = []
    for _, tick in ctx.ticks:
        rows = [(u, before, d) for u, before, d, _ in tick if d]
        if not rows:
            continue
        H = max(d for _, _, d in rows)
        for j in range(H):
            step = []
            for u, before, d in rows:
                if d > j:
                    g = before + j + (1 if before == 0 else 0)  # output index decoded
                    step.append((u, len(ctx.served[u].prompt) + g - 1))
            out.append((1, step))
    return out
