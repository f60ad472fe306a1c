"""The whole step's share of the chip's peak: the operations the served
tokens need (``flops.decode_token_flops`` per decoded token at its
context, ``flops.prefill_flops`` per prompt first answered in the window)
over the window's seconds and the chip's peak bf16 FLOP/s."""
from bench import flops, steps


def read(ctx):
    if ctx.peak is None:
        return None
    work = sum(flops.decode_token_flops(ctx.shape, c)
               for _, rows in steps.decode_steps(ctx) for _, c in rows)
    work += sum(flops.prefill_flops(ctx.shape, len(s.prompt)) for s in ctx.served.values()
                if s.first_at is not None and 0 <= s.first_at < ctx.window_s)
    return 100.0 * work / ctx.window_s / ctx.peak["bf16_flops_per_s"] if work else None
