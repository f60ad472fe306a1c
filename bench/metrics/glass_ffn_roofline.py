"""The block-sparse FFN kernel's share of its roofline in decode: the least
time of each decode step's FFN (every tile in the union of the rows' kept
block lists read once, ``flops.ffn_step_work``) over the ``glass_ffn``
kernels' device time inside the decode programs.

Both Pallas kernels' bodies are named ``_kernel``, so the trace names
neither; a ``glass_ffn`` call is the TPU custom call that takes the FFN's
up (and gate) matrix, ``bf16[d,f]``, as an operand.  A configuration with
no FFN layer reads nothing."""
from bench import flops, steps
from bench import trace as tr

DECODE = r"^jit_dec\b"


def kernel(shape: dict) -> str:
    k = shape["kinds"]["ffn"]
    return rf'(?s)^(?=.*custom_call_target="tpu_custom_call")(?=.*\[{k["d"]},{k["f"]}\])'


def read(ctx):
    if (ctx.trace is None or not ctx.trace.devices or ctx.peak is None
            or "ffn" not in ctx.shape["kinds"]):
        return None
    pat = kernel(ctx.shape)
    secs = sum(tr.kernel_seconds(d, pat, DECODE) for d in ctx.trace.devices) / len(ctx.trace.devices)
    if not secs:
        return None
    lists = ctx.lists()
    least = 0.0
    for _, rows in steps.decode_steps(ctx):
        f, b = flops.ffn_step_work(ctx.shape, [lists[u] for u, _ in rows])
        least += flops.least_seconds(f, b, ctx.peak)
    return 100.0 * least / secs
