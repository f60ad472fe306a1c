"""The paged-attention kernel's share of its roofline in decode: the least
time of each decode step's attention (each row's live K/V, capped at the
window, read once; useful score operations, ``flops.attn_step_work``)
over the ``paged_attention`` kernels' device time inside the decode
programs.

Both Pallas kernels' bodies are named ``_kernel``, so the trace names
neither; a ``paged_attention`` call is the TPU custom call that takes the
paged K/V pool, ``[num_blocks, block_size, K, head_dim]``, as operands.  A
configuration with no attention layer reads nothing."""
from bench import flops, steps
from bench import trace as tr

DECODE = r"^jit_dec\b"


def kernel(shape: dict) -> str:
    k = shape["kinds"]["attention"]
    return rf'(?s)^(?=.*custom_call_target="tpu_custom_call")(?=.*\[\d+,\d+,{k["K"]},{k["hd"]}\])'


def read(ctx):
    if (ctx.trace is None or not ctx.trace.devices or ctx.peak is None
            or "attention" not in ctx.shape["kinds"]):
        return None
    pat = kernel(ctx.shape)
    secs = sum(tr.kernel_seconds(d, pat, DECODE) for d in ctx.trace.devices) / len(ctx.trace.devices)
    if not secs:
        return None
    least = 0.0
    for _, rows in steps.decode_steps(ctx):
        f, b = flops.attn_step_work(ctx.shape, [c for _, c in rows])
        least += flops.least_seconds(f, b, ctx.peak)
    return 100.0 * least / secs
