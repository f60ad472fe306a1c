"""Share of the decode programs' device time that the block-sparse FFN
kernels take: the Pallas calls named ``glass_ffn_shared`` and
``glass_ffn_rowwise`` inside the ``jit_dec`` programs, over those
programs' time.  A TPU trace names such an operation by its HLO text,
whose custom call instruction carries the kernel's name
(``%glass_ffn_rowwise.64 = ... custom-call(...)``); a program whose
kernels carry no such name reads nothing."""
from bench import trace as tr

DECODE = r"^jit_dec\b"
KERNEL = r'(?s)^(?=.*custom_call_target="tpu_custom_call")(?=.*\bglass_ffn_(?:shared|rowwise)\b)'


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    ffn = sum(tr.kernel_seconds(d, KERNEL, DECODE) for d in ctx.trace.devices)
    dec = sum(tr.module_seconds(d, DECODE) for d in ctx.trace.devices)
    return 100.0 * ffn / dec if ffn and dec else None
