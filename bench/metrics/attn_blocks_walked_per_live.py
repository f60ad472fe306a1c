"""Grid steps of the paged-attention kernel over the K/V blocks that hold
the decoding rows' live, window-capped K/V, summed over layers and steps in
the window (``PagedEngine`` counters ``attn_blocks_walked`` /
``attn_blocks_live``): 1 when the kernel walks only live blocks."""
from bench import probe


def read(ctx):
    return probe.ratio(ctx.counters, "attn_blocks_walked", "attn_blocks_live")
