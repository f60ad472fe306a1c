"""FFN tile fetches of the decode calls over the tiles in the union of the
decoding rows' kept lists, summed over layers and steps in the window
(``PagedEngine`` counters ``ffn_tiles_read`` / ``ffn_tiles_union``): 1 when
each tile some row keeps is read once a step."""
from bench import probe


def read(ctx):
    return probe.ratio(ctx.counters, "ffn_tiles_read", "ffn_tiles_union")
