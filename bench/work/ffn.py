"""A dense FFN served under GLASS: the prompt runs it whole; a decoded token
reads only the ``n_keep`` kept tiles of ``bs`` units, each tile its columns
of the up (and gate) matrix and its rows of the down matrix.

Sizes: ``d``, ``f``, ``gated`` (three matrices, gate and up and down; else
two, up and down), ``bytes``; ``bs`` and ``n_keep`` follow the file's
``"glass"`` block size and density.
"""
from __future__ import annotations

from bench.work import dtype_bytes


def sizes(config: dict, given: dict) -> dict:
    hf, g = config["hf_config"], config["glass"]
    k = {"d": hf["hidden_size"], "f": hf["intermediate_size"], "gated": True,
         "bytes": dtype_bytes(hf), **given}
    keep = max(1, int(round(g["density"] * k["f"])))
    return dict(k, bs=g["block_size"], n_keep=-(-keep // g["block_size"]))


def matrices(k: dict) -> int:
    return 3 if k["gated"] else 2


def tile_params(k: dict) -> int:
    """Weights of one FFN tile: its up (and gate) columns and down rows."""
    return matrices(k) * k["d"] * k["bs"]


def weights(k: dict) -> int:
    return k["n_keep"] * tile_params(k)


def token_flops(k: dict, context: int) -> int:
    return 2 * weights(k)


def row_bytes(k: dict, context: int) -> int:
    """The row's input read and its output written."""
    return 2 * k["d"] * k["bytes"]


def prefill_flops(k: dict, prompt: int) -> int:
    return 2 * prompt * matrices(k) * k["d"] * k["f"]
