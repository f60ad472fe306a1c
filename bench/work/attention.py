"""Grouped-query attention over paged K/V: the Q, K, V and O projections,
the useful score operations of each query (QK^T and PV over the keys it
sees, capped at the layer's window), and each row's live K/V read once.

Sizes: ``d``, ``H`` query heads, ``K`` K/V heads of ``hd``, ``window``
(``None``: the whole context), ``bytes``.  A file that gives its attention
layers different windows states them under
``"kinds": {"attention": {"per_layer": {"window": [...]}}}``, one per
attention layer.
"""
from __future__ import annotations

from bench.work import dtype_bytes


def head_dim(hf: dict) -> int:
    """``head_dim``, else ``attention_head_dim``, else hidden / heads."""
    return (hf.get("head_dim") or hf.get("attention_head_dim")
            or hf["hidden_size"] // hf["num_attention_heads"])


def sizes(config: dict, given: dict) -> dict:
    hf = config["hf_config"]
    return {"d": hf["hidden_size"], "H": hf["num_attention_heads"],
            "K": hf["num_key_value_heads"], "hd": head_dim(hf),
            "window": hf.get("sliding_window"), "bytes": dtype_bytes(hf), **given}


def seen(k: dict, keys: int) -> int:
    """Keys a query attends to, of ``keys``: capped at the window."""
    return min(keys, k["window"] or keys)


def weights(k: dict) -> int:
    return k["d"] * k["hd"] * (2 * k["H"] + 2 * k["K"])


def score_flops(k: dict, keys: int) -> int:
    """QK^T and PV of one query over ``keys`` keys, every head."""
    return 4 * k["H"] * k["hd"] * seen(k, keys)


def token_flops(k: dict, context: int) -> int:
    return 2 * weights(k) + score_flops(k, context + 1)


def row_bytes(k: dict, context: int) -> int:
    """The row's live K and V, the new token's among them."""
    return seen(k, context + 1) * 2 * k["K"] * k["hd"] * k["bytes"]


def prefill_flops(k: dict, prompt: int) -> int:
    """Projections at every position; position t attends to min(t, window)
    keys, summed in closed form."""
    w = k["window"] or prompt
    n = min(prompt, w)
    keys = n * (n + 1) // 2 + (prompt - n) * w
    return 2 * prompt * weights(k) + 4 * k["H"] * k["hd"] * keys
