"""Operations and bytes that the algorithm needs, from a configuration's sizes.

Every count here is of the work GLASS serving needs, whatever implements it:
each active FFN weight tile once per decode step however many rows use it
(the union of the running requests' block lists), each row's live K/V once,
capped at the sliding window, and only the useful score operations.  The
functions read the configuration file's ``config.json`` keys, so a new
configuration needs no edit here.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The chip's peak bf16 FLOP/s and HBM bytes/s by JAX's ``device_kind``."""
    table = json.loads(PEAKS.read_text())["chips"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


def shape(config: dict) -> dict:
    hf, g = config["hf_config"], config["glass"]
    d, h = hf["hidden_size"], hf["num_attention_heads"]
    f, bs = hf["intermediate_size"], g["block_size"]
    k = max(1, int(round(g["density"] * f)))
    return {
        "d": d, "f": f, "H": h, "K": hf["num_key_value_heads"],
        "hd": hf.get("head_dim") or d // h, "L": hf["num_hidden_layers"],
        "V": hf["vocab_size"], "window": hf.get("sliding_window") or 2**30,
        "bs": bs, "n_keep": -(-k // bs),
        "bytes": 2 if hf["torch_dtype"] in ("bfloat16", "float16") else 4,
    }


def attn_params(s: dict) -> int:
    return s["d"] * s["hd"] * (2 * s["H"] + 2 * s["K"])


def ffn_params(s: dict) -> int:
    return 3 * s["d"] * s["f"]


def tile_params(s: dict) -> int:
    """Weights of one FFN block: its gate and up columns and down rows."""
    return 3 * s["d"] * s["bs"]


def score_flops(s: dict, keys: int) -> int:
    """QK^T and PV of one query over ``keys`` keys, every head, one layer."""
    return 4 * s["H"] * s["hd"] * min(keys, s["window"])


def decode_token_flops(s: dict, context: int) -> int:
    """One decoded token whose context (prompt and earlier tokens) is
    ``context`` long: attention, kept FFN tiles and the head."""
    lin = attn_params(s) + s["n_keep"] * tile_params(s)
    return 2 * (s["L"] * lin + s["d"] * s["V"]) + s["L"] * score_flops(s, context + 1)


def prefill_flops(s: dict, prompt: int) -> int:
    """A whole prompt: dense FFN at every position, the head once."""
    lin = s["L"] * (attn_params(s) + ffn_params(s))
    scores = sum(score_flops(s, t + 1) for t in range(prompt)) * s["L"]
    return 2 * prompt * lin + scores + 2 * s["d"] * s["V"]


def ffn_step_work(s: dict, lists: list) -> tuple:
    """(flops, bytes) of one decode step's FFN over the rows whose kept
    block lists are ``lists`` (one sequence of per-layer block-id sets per
    row): every tile in the union of the rows' lists is read once."""
    rows = len(lists)
    tiles = sum(len(set().union(*(row[l] for row in lists))) for l in range(s["L"])) if rows else 0
    flops = 2 * rows * s["L"] * s["n_keep"] * tile_params(s)
    return flops, tiles * tile_params(s) * s["bytes"] + rows * s["L"] * 2 * s["d"] * s["bytes"]


def attn_step_work(s: dict, contexts: list) -> tuple:
    """(flops, bytes) of one decode step's attention kernel over rows whose
    contexts are ``contexts``: each row's live K and V, capped at the
    window, read once; score operations for the one new query."""
    kv_row = 2 * s["K"] * s["hd"] * s["bytes"]
    live = sum(min(c + 1, s["window"]) for c in contexts)
    flops = s["L"] * sum(score_flops(s, c + 1) for c in contexts)
    return flops, s["L"] * live * kv_row


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline: the larger of compute time and memory time at peak."""
    return max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
