"""Operations and bytes that the algorithm needs, from a configuration's sizes.

Every count here is of the work GLASS serving needs, whatever implements it:
each active FFN weight tile once per decode step however many rows use it
(the union of the running requests' block lists), each row's live K/V once,
capped at its layer's window, and only the useful score operations.

A configuration file may state its composition: ``"layers"``, per layer of
the cut the kinds it holds, and ``"kinds"``, each kind's sizes in the
harness's names (a kind's ``"per_layer"`` map gives a size one value per
layer of that kind).  Where it states neither, every layer holds
``["attention", "ffn"]`` at the sizes of its ``config.json`` keys.  Each
kind is counted by its module under ``bench/work/``, so a configuration of
another composition needs no edit here.
"""
from __future__ import annotations

import json
from pathlib import Path

from bench import work
from bench.work import attention, ffn

PEAKS = Path(__file__).resolve().parent / "peaks.json"
DEFAULT_LAYER = ["attention", "ffn"]


def peaks(device_kind: str) -> dict:
    """The chip's peak bf16 FLOP/s and HBM bytes/s by JAX's ``device_kind``."""
    table = json.loads(PEAKS.read_text())["chips"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


def shape(config: dict) -> dict:
    """``d``, ``V``, ``L``, ``bytes``; ``kinds``: each kind's sizes; and
    ``layers``: per layer, ``[(kind, sizes), ...]`` with each kind's
    per-layer sizes in place.  A kind with no work module is an error here."""
    hf = config["hf_config"]
    layers = config.get("layers") or [DEFAULT_LAYER] * hf["num_hidden_layers"]
    held = list(dict.fromkeys(kind for layer in layers for kind in layer))
    given = config.get("kinds", {})
    if set(given) - set(held):
        raise ValueError(f"\"kinds\" names {sorted(set(given) - set(held))}, which no layer holds")
    count = {kind: sum(layer.count(kind) for layer in layers) for kind in held}
    kinds, per_layer = {}, {}
    for kind in held:
        g = dict(given.get(kind, {}))
        per_layer[kind] = g.pop("per_layer", {})
        for key, vals in per_layer[kind].items():
            if len(vals) != count[kind]:
                raise ValueError(f"{kind}.per_layer.{key} has {len(vals)} values for "
                                 f"{count[kind]} layers")
        kinds[kind] = work.module(kind).sizes(config, g)
    each = {kind: iter([dict(kinds[kind], **{key: vals[j] for key, vals in per_layer[kind].items()})
                        for j in range(count[kind])]) for kind in held}
    return {"d": hf["hidden_size"], "V": hf["vocab_size"], "L": len(layers),
            "bytes": work.dtype_bytes(hf), "kinds": kinds,
            "layers": [[(kind, next(each[kind])) for kind in layer] for layer in layers]}


def layers_of(s: dict, kind: str) -> list:
    """The sizes of each layer that holds ``kind``, in order."""
    return [k for layer in s["layers"] for name, k in layer if name == kind]


def _each(s: dict):
    mods = {kind: work.module(kind) for kind in s["kinds"]}
    for layer in s["layers"]:
        for kind, k in layer:
            yield mods[kind], k


def decode_token_flops(s: dict, context: int) -> int:
    """One decoded token whose context (prompt and earlier tokens) is
    ``context`` long: every layer's kinds, then the head."""
    return sum(m.token_flops(k, context) for m, k in _each(s)) + 2 * s["d"] * s["V"]


def prefill_flops(s: dict, prompt: int) -> int:
    """A whole prompt: every layer's kinds at every position (the FFN
    dense), the head once."""
    return sum(m.prefill_flops(k, prompt) for m, k in _each(s)) + 2 * s["d"] * s["V"]


def ffn_step_work(s: dict, lists: list) -> tuple:
    """(flops, bytes) of one decode step's FFN over the rows whose kept
    block lists are ``lists`` (per row, one block-id set per FFN layer):
    every tile in the union of the rows' lists is read once."""
    layers = layers_of(s, "ffn")
    for row in lists:
        if len(row) != len(layers):
            raise ValueError(f"a row's kept lists hold {len(row)} layers; the configuration "
                             f"has {len(layers)} FFN layers")
    rows, flops, nbytes = len(lists), 0, 0
    for j, k in enumerate(layers):
        tiles = len(set().union(*(row[j] for row in lists)))
        flops += 2 * rows * ffn.weights(k)
        nbytes += tiles * ffn.tile_params(k) * k["bytes"] + rows * ffn.row_bytes(k, 0)
    return flops, nbytes


def attn_step_work(s: dict, contexts: list) -> tuple:
    """(flops, bytes) of one decode step's attention kernel over rows whose
    contexts are ``contexts``, in each layer that holds attention: each
    row's live K and V, capped at the window, read once; score operations
    for the one new query."""
    flops = nbytes = 0
    for k in layers_of(s, "attention"):
        flops += sum(attention.score_flops(k, c + 1) for c in contexts)
        nbytes += sum(attention.row_bytes(k, c) for c in contexts)
    return flops, nbytes


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline: the larger of compute time and memory time at peak."""
    return max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
