"""Work counts of a layer kind, one module each: ``bench/work/<kind>.py``.

A configuration file's ``"layers"`` lists, per layer of the cut, the kinds
that layer holds (``["attention", "ffn"]``, ``["mamba2"]``); its ``"kinds"``
gives each kind's sizes in the harness's names.  ``flops.shape`` counts each
kind by the module of its name here, so a new kind is a new file.  Every
module gives these functions, each of ONE layer of its kind, where ``k`` is
that layer's sizes:

- ``sizes(config, given) -> dict``: the kind's sizes from the configuration
  file's ``config.json`` keys (``config["hf_config"]``), with those the file
  states under ``"kinds"`` (``given``) in their place; ``"bytes"``, the
  stored type's width, among them.
- ``weights(k) -> int``: weight elements that one decoded token reads.
- ``token_flops(k, context) -> int``: operations of one decoded token whose
  context (prompt and earlier tokens) is ``context`` long.
- ``row_bytes(k, context) -> int``: bytes a decoding row reads and writes
  beside the weights at that context: its K/V, its state, its activations.
- ``prefill_flops(k, prompt) -> int``: operations of a whole prompt.
"""
from __future__ import annotations

import importlib


def module(kind: str):
    """The work module of ``kind``; an error where there is none."""
    name = f"{__name__}.{kind}"
    if not kind.isidentifier():
        raise ValueError(f"layer kind {kind!r} is not a module name")
    try:
        return importlib.import_module(name)
    except ModuleNotFoundError as e:
        if e.name != name:
            raise
        raise ValueError(f"layer kind {kind!r} has no work module bench/work/{kind}.py") from None


def dtype_bytes(hf: dict) -> int:
    """Bytes of one stored element in the configuration's ``torch_dtype``."""
    return 2 if hf["torch_dtype"] in ("bfloat16", "float16") else 4
