"""Set-up of one run: the device, the compile meter, seeded weights, the
GLASS prior and the engine under test.

``chip_smoke.py`` imports ``CompileMeter`` from here; ``memory`` and the
prior over a seeded token corpus follow ``chip_smoke.py``.  The weights are
the reference's layout, made on the device in one jitted call from the
seed, so that the comparison that decides ``correct`` takes nothing that the
system under test made.
"""
from __future__ import annotations

import dataclasses
import importlib
import json
import sys
from pathlib import Path

import numpy as np

from bench.work.attention import head_dim

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class NoChip(RuntimeError):
    """JAX found no accelerator of the kind, or fewer chips than, the cell asks."""


class CompileMeter:
    """Backend compilations and their seconds (a persistent-cache read
    replaces a compile and is counted as one), plus the persistent cache's
    hits and misses.  Tracing and lowering are left out; their events nest."""

    def __init__(self, jax):
        self.secs = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += duration_secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def line(self) -> str:
        return (f"compiles={self.compiles} compile_s={self.secs:.1f} "
                f"persistent_cache_hits={self.hits} persistent_cache_misses={self.misses}")


_METER = None


def compile_meter(jax) -> CompileMeter:
    """The process's one meter: JAX keeps every listener registered, so a
    second run in the same process reads the same meter."""
    global _METER
    if _METER is None:
        _METER = CompileMeter(jax)
    return _METER


def memory(dev) -> dict:
    st = dev.memory_stats() or {}
    return {"bytes_in_use": st.get("bytes_in_use"), "peak_bytes_in_use": st.get("peak_bytes_in_use")}


def load_json(rel: str) -> dict:
    return json.loads((ROOT / rel).read_text())


def find_cell(name: str) -> tuple:
    """(workload entry, configuration entry) of BENCHMARK.json by cell name."""
    spec = load_json("BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    cfg = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    return cell, cfg


def reference_module(config: dict):
    return importlib.import_module(f"bench.references.{config['reference']}")


def seed_words(seed: int, n: int = 4) -> list:
    """``n`` 31-bit words from any non-negative seed, 64 bits and more."""
    return [int(w) & 0x7FFFFFFF for w in np.random.SeedSequence(seed).generate_state(n)]


def open_device(jax, chips: int):
    """The chips of the cell; raise ``NoChip`` on another platform."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found platform {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"needs {chips} chips; JAX found {len(devs)}")
    return devs[:chips]


# ModelConfig field: the config.json key it is checked against, where the file has it
CORE_KEYS = {
    "n_layers": "num_hidden_layers", "d_model": "hidden_size", "d_ff": "intermediate_size",
    "n_heads": "num_attention_heads", "n_kv_heads": "num_key_value_heads",
    "vocab_size": "vocab_size", "rope_theta": "rope_theta", "norm_eps": "rms_norm_eps",
    "tie_embeddings": "tie_word_embeddings", "dtype": "torch_dtype",
}


def _plain(v):
    return [_plain(x) for x in v] if isinstance(v, (list, tuple)) else v


def program_model(config: dict):
    """The system under test's model for a configuration file: the registry
    entry cut as the file says, checked against the file: each core
    ``config.json`` key the file has, the head size and the window, every
    entry of its ``"registry_fields"`` (``{ModelConfig field: value}``), and
    the length of its ``"layers"``."""
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    from repro.configs import get_config
    from repro.models import build_model

    hf = config["hf_config"]
    layers = config.get("layers")
    if layers is not None and len(layers) != hf["num_hidden_layers"]:
        raise ValueError(f"\"layers\" lists {len(layers)} layers; num_hidden_layers is "
                         f"{hf['num_hidden_layers']}")
    cfg = get_config(config["registry"]).replace(**config.get("registry_overrides", {}))
    fields = config.get("registry_fields", {})
    unknown = set(fields) - {f.name for f in dataclasses.fields(cfg)}
    if unknown:
        raise ValueError(f"registry_fields names no ModelConfig field: {sorted(unknown)}")
    want = {f: hf[key] for f, key in CORE_KEYS.items() if key in hf}
    if "rope_theta" in want:
        want["rope_theta"] = float(want["rope_theta"])
    want.update(head_dim=head_dim(hf), sliding_window=hf.get("sliding_window"))
    want.update(fields)
    got = {k: getattr(cfg, k) for k in want}
    diff = {k: (got[k], want[k]) for k in want if _plain(got[k]) != _plain(want[k])}
    if diff:
        raise ValueError(f"registry {config['registry']!r} differs from the file "
                         f"(registry, file): {diff}")
    return build_model(cfg)


def weights(jax, config: dict, seed: int, model):
    """Seeded weights in the reference's layout, made on the device in one
    jitted call; their tree must be the one the model's own init makes."""
    import jax.numpy as jnp

    ref = reference_module(config)
    dtype = jnp.dtype(config["hf_config"]["torch_dtype"])
    key = jax.random.key(seed_words(seed)[0])
    params = jax.jit(lambda k: ref.init_params(config["hf_config"], k, dtype))(key)
    want = jax.eval_shape(model.init, key)
    same = jax.tree.structure(params) == jax.tree.structure(want) and all(
        a.shape == b.shape and a.dtype == b.dtype
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(want)))
    if not same:
        raise ValueError("the reference's weight layout is not the model's")
    return params


def corpus(jax, config: dict, seed: int):
    """The seeded token corpus the GLASS prior is taken over."""
    g = config["glass"]
    key = jax.random.key(seed_words(seed)[1])
    return jax.random.randint(key, (g["prior_seqs"], g["prior_len"]), 3,
                              config["hf_config"]["vocab_size"])


def program_prior(jax, model, params, config: dict, toks):
    """The program's GLASS prior, through ``compute_global_prior``'s corpus
    path (NPS's (batch, V, V) bigram table does not fit at these vocabularies)."""
    from repro.core.glass import compute_global_prior
    from repro.core.nps import NPSConfig

    g = config["glass"]
    npc = NPSConfig(n_seqs=g["prior_seqs"], seq_len=g["prior_len"], batch=g["prior_seqs"],
                    bos_id=g["bos_id"])
    return jax.block_until_ready(compute_global_prior(
        model, params, jax.random.key(0), npc, variant=g["variant"], corpus=toks))


def engine(model, params, prior, config: dict, cell_file: dict):
    """One ``PagedEngine`` as the configuration and the cell set it up."""
    from repro.core import GlassConfig
    from repro.serve.engine import PagedEngine

    g = config["glass"]
    gcfg = GlassConfig(density=g["density"], lam=g["lam"], variant=g["variant"],
                       selection=g["selection"], block_size=g["block_size"])
    return PagedEngine(model, params, glass=gcfg, global_prior=prior,
                       max_len=cell_file["max_len"], num_blocks=cell_file["num_blocks"],
                       **config["engine"])
