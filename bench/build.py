"""Set-up of one run: the device, the compile meter, seeded weights, the
GLASS prior and the engine under test.

``CompileMeter``, ``memory`` and the prior over a seeded token corpus follow
``chip_smoke.py``; the weights are the reference's layout, made on the device
in one jitted call from the seed, so that the comparison that decides
``correct`` takes nothing that the system under test made.
"""
from __future__ import annotations

import importlib
import json
import sys
from pathlib import Path

import numpy as np

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


def program_model(config: dict):
    """The system under test's model for a configuration file: the registry
    entry cut as the file says, every width checked against the file."""
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    from repro.configs import get_config
    from repro.models import build_model

    hf = config["hf_config"]
    cfg = get_config(config["registry"]).replace(**config.get("registry_overrides", {}))
    want = {
        "n_layers": hf["num_hidden_layers"], "d_model": hf["hidden_size"],
        "d_ff": hf["intermediate_size"], "n_heads": hf["num_attention_heads"],
        "n_kv_heads": hf["num_key_value_heads"], "vocab_size": hf["vocab_size"],
        "head_dim": hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"],
        "sliding_window": hf.get("sliding_window"), "rope_theta": float(hf["rope_theta"]),
        "norm_eps": hf["rms_norm_eps"], "tie_embeddings": hf["tie_word_embeddings"],
        "dtype": hf["torch_dtype"],
    }
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        diff = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
        raise ValueError(f"registry {config['registry']!r} differs from the file: {diff}")
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
