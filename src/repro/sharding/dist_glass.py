"""Distributed GLASS: shard-local compaction under shard_map.

Rank fusion runs on *replicated* score vectors — (L, m) f32 is tiny (a few
MB even for gemma2-27b), so exact global ranking costs one small all-gather.
Selection is shard-balanced (k/n per model shard) so the subsequent weight
gather never crosses a shard boundary; the gather itself runs under
shard_map with zero collectives.
"""
from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from ..models.common import ModelConfig


def to_local_indices(idx: jax.Array, m: int, n_shards: int) -> jax.Array:
    """Global shard-balanced indices (..., k) -> local (..., n_shards, k/n).

    Requires indices grouped by shard (guaranteed by select_shard_balanced's
    sorted output)."""
    k = idx.shape[-1]
    per = m // n_shards
    loc = idx.reshape(idx.shape[:-1] + (n_shards, k // n_shards))
    offs = (jnp.arange(n_shards, dtype=idx.dtype) * per)[:, None]
    return loc - offs


def _gather_cols(w, i):  # w (..., d, m_local), i (k_local,)
    return jnp.take(w, i, axis=-1)


def _gather_rows(w, i):  # w (..., m_local, d), i (k_local,)
    return jnp.take(w, i, axis=-2)


def compact_ffn_sharded(
    mesh: Mesh,
    ffn_params: Dict[str, jax.Array],  # stacked (L, d, m) / (L, m, d), m sharded "model"
    idx_local: jax.Array,  # (L, n_shards, k/n), dim1 sharded "model"
) -> Dict[str, jax.Array]:
    """Per-shard gather of selected FFN units; no collectives."""

    def kernel(w_up, w_down, w_gate, il):
        il = il[:, 0]  # (L, 1, k/n) -> (L, k/n)
        out = {
            "w_up": jax.vmap(_gather_cols)(w_up, il),
            "w_down": jax.vmap(_gather_rows)(w_down, il),
        }
        if w_gate is not None:
            out["w_gate"] = jax.vmap(_gather_cols)(w_gate, il)
        return out

    has_gate = "w_gate" in ffn_params
    in_specs = (
        P(None, None, "model"),
        P(None, "model", None),
        P(None, None, "model") if has_gate else None,
        P(None, "model", None),
    )
    out_specs = {"w_up": P(None, None, "model"), "w_down": P(None, "model", None)}
    if has_gate:
        out_specs["w_gate"] = P(None, None, "model")
    fn = shard_map(
        kernel, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )
    return fn(
        ffn_params["w_up"],
        ffn_params["w_down"],
        ffn_params.get("w_gate"),
        idx_local,
    )


def compact_moe_sharded(mesh: Mesh, moe_params, idx_local):
    """MoE per-expert compaction. weights (L, E, d, f) / (L, E, f, d) with f
    sharded over model; idx_local (L, E, n, k/n)."""

    def kernel(w_up, w_down, w_gate, router, il):
        il = il[:, :, 0]  # (L, E, k/n)
        g2 = jax.vmap(jax.vmap(_gather_cols))
        g2r = jax.vmap(jax.vmap(_gather_rows))
        out = {
            "router": router,
            "w_up": g2(w_up, il),
            "w_down": g2r(w_down, il),
        }
        if w_gate is not None:
            out["w_gate"] = g2(w_gate, il)
        return out

    has_gate = "w_gate" in moe_params
    ep = P(None, None, None, "model")  # (L,E,d,f)
    dn = P(None, None, "model", None)  # (L,E,f,d)
    in_specs = (ep, dn, ep if has_gate else None, P(None, None, None), P(None, None, "model", None))
    out_specs = {"router": P(None, None, None), "w_up": ep, "w_down": dn}
    if has_gate:
        out_specs["w_gate"] = ep
    fn = shard_map(kernel, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False)
    return fn(
        moe_params["w_up"],
        moe_params["w_down"],
        moe_params.get("w_gate"),
        moe_params["router"],
        idx_local,
    )


def compact_rwkv_cm_sharded(mesh: Mesh, cm_params, idx_local):
    """RWKV channel-mix: wk (L,d,f), wv (L,f,d); wr/mu pass through."""

    def kernel(wk, wv, il):
        il = il[:, 0]
        return {
            "wk": jax.vmap(_gather_cols)(wk, il),
            "wv": jax.vmap(_gather_rows)(wv, il),
        }

    fn = shard_map(
        kernel,
        mesh=mesh,
        in_specs=(P(None, None, "model"), P(None, "model", None), P(None, "model", None)),
        out_specs={"wk": P(None, None, "model"), "wv": P(None, "model", None)},
        check_vma=False,
    )
    out = fn(cm_params["wk"], cm_params["wv"], idx_local)
    return {"mu": cm_params["mu"], "wr": cm_params["wr"], **out}
