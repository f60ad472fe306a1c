"""Step builders: the jittable train / glass-prefill / decode programs.

These are the functions the dry-run lowers and the real launchers run.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ..core.fusion import GlassConfig, glass_scores, select_shard_balanced
from ..core.importance import finalize
from ..models.api import Model
from ..sharding.dist_glass import (
    compact_ffn_sharded,
    compact_moe_sharded,
    compact_rwkv_cm_sharded,
    to_local_indices,
)
from ..train.optim import OptConfig, adamw_update, init_opt_state


# ---------------------------------------------------------------------------
# Training
# ---------------------------------------------------------------------------


def make_train_step(
    model: Model,
    oc: OptConfig,
    grad_accum: int = 1,
    grad_shardings=None,  # pytree of NamedSharding like params: pins the f32
):  # grad-accum carry (otherwise SPMD may replicate it — 4 bytes/param!)
    """Returns train_step(params, opt_state, batch) -> (params, opt, metrics).

    grad_accum > 1 scans over microbatches accumulating f32 grads — the
    standard memory lever for the big-model cells."""

    def loss_fn(params, batch):
        loss, metrics = model.loss(params, batch)
        return loss, metrics

    def pin(tree):
        if grad_shardings is None:
            return tree
        return jax.tree.map(jax.lax.with_sharding_constraint, tree, grad_shardings)

    def train_step(params, opt_state, batch):
        if grad_accum == 1:
            (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
            grads = pin(grads)
        else:
            def resh(x):
                B = x.shape[0]
                return x.reshape(grad_accum, B // grad_accum, *x.shape[1:])

            mbs = jax.tree.map(resh, batch)
            g0 = pin(jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params))

            def body(acc, mb):
                (l, met), g = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
                acc = pin(jax.tree.map(lambda a, b: a + b.astype(jnp.float32), acc, g))
                return acc, (l, met)

            gsum, (ls, mets) = jax.lax.scan(body, g0, mbs)
            grads = jax.tree.map(lambda g: g / grad_accum, gsum)
            loss = jnp.mean(ls)
            metrics = jax.tree.map(lambda x: jnp.mean(x, axis=0), mets)
        new_params, new_opt, om = adamw_update(params, grads, opt_state, oc)
        return new_params, new_opt, {**metrics, **om, "loss": loss}

    return train_step


# ---------------------------------------------------------------------------
# Serving: GLASS prefill (stats -> fusion -> shard-balanced compaction)
# ---------------------------------------------------------------------------


def _ffn_width(cfg) -> int:
    return cfg.d_ff


def make_glass_prefill(
    model: Model,
    gcfg: GlassConfig,
    max_len: int,
    mesh: Optional[Mesh] = None,
    model_shards: int = 1,
):
    """Returns prefill(params, inputs, global_prior) ->
    (last_logits, cache, compact_ffn).

    With a mesh, selection is shard-balanced over the model axis and the
    weight gather runs shard-locally under shard_map (no collectives); on a
    single device it falls back to the exact global top-k."""
    cfg = model.cfg
    m_width = _ffn_width(cfg)

    def prefill(params, inputs, global_prior):
        logits, cache, stats = model.prefill(params, inputs, max_len)
        local = finalize(stats)
        if local.ndim == 1:
            local = local[None]
        prior = global_prior if global_prior.ndim == local.ndim else global_prior[None]
        scores = glass_scores(local, prior, gcfg.lam)
        k = gcfg.k_of(scores.shape[-1])
        if model_shards > 1:
            idx, _ = select_shard_balanced(scores, k, model_shards)
            idx_local = to_local_indices(idx, scores.shape[-1], model_shards)
        else:
            from ..core.fusion import select_topk

            idx, _ = select_topk(scores, k)
            idx_local = idx[..., None, :]  # (L, 1, k)

        if mesh is not None and model_shards > 1:
            if cfg.family == "moe":
                compact = compact_moe_sharded(mesh, params["layers"]["moe"], idx_local)
            elif cfg.family == "ssm":
                compact = compact_rwkv_cm_sharded(mesh, params["layers"]["cm"], idx_local)
            elif cfg.family == "hybrid":
                ffn = {k2: v[None] for k2, v in params["shared_attn"]["ffn"].items()}
                compact = compact_ffn_sharded(mesh, ffn, idx_local)
                compact = {k2: v[0] for k2, v in compact.items()}
            elif cfg.is_encoder_decoder:
                compact = compact_ffn_sharded(mesh, params["dec_layers"]["ffn"], idx_local)
            else:
                compact = compact_ffn_sharded(mesh, params["layers"]["ffn"], idx_local)
        else:
            from ..core.glass import compact_params as _cp

            compact = _cp(model, params, idx)
        last = logits[:, -1]
        return last, cache, compact

    return prefill


# ---------------------------------------------------------------------------
# Serving: decode step (greedy for the dry-run; engine uses sampling)
# ---------------------------------------------------------------------------


def make_decode_step(model: Model, greedy: bool = True, attn_mode: str = "gather"):
    """decode(params, cache, token, cache_len) -> (next_token, cache).

    For GLASS steady-state decode, pass params whose FFN weights are the
    compact ones (built by glass-prefill) — the step code is identical.
    ``attn_mode="paged_pallas"`` runs the fused paged-attention kernel on
    the paged cache layout instead of the XLA gather reference."""

    def decode(params, cache, token, cache_len):
        logits, cache = model.decode_step(
            params, token, cache, cache_len, attn_mode=attn_mode
        )
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        return nxt, cache

    return decode


def make_decode_step_sampled(model: Model, attn_mode: str = "gather"):
    """Per-slot sampled decode with the counter-based positional PRNG —
    the jittable program behind the per-request ``SamplingParams`` API.

    Returns ``decode(params, cache, token, cache_len, seeds, pos,
    temperature, top_k, greedy_mask[, top_p, min_p]) ->
    (next_token (B, 1), cache)``: row ``b`` draws token ``pos[b]`` of
    request ``seeds[b]``'s stream (``sample_positional`` keys on exactly
    that pair, so replaying a position regenerates the same token), or the
    argmax where ``greedy_mask`` is set.  All sampling inputs are traced
    (B,) vectors — one compiled program serves any mix of greedy and
    sampled requests.  ``top_p`` / ``min_p`` are optional trailing (B,)
    vectors (nucleus and min-p filtering; omitted = disabled) so existing
    9-argument callers lower the identical program as before."""
    from ..serve.sampling import sample_positional

    def decode(params, cache, token, cache_len, seeds, pos, temperature,
               top_k, greedy_mask, top_p=None, min_p=None):
        logits, cache = model.decode_step(
            params, token, cache, cache_len, attn_mode=attn_mode
        )
        lg = logits[:, -1].astype(jnp.float32)
        g = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        s = sample_positional(lg, seeds, pos, temperature, top_k,
                              top_p=top_p, min_p=min_p)
        nxt = jnp.where(greedy_mask, g, s).astype(jnp.int32)[:, None]
        return nxt, cache

    return decode


def make_decode_step_masked(model: Model, attn_mode: str = "gather"):
    """Masked decode (no compaction): GLASS as a multiplier mask — the jnp
    reference for the block-sparse kernel path."""

    def decode(params, cache, token, cache_len, ffn_masks):
        logits, cache = model.decode_step(
            params, token, cache, cache_len, ffn_masks=ffn_masks,
            attn_mode=attn_mode,
        )
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        return nxt, cache

    return decode


def make_decode_step_block_sparse(model: Model, block_size: int,
                                  attn_mode: str = "gather"):
    """Block-sparse decode: per-request active FFN block ids (from
    ``GlassConfig(selection="block")``) feed the pallas ``glass_ffn`` kernel
    directly — weights stay resident, only active (d x block_size) tiles are
    streamed.  ``block_idx`` is (L, nb_keep) shared or (L, B, nb_keep)
    per-slot (continuous batching); per-slot lists run as one grid over
    their union, each kept tile streamed once for all rows."""

    def decode(params, cache, token, cache_len, block_idx):
        logits, cache = model.decode_step(
            params, token, cache, cache_len,
            ffn_block_idx=block_idx, ffn_block_size=block_size,
            attn_mode=attn_mode,
        )
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        return nxt, cache

    return decode


def make_verify_step(model: Model, glass_mode: Optional[str] = None,
                     block_size: int = 128, parallel: bool = False,
                     attn_mode: str = "gather"):
    """Speculative-verify step builder: the TARGET tier checks all ``T``
    candidate positions of a draft in one jittable program.

    Returns ``verify(params, cache, tokens, cache_len[, tier])`` ->
    ``(greedy (B, T), cache)`` where ``tokens`` is ``[pending, d_1..d_k]``
    and ``greedy[:, j]`` is the target verdict ``t_j`` (accept the longest
    prefix with ``d_{j+1} == t_j``).  The ``tier`` argument matches
    ``glass_mode``: ``None`` serves dense, ``"masked"`` takes per-slot
    ``ffn_masks``, ``"compact"`` takes a compact-weight pytree,
    ``"block_sparse"`` takes active FFN block ids.  The DRAFT pass needs no
    new builder — the existing decode-step builders accept the draft
    tier's rows/masks unchanged (tiers share every layout, only ``k``
    differs).

    ``parallel=True`` lowers the one-forward T-position verify (attention
    families only — see :meth:`Model.verify_steps`); the verdicts and KV
    rows stay BIT-identical to the sequential scan."""
    if glass_mode not in (None, "masked", "compact", "block_sparse"):
        raise ValueError(glass_mode)
    common = dict(parallel=parallel, attn_mode=attn_mode)

    if glass_mode is None:
        def verify(params, cache, tokens, cache_len):
            return model.verify_steps(params, tokens, cache, cache_len, **common)

        return verify

    def verify_tiered(params, cache, tokens, cache_len, tier):
        kw = dict(common)
        if glass_mode == "masked":
            kw["ffn_masks"] = tier
        elif glass_mode == "compact":
            kw["compact_layers"] = tier
        else:
            kw["ffn_block_idx"] = tier
            kw["ffn_block_size"] = block_size
        return model.verify_steps(params, tokens, cache, cache_len, **kw)

    return verify_tiered


def make_chunked_prefill(model: Model, chunk_tokens: int,
                         attn_mode: str = "gather"):
    """Chunked-prefill step for the paged serving path: processes up to
    ``chunk_tokens`` prompt tokens against a paged cache + block table,
    returning merged-by-addition GLASS chunk stats (see
    ``Model.prefill_chunk``).  The dry-run lowers one chunk at the bound
    length; the engine jit-caches per observed (T, nb) signature."""

    def prefill_chunk(params, tokens, cache, cache_len, block_table):
        assert tokens.shape[1] <= chunk_tokens, (tokens.shape, chunk_tokens)
        return model.prefill_chunk(
            params, tokens, cache, cache_len, block_table=block_table,
            attn_mode=attn_mode,
        )

    return prefill_chunk


def make_resumed_prefill(model: Model, chunk_tokens: int,
                         attn_mode: str = "gather"):
    """Prefix-cache warm prefill: one chunk that CONTINUES a cached
    prefix's GLASS stat fold instead of starting a fresh one.

    Returns ``prefill_resumed(params, tokens, cache, cache_len, block_table,
    carry_stats) -> (logits, cache, merged_stats)`` where ``carry_stats``
    is a restored prefix-cache snapshot (the left-fold over the cached
    rows) and ``merged_stats = merge_stat_sums(carry, chunk)``.  Because
    the merge is the same addition the engine applies between cold chunks,
    lowering this program at the fork point reproduces the cold fold
    bit-for-bit — the jittable witness of the prefix-cache resume
    invariant, and what the dry-run lowers for warm-start serving."""
    from ..core.fusion import merge_stat_sums

    def prefill_resumed(params, tokens, cache, cache_len, block_table,
                        carry_stats):
        assert tokens.shape[1] <= chunk_tokens, (tokens.shape, chunk_tokens)
        logits, cache, stats = model.prefill_chunk(
            params, tokens, cache, cache_len, block_table=block_table,
            attn_mode=attn_mode,
        )
        return logits, cache, merge_stat_sums(carry_stats, stats)

    return prefill_resumed
