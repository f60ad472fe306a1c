"""Uniform model API over all families.

A ``Model`` bundles pure functions keyed off the config; params stay explicit
pytrees so pjit/shard_map wrap these functions directly.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import encdec, transformer
from .attention import init_cache as _init_kv_cache
from .common import ModelConfig


@dataclass(frozen=True)
class Model:
    cfg: ModelConfig

    # -- init ---------------------------------------------------------------
    def init(self, rng: jax.Array):
        if self.cfg.is_encoder_decoder:
            return encdec.init_encdec(rng, self.cfg)
        return transformer.init_lm(rng, self.cfg)

    # -- training -----------------------------------------------------------
    def loss(self, params, batch) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        if self.cfg.is_encoder_decoder:
            return encdec.encdec_loss(params, batch, self.cfg)
        return transformer.lm_loss(params, batch, self.cfg)

    # -- full-sequence logits (evaluation, KLD/PPL metrics) ------------------
    def logits(self, params, batch, **kw) -> jax.Array:
        if self.cfg.is_encoder_decoder:
            enc_out = encdec.encode(params, batch["frames"], self.cfg)
            out, _, _ = encdec.decode_full(params, batch["tokens"], enc_out, self.cfg, **kw)
            return out
        out, _, _, _ = transformer.forward(params, batch["tokens"], self.cfg, **kw)
        return out

    # -- forward with GLASS instrumentation ----------------------------------
    def logits_with_stats(self, params, batch):
        """Returns (logits, stats) — stats are per-layer A-signal sums."""
        if self.cfg.is_encoder_decoder:
            enc_out = encdec.encode(params, batch["frames"], self.cfg)
            out, stats, _ = encdec.decode_full(
                params, batch["tokens"], enc_out, self.cfg, collect_stats=True
            )
            return out, stats
        out, _, stats, _ = transformer.forward(
            params, batch["tokens"], self.cfg, collect_stats=True
        )
        return out, stats

    def loss_with_probes(self, params, probes, batch):
        """CE loss with additive zero probes on every FFN hidden vector.
        grad w.r.t. ``probes`` gives the per-token dL/dh for I-GLASS."""
        cfg = self.cfg
        if cfg.is_encoder_decoder:
            enc_out = encdec.encode(params, batch["frames"], cfg)
            logits, _, _ = encdec.decode_full(params, batch["tokens"], enc_out, cfg, probes=probes)
        else:
            logits, _, _, _ = transformer.forward(params, batch["tokens"], cfg, probes=probes)
        loss, _ = transformer.cross_entropy(logits, batch["labels"], batch.get("mask"))
        return loss

    def probe_zeros(self, batch_shape: Tuple[int, int]) -> jax.Array:
        """Zero probes (L, B, S, m) matching this config's FFN hidden width."""
        cfg = self.cfg
        B, S = batch_shape
        if cfg.family == "hybrid":
            raise NotImplementedError("hybrid probes: use shared-block stats instead")
        return jnp.zeros((cfg.n_layers, B, S, cfg.d_ff), jnp.float32)

    # -- serving ------------------------------------------------------------
    def prefill(self, params, inputs: Dict[str, jax.Array], max_len: int):
        """inputs: {"tokens": (B,S)} (+ "frames" for enc-dec).
        Returns (logits, cache, local_stats)."""
        cfg = self.cfg
        if cfg.is_encoder_decoder:
            return encdec.encdec_prefill(params, inputs["frames"], inputs["tokens"], cfg, max_len)
        if cfg.family == "ssm":
            logits, _, stats, cache = transformer.rwkv_forward(
                params, inputs["tokens"], cfg, collect_stats=True, return_cache=True
            )
            return logits, cache, stats
        if cfg.family == "hybrid":
            return transformer.hybrid_prefill(params, inputs["tokens"], cfg, max_len)
        return transformer.dense_prefill(params, inputs["tokens"], cfg, max_len)

    def prefill_chunk(
        self,
        params,
        tokens: jax.Array,  # (B, T): the next T prompt tokens
        cache,  # paged leaves = whole block arenas; state leaves = this request's rows
        cache_len: jax.Array,  # (B,) int32 tokens already processed
        *,
        block_table=None,  # (B, nb) int32; None for pure-state families (ssm)
        attn_mode: str = "gather",  # "paged_pallas" = fused paged-attention kernel
    ):
        """Incremental prefill: extend the cache by T prompt tokens.

        Returns (logits (B,T,V), cache, chunk_stats); chunk_stats are sums
        over this chunk's tokens and merge across chunks by addition, so the
        finalized GLASS local signal is the same as single-shot prefill."""
        cfg = self.cfg
        if cfg.is_encoder_decoder:
            raise NotImplementedError("chunked prefill targets decoder LMs")
        if cfg.family == "ssm":
            return transformer.rwkv_prefill_chunk(params, tokens, cfg, cache)
        if cfg.family == "hybrid":
            return transformer.hybrid_prefill_chunk(
                params, tokens, cfg, cache, block_table, cache_len,
                attn_mode=attn_mode,
            )
        return transformer.dense_prefill_chunk(
            params, tokens, cfg, cache, block_table, cache_len,
            attn_mode=attn_mode,
        )

    def decode_step(
        self,
        params,
        token: jax.Array,  # (B, 1); dense families accept (B, T) for the
        # parallel multi-token verify / forced replay
        cache,
        cache_len: jax.Array,  # scalar, or (B,) per-slot lengths (continuous batching)
        *,
        ffn_masks=None,  # shared (L, m), or per-slot with an extra B axis after L
        compact_layers=None,  # compact FFN pytree; per-slot adds a B axis after L
        block_table=None,  # (B, nb) int32: paged-KV serving (BlockPool)
        ffn_block_idx=None,  # active FFN block ids -> block-sparse pallas kernel
        ffn_block_size: int = 128,
        ffn_block_scale=None,  # per-(row, tile) f32 multipliers (per-request density)
        ffn_block_count=None,  # (L,) int32: ffn_block_idx is the slots' union
        # (kernels.ops.ffn_union), computed once by a multi-step caller
        attn_mode: str = "gather",  # "paged_pallas" = fused paged-attention kernel
    ):
        cfg = self.cfg
        if ffn_block_idx is not None and cfg.family not in ("dense", "vlm"):
            raise NotImplementedError("block-sparse decode targets dense-FFN families")
        if cfg.is_encoder_decoder:
            return encdec.encdec_decode_step(
                params, token, cache, cache_len, cfg, ffn_masks=ffn_masks, compact_layers=compact_layers
            )
        if cfg.family == "ssm":
            return transformer.rwkv_decode_step(
                params, token, cache, cache_len, cfg, ffn_masks=ffn_masks, compact_layers=compact_layers
            )
        if cfg.family == "hybrid":
            # mask layouts are rank-distinguished (never shape-sniffed):
            # (m,) shared | (1, m) MaskSet layout -> shared | (1, B, m)
            # per-slot arena -> (B, m)
            mask = ffn_masks
            if mask is not None and mask.ndim > 1:
                mask = mask[0]
            return transformer.hybrid_decode_step(
                params, token, cache, cache_len, cfg, shared_mask=mask,
                shared_compact=compact_layers, block_table=block_table,
                attn_mode=attn_mode,
            )
        return transformer.dense_decode_step(
            params, token, cache, cache_len, cfg, ffn_masks=ffn_masks,
            compact_layers=compact_layers, block_table=block_table,
            ffn_block_idx=ffn_block_idx, ffn_block_size=ffn_block_size,
            ffn_block_scale=ffn_block_scale, ffn_block_count=ffn_block_count,
            attn_mode=attn_mode,
        )

    def verify_steps(
        self,
        params,
        tokens: jax.Array,  # (B, T): the T candidate feeds, in order
        cache,
        cache_len,  # scalar, or (B,) per-slot lengths
        *,
        ffn_masks=None,
        compact_layers=None,
        block_table=None,
        ffn_block_idx=None,
        ffn_block_size: int = 128,
        ffn_block_scale=None,  # per-(row, tile) f32 multipliers (per-request density)
        seeds=None,  # (B,) int32: per-slot sampling seeds -> sampled verdicts
        pos0=None,  # (B,) int32 generated position of the FIRST verdict
        temperature=None,  # (B,) f32
        top_k=None,  # (B,) int32
        greedy_mask=None,  # (B,) bool: rows that verdict by argmax regardless
        parallel: bool = False,  # ONE T-token forward instead of the scan
        attn_mode: str = "gather",
    ):
        """Multi-token verification: feed ``tokens[:, j]`` sequentially
        through :meth:`decode_step` inside ONE jitted program (unrolled —
        see the loop comment below for why not ``lax.scan``), returning
        each position's verdict token and the advanced cache.

        This is the model-level primitive behind self-speculative decoding:
        feed ``[pending, d_1 .. d_k]`` under the TARGET tier's masks and
        read the verdict ``t_j`` at every position (accept the longest
        prefix with ``d_{j+1} == t_j``).  It runs the SAME single-token
        decode body the serving engines run, so KV rows, recurrent state,
        and logits are BIT-identical to ``T`` individual decode steps — the
        property the speculative state-invariant suite relies on for exact
        rollback.

        ``parallel=True`` is the one-forward path over all T positions that
        the sequential scan deferred: every feed is already known (they are
        all forced), so attention-backed families run ONE ``decode_step``
        with ``tokens (B, T)`` and the causal intra-chunk mask, and read a
        verdict per position.  The paged-pallas kernel keeps each query's
        op graph identical to a T = 1 tick (query axis on the kernel grid),
        so KV rows and verdicts stay BIT-identical to the scan — the
        state-invariant suite asserts it.  Recurrent families (ssm /
        hybrid) refuse: a chunkwise-parallel state update is a different
        reduction order than T sequential updates, which would break exact
        rollback.

        The verdict is the greedy argmax by default.  With ``seeds``/
        ``pos0``/``temperature``/``top_k`` given, it is the **counter-based
        positional sample** from the same pre-override logits
        (:func:`repro.serve.sampling.sample_positional` keyed on
        ``(seed, pos0 + j)``) — a pure function of (seed, position,
        logits), so a draft/verify pair under sampling is exactly as
        replayable as under greedy; ``greedy_mask`` rows keep the argmax
        verdict (mixed batches).

        Returns ``(verdicts (B, T) int32, cache)``.
        """
        ffn_block_count = None
        if ffn_block_idx is not None and ffn_block_idx.ndim == 3:
            # per-slot lists: one union for every position (decode_step)
            from ..kernels.ops import ffn_union

            ffn_block_idx, ffn_block_count, ffn_block_scale = ffn_union(
                ffn_block_idx, ffn_block_scale, n_tiles=self.cfg.d_ff // ffn_block_size
            )
        kw = dict(
            ffn_masks=ffn_masks, compact_layers=compact_layers,
            block_table=block_table, ffn_block_idx=ffn_block_idx,
            ffn_block_size=ffn_block_size, ffn_block_scale=ffn_block_scale,
            ffn_block_count=ffn_block_count, attn_mode=attn_mode,
        )
        cache_len = jnp.asarray(cache_len, jnp.int32)
        sampled = seeds is not None
        if sampled:
            from ..serve.sampling import sample_positional

            pos0 = jnp.asarray(pos0, jnp.int32)
            if greedy_mask is None:
                greedy_mask = jnp.zeros(seeds.shape, bool)

        if parallel:
            if self.cfg.family not in ("dense", "moe", "vlm"):
                raise NotImplementedError(
                    "parallel verify targets attention-backed families; "
                    "recurrent state must advance token-by-token to stay "
                    "bit-identical to sequential decode"
                )
            logits, cache = self.decode_step(params, tokens, cache, cache_len, **kw)
            lg = logits.astype(jnp.float32)  # (B, T, V)
            g = jnp.argmax(lg, axis=-1).astype(jnp.int32)
            if sampled:
                B, T = tokens.shape
                pos = pos0[:, None] + jnp.arange(T, dtype=jnp.int32)[None]
                rep = lambda a: jnp.repeat(a, T, axis=0)
                s = sample_positional(
                    lg.reshape(B * T, -1), rep(seeds), pos.reshape(-1),
                    rep(temperature), rep(top_k),
                ).reshape(B, T)
                g = jnp.where(greedy_mask[:, None], g, s)
            return g, cache

        # UNROLLED python loop, not lax.scan: XLA fuses a while-loop body
        # differently than the same ops inlined, and the two disagree at
        # the last ulp deep in the layer stack — which would break the
        # bit-equality contract between this path and ``parallel=True``
        # (and between this path and T individual decode_step programs).
        # T = spec_k + 1 stays small, so the unroll cost is bounded.
        verdicts = []
        for j in range(tokens.shape[1]):
            logits, cache = self.decode_step(
                params, tokens[:, j:j + 1], cache, cache_len, **kw
            )
            cache_len = cache_len + 1
            lg = logits[:, -1].astype(jnp.float32)
            g = jnp.argmax(lg, axis=-1).astype(jnp.int32)
            if sampled:
                s = sample_positional(lg, seeds, pos0 + j, temperature, top_k)
                g = jnp.where(greedy_mask, g, s)
            verdicts.append(g)
        return jnp.stack(verdicts, axis=1), cache

    def init_cache(self, batch: int, max_len: int):
        cfg = self.cfg
        dt = cfg.compute_dtype
        if cfg.is_encoder_decoder:
            raise NotImplementedError("enc-dec cache comes from prefill")
        if cfg.family == "ssm":
            from .rwkv6 import rwkv_heads

            H, P = rwkv_heads(cfg), cfg.rwkv_headdim
            return {
                "state": jnp.zeros((cfg.n_layers, batch, H, P, P), jnp.float32),
                "shift_tm": jnp.zeros((cfg.n_layers, batch, cfg.d_model), dt),
                "shift_cm": jnp.zeros((cfg.n_layers, batch, cfg.d_model), dt),
            }
        if cfg.family == "hybrid":
            return transformer.init_hybrid_cache(cfg, batch, max_len)
        return _init_kv_cache(cfg, batch, max_len, cfg.n_layers, dt)


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
