"""Decoder-only LM assembly for the dense / moe / vlm / ssm(rwkv6) / hybrid
(zamba2) families.

Layer parameters are stacked along a leading L axis and the stack is executed
with ``lax.scan`` (keeps HLO size O(1) in depth — essential for the 80-layer
dry-runs).  GLASS plumbing rides the scan:

  * ``ffn_masks``  (L, m) or (L, E, f)  — multiplier on FFN hidden units
  * ``probes``     (L, B, S, m)          — zeros; grad w.r.t. them = dL/dh
  * ``collect_stats``                    — emit per-layer |h|/||h||_2 sums

Gemma2-style local/global alternation is data-driven: a per-layer int32
``window`` rides the scan, so one body serves both layer kinds.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..sharding.ctx import constrain
from . import rwkv6 as rk
from .attention import (
    attention_decode,
    attention_decode_paged,
    attention_forward,
    init_attention,
    init_cache,
    write_cache_prefill,
)
from .common import (
    ModelConfig,
    dense_init,
    embed_init,
    layer_norm,
    maybe_remat,
    rms_norm,
    softcap,
)
from .ffn import ffn_forward, ffn_forward_with_stats, init_ffn
from .mamba2 import (
    init_mamba2,
    mamba2_decode,
    mamba2_forward,
    mamba_dims,
)
from .moe import init_moe, moe_forward
from .rope import mrope_positions_text, positions_default

GLOBAL_WINDOW = np.int32(2**30)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _init_dense_layer(key, cfg: ModelConfig, dtype) -> dict:
    ks = jax.random.split(key, 2)
    d = cfg.d_model
    p = {
        "attn": init_attention(ks[0], cfg, dtype),
        "ln1": jnp.zeros((d,), dtype) if cfg.sandwich_norms else jnp.ones((d,), dtype),
        "ln2": jnp.zeros((d,), dtype) if cfg.sandwich_norms else jnp.ones((d,), dtype),
    }
    if cfg.family == "moe":
        p["moe"] = init_moe(ks[1], cfg, dtype)
    else:
        p["ffn"] = init_ffn(ks[1], cfg, dtype)
    if cfg.sandwich_norms:
        p["ln1_post"] = jnp.zeros((d,), dtype)
        p["ln2_post"] = jnp.zeros((d,), dtype)
    return p


def _init_rwkv_layer(key, cfg: ModelConfig, dtype) -> dict:
    ks = jax.random.split(key, 2)
    d = cfg.d_model
    return {
        "tm": rk.init_time_mix(ks[0], cfg, dtype),
        "cm": rk.init_channel_mix(ks[1], cfg, dtype),
        "ln1_w": jnp.ones((d,), dtype),
        "ln1_b": jnp.zeros((d,), dtype),
        "ln2_w": jnp.ones((d,), dtype),
        "ln2_b": jnp.zeros((d,), dtype),
    }


def _init_mamba_layer(key, cfg: ModelConfig, dtype) -> dict:
    return {
        "mixer": init_mamba2(key, cfg, dtype),
        "ln": jnp.ones((cfg.d_model,), dtype),
    }


def hybrid_layout(cfg: ModelConfig) -> Tuple[int, int, int]:
    """(n_groups, group_size, n_tail) — groups of mamba layers, each followed
    by one shared-attention-block invocation; tail mamba layers at the end."""
    g = cfg.attn_every
    n_groups = cfg.n_layers // g
    return n_groups, g, cfg.n_layers - n_groups * g


def init_lm(key, cfg: ModelConfig) -> Dict[str, Any]:
    dtype = cfg.compute_dtype
    ks = jax.random.split(key, 8)
    params: Dict[str, Any] = {"embed": embed_init(ks[0], (cfg.vocab_size, cfg.d_model), dtype)}

    if cfg.family in ("dense", "moe", "vlm"):
        lkeys = jnp.stack(jax.random.split(ks[1], cfg.n_layers))
        params["layers"] = jax.vmap(lambda k: _init_dense_layer(k, cfg, dtype))(lkeys)
        params["final_norm"] = (
            jnp.zeros((cfg.d_model,), dtype) if cfg.sandwich_norms else jnp.ones((cfg.d_model,), dtype)
        )
    elif cfg.family == "ssm":  # rwkv6
        lkeys = jnp.stack(jax.random.split(ks[1], cfg.n_layers))
        params["layers"] = jax.vmap(lambda k: _init_rwkv_layer(k, cfg, dtype))(lkeys)
        params["ln0_w"] = jnp.ones((cfg.d_model,), dtype)
        params["ln0_b"] = jnp.zeros((cfg.d_model,), dtype)
        params["final_norm"] = jnp.ones((cfg.d_model,), dtype)
        params["final_norm_b"] = jnp.zeros((cfg.d_model,), dtype)
    elif cfg.family == "hybrid":  # zamba2
        n_groups, g, n_tail = hybrid_layout(cfg)
        gkeys = jax.random.split(ks[1], n_groups * g).reshape(n_groups, g)
        params["layers"] = jax.vmap(jax.vmap(lambda k: _init_mamba_layer(k, cfg, dtype)))(gkeys)
        if n_tail:
            tkeys = jnp.stack(jax.random.split(ks[2], n_tail))
            params["tail"] = jax.vmap(lambda k: _init_mamba_layer(k, cfg, dtype))(tkeys)
        params["shared_attn"] = _init_dense_layer(ks[3], cfg.replace(family="dense"), dtype)
        params["final_norm"] = jnp.ones((cfg.d_model,), dtype)
    else:
        raise ValueError(cfg.family)

    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(ks[4], (cfg.d_model, cfg.vocab_size), dtype)
    return params


# ---------------------------------------------------------------------------
# Per-layer windows (gemma2 local/global alternation)
# ---------------------------------------------------------------------------


def layer_windows(cfg: ModelConfig) -> jax.Array:
    if cfg.attn_pattern == "local_global" and cfg.sliding_window:
        w = [cfg.sliding_window if i % 2 == 0 else GLOBAL_WINDOW for i in range(cfg.n_layers)]
    elif cfg.sliding_window:
        w = [cfg.sliding_window] * cfg.n_layers
    else:
        w = [GLOBAL_WINDOW] * cfg.n_layers
    return jnp.asarray(w, jnp.int32)


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------


def embed_tokens(params, tokens, cfg: ModelConfig):
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.embed_scale:
        x = x * jnp.asarray(np.sqrt(cfg.d_model), x.dtype)
    return x


def lm_logits(params, x, cfg: ModelConfig):
    if cfg.tie_embeddings:
        logits = x @ params["embed"].T
    else:
        logits = x @ params["lm_head"]
    return constrain(softcap(logits, cfg.logit_softcap), "logits")


def cross_entropy(
    logits: jax.Array,  # (..., V) any float dtype
    labels: jax.Array,  # (...,) int
    mask: Optional[jax.Array] = None,  # (...,) float
) -> Tuple[jax.Array, jax.Array]:
    """Mean CE over masked positions; stable f32 logsumexp. Returns (loss, n)."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    lab = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - lab
    if mask is not None:
        m = mask.astype(jnp.float32)
        n = jnp.maximum(jnp.sum(m), 1.0)
        return jnp.sum(nll * m) / n, n
    return jnp.mean(nll), jnp.asarray(float(nll.size), jnp.float32)


# ---------------------------------------------------------------------------
# Dense / MoE / VLM forward (full sequence)
# ---------------------------------------------------------------------------


def _dense_block(
    x,
    lp,
    cfg: ModelConfig,
    *,
    positions,
    window,
    mask_l=None,
    probe_l=None,
    collect_stats=False,
    stats_mask=None,
    return_kv=False,
):
    plus_one = cfg.sandwich_norms  # gemma-style (1+w) rmsnorm
    h = rms_norm(x, lp["ln1"], cfg.norm_eps, plus_one)
    attn_out = attention_forward(
        lp["attn"], h, cfg, positions=positions, window=window, return_kv=return_kv
    )
    kv = None
    if return_kv:
        attn_out, kv = attn_out
    if cfg.sandwich_norms:
        attn_out = rms_norm(attn_out, lp["ln1_post"], cfg.norm_eps, True)
    x = x + attn_out
    h2 = rms_norm(x, lp["ln2"], cfg.norm_eps, plus_one)
    aux = jnp.float32(0.0)
    stats = None
    if cfg.family == "moe":
        y, aux, stats = moe_forward(
            lp["moe"], h2, cfg, mask=mask_l, collect_stats=collect_stats, stats_mask=stats_mask
        )
    elif collect_stats:
        y, stats = ffn_forward_with_stats(lp["ffn"], h2, cfg, token_mask=stats_mask)
    else:
        y = ffn_forward(lp["ffn"], h2, cfg, mask=mask_l, probe=probe_l)
    if cfg.sandwich_norms:
        y = rms_norm(y, lp["ln2_post"], cfg.norm_eps, True)
    x = constrain(x + y, "act_btd")
    return x, aux, stats, kv


def dense_forward(
    params,
    tokens,
    cfg: ModelConfig,
    *,
    ffn_masks=None,  # (L, m) or (L, E, f)
    probes=None,  # (L, B, S, m)
    collect_stats: bool = False,
    stats_mask=None,  # (B, S) float: restrict stats to these positions
    return_cache: bool = False,
    positions=None,
):
    """Full-sequence forward. Returns (logits, aux, stats, kv_stack)."""
    B, S = tokens.shape
    x = constrain(embed_tokens(params, tokens, cfg), "act_btd")
    if positions is None:
        positions = (
            mrope_positions_text(B, S) if cfg.rope_type == "mrope" else positions_default(B, S)
        )
    windows = layer_windows(cfg)

    def body(carry, xs):
        x = carry
        lp, window, mask_l, probe_l = xs
        x, aux, stats, kv = _dense_block(
            x,
            lp,
            cfg,
            positions=positions,
            window=window,
            mask_l=mask_l,
            probe_l=probe_l,
            collect_stats=collect_stats,
            stats_mask=stats_mask,
            return_kv=return_cache,
        )
        ys = (aux, stats, kv)
        return x, ys

    L = cfg.n_layers
    mask_xs = ffn_masks if ffn_masks is not None else jnp.zeros((L, 0))
    probe_xs = probes if probes is not None else jnp.zeros((L, 0))
    # normalize "absent" to None inside body via static flags:
    have_mask = ffn_masks is not None
    have_probe = probes is not None

    def body_wrap(carry, xs):
        lp, window, mask_l, probe_l = xs
        return body(
            carry,
            (lp, window, mask_l if have_mask else None, probe_l if have_probe else None),
        )

    scan_body = maybe_remat(body_wrap, cfg)
    x, (auxs, stats, kvs) = jax.lax.scan(
        scan_body, x, (params["layers"], windows, mask_xs, probe_xs)
    )
    x = rms_norm(x, params["final_norm"], cfg.norm_eps, cfg.sandwich_norms)
    logits = lm_logits(params, x, cfg)
    return logits, jnp.sum(auxs) if auxs is not None else 0.0, stats, kvs


def dense_prefill(params, tokens, cfg: ModelConfig, max_len: int):
    """Prefill: logits + populated cache + GLASS local stats."""
    logits, _, stats, kvs = dense_forward(
        params, tokens, cfg, collect_stats=True, return_cache=True
    )
    B, S = tokens.shape
    cache = init_cache(cfg, B, max_len, cfg.n_layers, cfg.compute_dtype)
    k, v = kvs  # (L, B, S, K, hd)
    cache["k"], cache["v"] = jax.vmap(write_cache_prefill)(cache["k"], cache["v"], k, v)
    return logits, cache, stats


def dense_prefill_chunk(
    params, tokens, cfg: ModelConfig, cache, block_table, cache_len,
    attn_mode: str = "gather",
):
    """One chunk of an incremental (paged) prefill for dense/moe/vlm.

    tokens (B, T) continue a prompt whose first ``cache_len`` tokens already
    live in the paged cache {"k","v": (L, num_blocks, bs, K, hd)} through
    ``block_table`` (B, nb).  Positions are absolute (``cache_len + t``), so
    RoPE and sliding windows match the single-shot prefill exactly.  Returns
    (logits (B,T,V), cache, chunk_stats) — stats are *sums* over this
    chunk's tokens and merge across chunks by addition (importance.merge).
    """
    x = constrain(embed_tokens(params, tokens, cfg), "act_btd")
    windows = layer_windows(cfg)
    plus_one = cfg.sandwich_norms

    def body(x, xs):
        lp, ck, cv, window = xs
        h = rms_norm(x, lp["ln1"], cfg.norm_eps, plus_one)
        a, ck, cv = attention_decode_paged(
            lp["attn"], h, cfg, cache_k=ck, cache_v=cv,
            block_table=block_table, cache_len=cache_len, window=window,
            attn_mode=attn_mode,
        )
        if cfg.sandwich_norms:
            a = rms_norm(a, lp["ln1_post"], cfg.norm_eps, True)
        x = x + a
        h2 = rms_norm(x, lp["ln2"], cfg.norm_eps, plus_one)
        if cfg.family == "moe":
            y, _, stats = moe_forward(lp["moe"], h2, cfg, collect_stats=True)
        else:
            y, stats = ffn_forward_with_stats(lp["ffn"], h2, cfg)
        if cfg.sandwich_norms:
            y = rms_norm(y, lp["ln2_post"], cfg.norm_eps, True)
        x = constrain(x + y, "act_btd")
        return x, (ck, cv, stats)

    x, (ck, cv, stats) = jax.lax.scan(
        body, x, (params["layers"], cache["k"], cache["v"], windows)
    )
    x = rms_norm(x, params["final_norm"], cfg.norm_eps, cfg.sandwich_norms)
    logits = lm_logits(params, x, cfg)
    return logits, {"k": ck, "v": cv}, stats


def dense_decode_step(
    params,
    token,  # (B, T) int32: T = 1 decode tick, T > 1 parallel multi-token verify
    cache,  # {"k","v": (L,B,Smax,K,hd)}; paged: (L,num_blocks,bs,K,hd)
    cache_len,  # int32: scalar, or (B,) per-slot lengths (continuous batching)
    cfg: ModelConfig,
    *,
    ffn_masks=None,  # (L, m) shared, or (L, B, m) per-slot; MoE adds an E axis
    compact_layers=None,  # stacked compact FFN params (L-leading) replacing lp["ffn"];
    # per-slot serving stacks an extra slot axis after L, e.g. w_up (L, B, d, k)
    block_table=None,  # (B, nb) int32: paged-KV block table (BlockPool serving)
    ffn_block_idx=None,  # (L, nb_keep) shared or (L, B, nb_keep) per-slot active
    # FFN block ids -> block-sparse pallas kernel instead of dense masked matmuls
    ffn_block_size: int = 128,
    ffn_block_scale=None,  # f32 tile multipliers matching ffn_block_idx:
    # (L, nb_keep) shared or (L, B, nb_keep) per-slot (per-request density
    # nested inside the capacity-tier lists; 0.0 exactly zeroes a padding
    # tile); (L, n_tiles, B) per-row with ffn_block_count.  None = all 1.0
    ffn_block_count=None,  # (L,) int32: ffn_block_idx (L, n_tiles) is already
    # the union of the slots' lists (kernels.ops.ffn_union) with this many
    # real ids, and ffn_block_scale its (L, n_tiles, B) per-row table
    attn_mode: str = "gather",
):
    """One decode step across all layers (scan). Returns (logits, new_cache).

    ``T > 1`` tokens run every position through one forward with the causal
    intra-chunk attention mask — the parallel speculative verify.

    Per-slot block lists run as ONE shared-list ``glass_ffn`` grid over
    their union per layer, each row under its own scale column, so a tile
    that several rows keep is read once per step.  A caller that decodes
    several steps from the same lists computes the union once
    (``ffn_block_count``); otherwise it is computed here, over every row.
    With ``T > 1`` the grid takes all ``B*T`` rows at once, each slot's
    scale column repeated per token.
    """
    x = embed_tokens(params, token, cfg)
    windows = layer_windows(cfg)
    plus_one = cfg.sandwich_norms
    if ffn_block_idx is not None and cfg.family == "moe":
        raise NotImplementedError("block-sparse decode targets dense-FFN families")

    def body(x, xs):
        lp, ck, cv, window, mask_l, comp_l, bidx_l, bscale_l, cnt_l = xs
        h = rms_norm(x, lp["ln1"], cfg.norm_eps, plus_one)
        if block_table is not None:
            a, ck, cv = attention_decode_paged(
                lp["attn"], h, cfg, cache_k=ck, cache_v=cv,
                block_table=block_table, cache_len=cache_len, window=window,
                attn_mode=attn_mode,
            )
        else:
            a, ck, cv = attention_decode(
                lp["attn"], h, cfg, cache_k=ck, cache_v=cv, cache_len=cache_len, window=window
            )
        if cfg.sandwich_norms:
            a = rms_norm(a, lp["ln1_post"], cfg.norm_eps, True)
        x = x + a
        h2 = rms_norm(x, lp["ln2"], cfg.norm_eps, plus_one)
        if cfg.family == "moe":
            mp = comp_l if comp_l is not None else lp["moe"]
            y, _, _ = moe_forward(mp, h2, cfg, mask=mask_l)
        elif bidx_l is not None:
            from ..kernels.ops import glass_ffn

            fp = lp["ffn"]
            B_, T_ = h2.shape[0], h2.shape[1]
            bsc = bscale_l
            if cnt_l is not None and T_ > 1:  # the union's (n_tiles, B) table
                bsc = jnp.repeat(bscale_l, T_, axis=1)
            y32 = glass_ffn(
                h2.reshape(B_ * T_, -1), fp["w_up"], fp["w_down"], bidx_l,
                fp.get("w_gate"), block_scale=bsc, n_active=cnt_l,
                act=cfg.ffn_act, block_size=ffn_block_size,
            )
            y = y32.astype(x.dtype).reshape(B_, T_, -1)
        else:
            fp = comp_l if comp_l is not None else lp["ffn"]
            if mask_l is not None and mask_l.ndim == 2:  # per-slot (B, m)
                mask_l = mask_l[:, None, :]
            y = ffn_forward(fp, h2, cfg, mask=mask_l)
        if cfg.sandwich_norms:
            y = rms_norm(y, lp["ln2_post"], cfg.norm_eps, True)
        x = x + y
        return x, (ck, cv)

    L = cfg.n_layers
    if ffn_block_idx is not None and ffn_block_idx.ndim == 3:  # per-slot lists
        from ..kernels.ops import ffn_union

        ffn_block_idx, ffn_block_count, ffn_block_scale = ffn_union(
            ffn_block_idx, ffn_block_scale, n_tiles=cfg.d_ff // ffn_block_size
        )
    have_mask = ffn_masks is not None
    have_comp = compact_layers is not None
    have_bidx = ffn_block_idx is not None
    have_bscale = ffn_block_scale is not None
    have_cnt = ffn_block_count is not None
    mask_xs = ffn_masks if have_mask else jnp.zeros((L, 0))
    comp_xs = compact_layers if have_comp else jnp.zeros((L, 0))
    bidx_xs = ffn_block_idx if have_bidx else jnp.zeros((L, 0))
    bscale_xs = ffn_block_scale if have_bscale else jnp.zeros((L, 0))
    cnt_xs = ffn_block_count if have_cnt else jnp.zeros((L, 0))

    def body_wrap(x, xs):
        lp, ck, cv, window, mask_l, comp_l, bidx_l, bscale_l, cnt_l = xs
        return body(
            x,
            (lp, ck, cv, window, mask_l if have_mask else None,
             comp_l if have_comp else None, bidx_l if have_bidx else None,
             bscale_l if have_bscale else None, cnt_l if have_cnt else None),
        )

    x, (ck, cv) = jax.lax.scan(
        body_wrap, x,
        (params["layers"], cache["k"], cache["v"], windows, mask_xs, comp_xs,
         bidx_xs, bscale_xs, cnt_xs),
    )
    x = rms_norm(x, params["final_norm"], cfg.norm_eps, cfg.sandwich_norms)
    logits = lm_logits(params, x, cfg)
    return logits, {"k": ck, "v": cv}


# ---------------------------------------------------------------------------
# RWKV6 forward
# ---------------------------------------------------------------------------


def rwkv_forward(
    params,
    tokens,
    cfg: ModelConfig,
    *,
    ffn_masks=None,
    probes=None,
    collect_stats=False,
    stats_mask=None,
    return_cache=False,
):
    B, S = tokens.shape
    x = constrain(embed_tokens(params, tokens, cfg), "act_btd")
    x = layer_norm(x, params["ln0_w"], params["ln0_b"], cfg.norm_eps)
    L = cfg.n_layers
    have_mask = ffn_masks is not None
    have_probe = probes is not None
    mask_xs = ffn_masks if have_mask else jnp.zeros((L, 0))
    probe_xs = probes if have_probe else jnp.zeros((L, 0))

    def body(x, xs):
        lp, mask_l, probe_l = xs
        h = layer_norm(x, lp["ln1_w"], lp["ln1_b"], cfg.norm_eps)
        y, state, shift_tm = rk.time_mix_forward(lp["tm"], h, cfg)
        x = x + y
        h2 = layer_norm(x, lp["ln2_w"], lp["ln2_b"], cfg.norm_eps)
        y2, shift_cm, stats = rk.channel_mix_forward(
            lp["cm"],
            h2,
            cfg,
            mask=mask_l if have_mask else None,
            probe=probe_l if have_probe else None,
            collect_stats=collect_stats,
            stats_mask=stats_mask,
        )
        x = constrain(x + y2, "act_btd")
        return x, (stats, (state, shift_tm, shift_cm) if return_cache else None)

    scan_body = maybe_remat(body, cfg)
    x, (stats, cache_parts) = jax.lax.scan(scan_body, x, (params["layers"], mask_xs, probe_xs))
    x = layer_norm(x, params["final_norm"], params["final_norm_b"], cfg.norm_eps)
    logits = lm_logits(params, x, cfg)
    cache = None
    if return_cache:
        state, shift_tm, shift_cm = cache_parts
        cache = {"state": state, "shift_tm": shift_tm, "shift_cm": shift_cm}
    return logits, jnp.float32(0.0), stats, cache


def rwkv_decode_step(params, token, cache, cache_len, cfg: ModelConfig, *, ffn_masks=None, compact_layers=None):
    x = embed_tokens(params, token, cfg)
    x = layer_norm(x, params["ln0_w"], params["ln0_b"], cfg.norm_eps)
    L = cfg.n_layers
    have_mask = ffn_masks is not None
    have_comp = compact_layers is not None
    mask_xs = ffn_masks if have_mask else jnp.zeros((L, 0))
    comp_xs = compact_layers if have_comp else jnp.zeros((L, 0))

    def body(x, xs):
        lp, state, sh_tm, sh_cm, mask_l, comp_l = xs
        h = layer_norm(x, lp["ln1_w"], lp["ln1_b"], cfg.norm_eps)
        y, state, sh_tm = rk.time_mix_decode(lp["tm"], h, cfg, state=state, shift_prev=sh_tm)
        x = x + y
        h2 = layer_norm(x, lp["ln2_w"], lp["ln2_b"], cfg.norm_eps)
        cm = comp_l if have_comp else lp["cm"]
        if have_mask and mask_l.ndim == 2:  # per-slot (B, m)
            mask_l = mask_l[:, None, :]
        y2, sh_cm, _ = rk.channel_mix_forward(
            cm, h2, cfg, shift_prev=sh_cm, mask=mask_l if have_mask else None
        )
        x = x + y2
        return x, (state, sh_tm, sh_cm)

    def body_wrap(x, xs):
        lp, state, sh_tm, sh_cm, mask_l, comp_l = xs
        return body(
            x,
            (lp, state, sh_tm, sh_cm, mask_l if have_mask else None, comp_l if have_comp else None),
        )

    x, (state, sh_tm, sh_cm) = jax.lax.scan(
        body_wrap,
        x,
        (params["layers"], cache["state"], cache["shift_tm"], cache["shift_cm"], mask_xs, comp_xs),
    )
    x = layer_norm(x, params["final_norm"], params["final_norm_b"], cfg.norm_eps)
    return lm_logits(params, x, cfg), {"state": state, "shift_tm": sh_tm, "shift_cm": sh_cm}


def rwkv_prefill_chunk(params, tokens, cfg: ModelConfig, cache):
    """One chunk of an incremental rwkv6 prefill.

    The cache IS the recurrent state ({"state","shift_tm","shift_cm"}, rows
    for this request only), threaded through the chunkwise-parallel forward
    as initial carries; there are no KV rows to page.  Returns
    (logits (B,T,V), cache, chunk_stats)."""
    S = tokens.shape[1]
    x = constrain(embed_tokens(params, tokens, cfg), "act_btd")
    x = layer_norm(x, params["ln0_w"], params["ln0_b"], cfg.norm_eps)

    def body(x, xs):
        lp, st, sh_tm, sh_cm = xs
        h = layer_norm(x, lp["ln1_w"], lp["ln1_b"], cfg.norm_eps)
        # chunk=S: one wkv6 chunk per prefill chunk (T is engine-bounded, so
        # the intra-chunk quadratic term stays small)
        y, st, sh_tm = rk.time_mix_forward(lp["tm"], h, cfg, state=st, shift_prev=sh_tm, chunk=S)
        x = x + y
        h2 = layer_norm(x, lp["ln2_w"], lp["ln2_b"], cfg.norm_eps)
        y2, sh_cm, stats = rk.channel_mix_forward(
            lp["cm"], h2, cfg, shift_prev=sh_cm, collect_stats=True
        )
        x = constrain(x + y2, "act_btd")
        return x, (st, sh_tm, sh_cm, stats)

    x, (st, sh_tm, sh_cm, stats) = jax.lax.scan(
        body, x, (params["layers"], cache["state"], cache["shift_tm"], cache["shift_cm"])
    )
    x = layer_norm(x, params["final_norm"], params["final_norm_b"], cfg.norm_eps)
    logits = lm_logits(params, x, cfg)
    return logits, {"state": st, "shift_tm": sh_tm, "shift_cm": sh_cm}, stats


# ---------------------------------------------------------------------------
# Hybrid (zamba2): groups of mamba layers + shared attention block
# ---------------------------------------------------------------------------


def hybrid_forward(
    params,
    tokens,
    cfg: ModelConfig,
    *,
    shared_mask=None,  # (m,) mask for the shared block's FFN
    collect_stats=False,
    stats_mask=None,
    return_cache=False,
):
    B, S = tokens.shape
    n_groups, g, n_tail = hybrid_layout(cfg)
    x = embed_tokens(params, tokens, cfg)
    positions = positions_default(B, S)
    sp = params["shared_attn"]

    def mamba_layer(x, lp):
        h = rms_norm(x, lp["ln"], cfg.norm_eps)
        y, (ssm, conv) = mamba2_forward(lp["mixer"], h, cfg)
        return constrain(x + y, "act_btd"), (ssm, conv)

    def group_body(x, xs):
        glp = xs
        x, states = jax.lax.scan(lambda c, lp: mamba_layer(c, lp), x, glp)
        # shared attention + FFN block (same params every group)
        x, aux, stats, kv = _dense_block(
            x,
            sp,
            cfg,
            positions=positions,
            window=None,
            mask_l=shared_mask,
            collect_stats=collect_stats,
            stats_mask=stats_mask,
            return_kv=return_cache,
        )
        return x, (states, stats, kv)

    scan_body = maybe_remat(group_body, cfg)
    x, (mstates, stats, kvs) = jax.lax.scan(scan_body, x, params["layers"])
    tail_states = None
    if n_tail:
        x, tail_states = jax.lax.scan(lambda c, lp: mamba_layer(c, lp), x, params["tail"])
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = lm_logits(params, x, cfg)
    if collect_stats and stats is not None:
        stats = {"sum_abs": jnp.sum(stats["sum_abs"], axis=0), "count": jnp.sum(stats["count"])}
    cache = None
    if return_cache:
        cache = {"mamba": mstates, "tail": tail_states, "kv": kvs}
    return logits, jnp.float32(0.0), stats, cache


def _conv_cache(cfg: ModelConfig, lead: tuple, batch: int):
    d_in, H, _ = mamba_dims(cfg)
    dt = cfg.compute_dtype
    K1 = cfg.ssm_conv - 1
    return {
        "x": jnp.zeros(lead + (batch, K1, d_in), dt),
        "B": jnp.zeros(lead + (batch, K1, cfg.ssm_state), dt),
        "C": jnp.zeros(lead + (batch, K1, cfg.ssm_state), dt),
    }


def init_hybrid_cache(cfg: ModelConfig, batch: int, max_len: int):
    n_groups, g, n_tail = hybrid_layout(cfg)
    d_in, H, _ = mamba_dims(cfg)
    P, N = cfg.mamba_headdim, cfg.ssm_state
    dt = cfg.compute_dtype
    cache = {
        "ssm": jnp.zeros((n_groups, g, batch, H, N, P), jnp.float32),
        "conv": _conv_cache(cfg, (n_groups, g), batch),
        "k": jnp.zeros((n_groups, batch, max_len, cfg.n_kv_heads, cfg.head_dim), dt),
        "v": jnp.zeros((n_groups, batch, max_len, cfg.n_kv_heads, cfg.head_dim), dt),
    }
    if n_tail:
        cache["tail_ssm"] = jnp.zeros((n_tail, batch, H, N, P), jnp.float32)
        cache["tail_conv"] = _conv_cache(cfg, (n_tail,), batch)
    return cache


def hybrid_prefill(params, tokens, cfg: ModelConfig, max_len: int):
    logits, _, stats, raw = hybrid_forward(
        params, tokens, cfg, collect_stats=True, return_cache=True
    )
    B, S = tokens.shape
    cache = init_hybrid_cache(cfg, B, max_len)
    (ssm, conv) = raw["mamba"]
    cache["ssm"], cache["conv"] = ssm, conv
    k, v = raw["kv"]
    cache["k"], cache["v"] = jax.vmap(write_cache_prefill)(cache["k"], cache["v"], k, v)
    if raw["tail"] is not None:
        cache["tail_ssm"], cache["tail_conv"] = raw["tail"]
    return logits, cache, stats


def hybrid_decode_step(
    params, token, cache, cache_len, cfg: ModelConfig, *, shared_mask=None,
    shared_compact=None, block_table=None, attn_mode: str = "gather"
):
    n_groups, g, n_tail = hybrid_layout(cfg)
    x = embed_tokens(params, token, cfg)
    sp = params["shared_attn"]
    if shared_mask is not None and shared_mask.ndim == 2:  # per-slot (B, m)
        shared_mask = shared_mask[:, None, :]

    def mamba_step(x, lp, ssm, conv):
        h = rms_norm(x, lp["ln"], cfg.norm_eps)
        y, (ssm, conv) = mamba2_decode(lp["mixer"], h, cfg, ssm_state=ssm, conv_state=conv)
        return x + y, ssm, conv

    def group_body(x, xs):
        glp, ssm_g, conv_g, ck, cv = xs

        def inner(c, inner_xs):
            lp, s, cv_ = inner_xs
            xx, s2, c2 = mamba_step(c, lp, s, cv_)
            return xx, (s2, c2)

        x, (ssm_g, conv_g) = jax.lax.scan(inner, x, (glp, ssm_g, conv_g))
        h = rms_norm(x, sp["ln1"], cfg.norm_eps)
        if block_table is not None:
            a, ck, cv = attention_decode_paged(
                sp["attn"], h, cfg, cache_k=ck, cache_v=cv,
                block_table=block_table, cache_len=cache_len,
                attn_mode=attn_mode,
            )
        else:
            a, ck, cv = attention_decode(
                sp["attn"], h, cfg, cache_k=ck, cache_v=cv, cache_len=cache_len
            )
        x = x + a
        h2 = rms_norm(x, sp["ln2"], cfg.norm_eps)
        fp = shared_compact if shared_compact is not None else sp["ffn"]
        y = ffn_forward(fp, h2, cfg, mask=shared_mask)
        x = x + y
        return x, (ssm_g, conv_g, ck, cv)

    x, (ssm, conv, ck, cv) = jax.lax.scan(
        group_body, x, (params["layers"], cache["ssm"], cache["conv"], cache["k"], cache["v"])
    )
    new_cache = dict(cache, ssm=ssm, conv=conv, k=ck, v=cv)
    if n_tail:
        def inner(c, inner_xs):
            lp, s, cv_ = inner_xs
            xx, s2, c2 = mamba_step(c, lp, s, cv_)
            return xx, (s2, c2)

        x, (tssm, tconv) = jax.lax.scan(
            inner, x, (params["tail"], cache["tail_ssm"], cache["tail_conv"])
        )
        new_cache["tail_ssm"], new_cache["tail_conv"] = tssm, tconv
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return lm_logits(params, x, cfg), new_cache


def hybrid_prefill_chunk(
    params, tokens, cfg: ModelConfig, cache, block_table, cache_len,
    attn_mode: str = "gather",
):
    """One chunk of an incremental hybrid (zamba2) prefill.

    Mamba layers thread their ssm/conv state rows as initial carries
    (``mamba2_forward(init_state, conv_prev)``); the shared attention block
    pages its KV through ``block_table`` like the dense path.  Returns
    (logits, cache, chunk_stats) with the shared block's stats aggregated
    over groups exactly as in :func:`hybrid_forward`."""
    n_groups, g, n_tail = hybrid_layout(cfg)
    T = tokens.shape[1]
    x = embed_tokens(params, tokens, cfg)
    sp = params["shared_attn"]

    def mamba_layer(x, lp, ssm0, conv0):
        h = rms_norm(x, lp["ln"], cfg.norm_eps)
        y, (ssm, conv) = mamba2_forward(lp["mixer"], h, cfg, init_state=ssm0, conv_prev=conv0, chunk=T)
        return constrain(x + y, "act_btd"), ssm, conv

    def inner(c, ixs):
        lp, s0, c0 = ixs
        xx, s1, c1 = mamba_layer(c, lp, s0, c0)
        return xx, (s1, c1)

    def group_body(x, xs):
        glp, ssm_g, conv_g, ck, cv = xs
        x, (ssm_g, conv_g) = jax.lax.scan(inner, x, (glp, ssm_g, conv_g))
        h = rms_norm(x, sp["ln1"], cfg.norm_eps)
        a, ck, cv = attention_decode_paged(
            sp["attn"], h, cfg, cache_k=ck, cache_v=cv,
            block_table=block_table, cache_len=cache_len,
            attn_mode=attn_mode,
        )
        x = x + a
        h2 = rms_norm(x, sp["ln2"], cfg.norm_eps)
        y, stats = ffn_forward_with_stats(sp["ffn"], h2, cfg)
        x = constrain(x + y, "act_btd")
        return x, (ssm_g, conv_g, ck, cv, stats)

    x, (ssm, conv, ck, cv, stats) = jax.lax.scan(
        group_body, x, (params["layers"], cache["ssm"], cache["conv"], cache["k"], cache["v"])
    )
    new_cache = dict(cache, ssm=ssm, conv=conv, k=ck, v=cv)
    if n_tail:
        x, (tssm, tconv) = jax.lax.scan(
            inner, x, (params["tail"], cache["tail_ssm"], cache["tail_conv"])
        )
        new_cache["tail_ssm"], new_cache["tail_conv"] = tssm, tconv
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = lm_logits(params, x, cfg)
    stats = {"sum_abs": jnp.sum(stats["sum_abs"], axis=0), "count": jnp.sum(stats["count"])}
    return logits, new_cache, stats


# ---------------------------------------------------------------------------
# Uniform entry points
# ---------------------------------------------------------------------------


def forward(params, tokens, cfg: ModelConfig, **kw):
    if cfg.family in ("dense", "moe", "vlm"):
        return dense_forward(params, tokens, cfg, **kw)
    if cfg.family == "ssm":
        return rwkv_forward(params, tokens, cfg, **kw)
    if cfg.family == "hybrid":
        kw.pop("probes", None)
        masks = kw.pop("ffn_masks", None)
        if masks is not None and masks.ndim > 1:
            masks = masks[0]
        return hybrid_forward(params, tokens, cfg, shared_mask=masks, **kw)
    raise ValueError(cfg.family)


def lm_loss(params, batch, cfg: ModelConfig) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    logits, aux, _, _ = forward(params, batch["tokens"], cfg)
    loss, n = cross_entropy(logits, batch["labels"], batch.get("mask"))
    total = loss + cfg.router_aux_weight * aux if cfg.family == "moe" else loss
    return total, {"ce": loss, "aux": aux, "tokens": n}
