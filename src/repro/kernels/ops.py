"""Jit'd public entry points for the Pallas kernels, and ``ffn_union``, which
turns per-row FFN block lists into the one list the batched decode streams.

``interpret=None`` (the default) is decided when the kernel is lowered, for
the platform it is lowered for: the Pallas interpreter for a CPU (where the
tests run), the compiled Mosaic kernel for a TPU, also when a CPU host
compiles for a described TPU.  Importing this module touches no backend;
callers may still force either mode per call.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention as _flash
from .glass_ffn import glass_ffn_block_sparse as _glass_ffn
from .glass_ffn import glass_ffn_block_sparse_rowwise as _glass_ffn_rowwise
from .local_stats import local_stats as _local_stats
from .paged_attention import paged_attention as _paged_attention


def _by_platform(kernel, args, interpret):
    """``kernel(*args, interpret=...)``: interpreted when lowered for a CPU,
    compiled otherwise, unless ``interpret`` forces one."""
    if interpret is not None:
        return kernel(*args, interpret=interpret)
    return jax.lax.platform_dependent(
        *args, cpu=partial(kernel, interpret=True), default=partial(kernel, interpret=False)
    )


@partial(jax.jit, static_argnames=("act", "block_size", "interpret"))
def glass_ffn(
    x, w_up, w_down, block_idx, w_gate=None, *, block_scale=None, n_active=None,
    act="silu", block_size=128, interpret=None,
):
    """Block-sparse GLASS FFN decode step: only active weight blocks are read,
    each once for all rows.  ``block_scale`` (nb,) scales every row alike,
    (nb, B) each row on its own; ``n_active`` is the list's real length
    (the union from :func:`ffn_union`)."""
    def kernel(x, w_up, w_down, block_idx, w_gate, block_scale, n_active, interpret):
        return _glass_ffn(
            x, w_up, w_down, block_idx, w_gate, block_scale=block_scale,
            n_active=n_active, act=act, block_size=block_size, interpret=interpret,
        )

    return _by_platform(
        kernel, (x, w_up, w_down, block_idx, w_gate, block_scale, n_active), interpret
    )


def ffn_union(block_idx, block_scale=None, rows=None, *, n_tiles: int):
    """Per layer, the union of the rows' FFN block lists, as the shared-list
    ``glass_ffn`` walks it.

    ``block_idx`` / ``block_scale`` are (L, B, nb) per-row lists and tile
    multipliers (``None``: all 1.0); ``rows`` (B,) bool marks the rows that
    decode (``None``: all).  Returns

      * ``ids`` (L, n_tiles) int32: the tiles some decoding row keeps with
        scale > 0, ascending, padded by repeating the last one (0 if none);
      * ``count`` (L,) int32: how many of ``ids`` are real;
      * ``scale`` (L, n_tiles, B) f32: at union position p, each row's scale
        of tile ``ids[:, p]`` where the row keeps it, 0.0 elsewhere, on
        padded positions and on rows that do not decode.

    The width is fixed at ``n_tiles``, so a decode program has one shape."""
    L, B, _ = block_idx.shape
    if block_scale is None:
        block_scale = jnp.ones(block_idx.shape, jnp.float32)
    scale = block_scale.astype(jnp.float32)
    if rows is not None:
        scale = jnp.where(rows[None, :, None], scale, 0.0)
    tiles = jnp.arange(n_tiles, dtype=jnp.int32)
    # (L, B, n_tiles): a row's scale of each tile (lists hold distinct ids)
    table = jnp.sum(
        jnp.where(block_idx[..., None] == tiles, scale[..., None], 0.0), axis=2
    )
    kept = jnp.any(table > 0.0, axis=1)  # (L, n_tiles)
    count = jnp.sum(kept, axis=-1, dtype=jnp.int32)
    order = jnp.argsort(~kept, axis=-1, stable=True).astype(jnp.int32)
    pos = jnp.minimum(tiles[None], jnp.maximum(count - 1, 0)[:, None])
    ids = jnp.take_along_axis(order, pos, axis=-1)
    per_pos = jnp.take_along_axis(table, jnp.broadcast_to(ids[:, None], (L, B, n_tiles)),
                                  axis=-1)
    per_pos = jnp.where(tiles[None, None] < count[:, None, None], per_pos, 0.0)
    return ids, count, per_pos.swapaxes(1, 2)


@partial(jax.jit, static_argnames=("act", "block_size", "interpret"))
def glass_ffn_rowwise(
    x, w_up, w_down, block_idx, w_gate=None, *, block_scale=None, act="silu",
    block_size=128, interpret=None,
):
    """Per-row block-sparse GLASS FFN: block_idx (B, nb) — one prompt-adaptive
    block list per serving slot (the continuous-batching decode path).
    ``block_scale`` (B, nb) multiplies each row's tile contributions (the
    per-request density hook)."""
    def kernel(x, w_up, w_down, block_idx, w_gate, block_scale, interpret):
        return _glass_ffn_rowwise(
            x, w_up, w_down, block_idx, w_gate, block_scale=block_scale,
            act=act, block_size=block_size, interpret=interpret,
        )

    return _by_platform(kernel, (x, w_up, w_down, block_idx, w_gate, block_scale), interpret)


@partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "scale", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q, k, v, *, causal=True, window=None, softcap=None, scale=None,
    block_q=512, block_k=512, interpret=None,
):
    kernel = partial(
        _flash, causal=causal, window=window, softcap=softcap, scale=scale,
        block_q=block_q, block_k=block_k,
    )
    return _by_platform(kernel, (q, k, v), interpret)


@partial(jax.jit, static_argnames=("softcap", "scale", "interpret"))
def paged_attention(
    q, cache_k, cache_v, block_table, cache_len, window, *,
    softcap=None, scale=None, interpret=None,
):
    """Fused paged-attention decode: block-table gather + online-softmax
    attention in one pass — the caller scatters the new k/v rows first.
    ``window`` is a traced int32 scalar (pass 2**30 for global layers)."""
    kernel = partial(_paged_attention, softcap=softcap, scale=scale)
    return _by_platform(
        kernel, (q, cache_k, cache_v, block_table, cache_len, window), interpret
    )


@partial(jax.jit, static_argnames=("block_t", "block_m", "interpret"))
def local_stats(h, *, block_t=256, block_m=512, interpret=None):
    kernel = partial(_local_stats, block_t=block_t, block_m=block_m)
    return _by_platform(kernel, (h,), interpret)
