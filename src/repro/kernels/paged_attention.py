"""Fused paged-attention decode kernel (Pallas, TPU target).

Replaces the XLA gather in :func:`repro.models.attention.attention_decode_paged`
that materializes every row's full ``(nb * bs)`` logical KV view per tick.
Grid ``(slot, query, kv_block)`` — TPU grids run sequentially
minor-to-major, so the kv-block axis is innermost and the online-softmax
state (m, l, acc) lives in VMEM scratch carried across kv blocks, exactly
like flash_attention.py:

    m_new = max(m, rowmax(s));  alpha = exp(m - m_new)
    l     = l * alpha + rowsum(p);   p = where(mask, exp(s - m_new), 0)
    acc   = acc * alpha + p @ v

Each program streams only ONE physical KV block HBM->VMEM, with all K of
its heads: a ``(1, bs, K, hd)`` block spans the pool's last two dims, which
is what Mosaic's (8, 128) tiling rule needs (a single head, ``(1, bs, 1,
hd)``, would put a 1 in the second-minor dim).  The block id is
read from the scalar-prefetched block table inside the BlockSpec index map,
so the ``(B, nb*bs, K, hd)`` gathered view is never materialized and the
HBM traffic per row scales with ``ceil((cache_len + T) / bs)`` live blocks,
not the ``nb`` allocated capacity.  Blocks past a row's frontier or fully
below its sliding window are skipped (``pl.when``) — their table entries
point at trash block 0, so even the prefetch pipeline re-reads one hot
block instead of walking the pool.

``T >= 1`` queries per row share one kernel: T = 1 is the decode tick,
T > 1 serves chunked prefill and the parallel multi-token speculative
verify.  The query axis deliberately lives on the GRID, not inside the
block shapes: every (slot, query) program runs the exact same traced
op graph at the exact same ``(K*G, bs*K)`` shapes whatever T is, which is what
makes a T = k+1 verify forward produce bitwise the tokens and KV rows of
k+1 sequential T = 1 ticks (a T-wide q tile compiles to differently fused
reductions and costs 1-ulp divergences).  Masked entries contribute EXACT
zeros to l/acc (the ``where`` below, not ``exp(NEG - m)``), which makes
the accumulator bitwise independent of how many dead or out-of-window
blocks a bucket carries — the engine's pow2 bucketing and the
sequential-vs-parallel verify bit-equality contract both rely on it.

The sliding ``window`` is a *traced* int32 scalar (scalar-prefetch operand)
so a single compiled kernel serves local and global layers inside the layer
scan — global layers pass ``2**30`` exactly like ``layer_windows``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -2.0e38


def _kernel(
    tab_ref,  # scalar prefetch: (B, nb) int32 block table
    clen_ref,  # scalar prefetch: (B,) int32 live rows before the T new tokens
    wnd_ref,  # scalar prefetch: (1,) int32 sliding window (2**30 = global)
    q_ref,  # (1, 1, K*G, hd) — query t of slot b, every head
    k_ref,  # (1, bs, K, hd) — physical block tab[b, j], every KV head
    v_ref,  # (1, bs, K, hd)
    same_ref,  # (K*G, bs*K) int32: 1 where query row and key column share a head
    row_ref,  # (1, bs*K) int32: block row of each flattened key column
    o_ref,  # (1, 1, K*G, hd)
    m_ref,  # scratch (K*G, 1) f32
    l_ref,  # scratch (K*G, 1) f32
    acc_ref,  # scratch (K*G, hd) f32
    *,
    bs: int,
    nb: int,
    softcap: Optional[float],
    scale: float,
):
    b = pl.program_id(0)
    t = pl.program_id(1)
    j = pl.program_id(2)
    clen = clen_ref[b]
    wnd = wnd_ref[0]
    KG, hd = acc_ref.shape
    qpos = clen + t  # this query's logical position

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Block-level skip: past this query's causal frontier (kpos > qpos for
    # the whole block) or entirely below its window.  Skipped blocks are
    # exactly those whose every entry would mask to a zero contribution,
    # so skipping is bitwise-identical to processing them.
    live = (j * bs) <= qpos
    in_window = (qpos - ((j + 1) * bs - 1)) < wnd

    @pl.when(live & in_window)
    def _compute():
        # All K heads of the block in one (K*G, bs*K) score tile: the
        # (bs, K, hd) block flattens to bs*K key rows (row r*K + h is slot
        # row r of head h), every query row meets every key row, and the
        # cross-head pairs mask to exact zeros below.  In f32 the flatten
        # keeps the (8, 128) tiling; the bf16 inputs convert exactly.
        q = q_ref[0, 0].astype(jnp.float32)  # (K*G, hd)
        k = k_ref[0].astype(jnp.float32).reshape(-1, hd)  # (bs*K, hd)
        v = v_ref[0].astype(jnp.float32).reshape(-1, hd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        ) * scale  # (K*G, bs*K)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        kpos = j * bs + row_ref[...]  # (1, bs*K)
        mask = (same_ref[...] != 0) & (qpos >= kpos) & ((qpos - kpos) < wnd)
        s = jnp.where(mask, s, NEG)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # Masked entries contribute an EXACT 0 (not exp(NEG - m), which is 0
        # only once a live m is around): a block whose every entry is below
        # this query's window must leave l/acc untouched even while m is
        # still NEG.
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        # p rounds to the cache dtype exactly as a native-dtype dot would
        p = p.astype(v_ref.dtype).astype(jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
        m_ref[...] = m_new

    @pl.when(j == nb - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_attention(
    q: jax.Array,  # (B, T, K, G, hd) post-RoPE grouped queries
    cache_k: jax.Array,  # (num_blocks, bs, K, hd) shared block pool
    cache_v: jax.Array,
    block_table: jax.Array,  # (B, nb) int32 block ids in logical order
    cache_len: jax.Array,  # (B,) int32 rows already live (before the T new)
    window: jax.Array,  # () or (1,) int32 traced sliding window
    *,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    """Attend T queries per row against that row's paged KV blocks.

    The new tokens' k/v rows must already be scattered into the pool (the
    caller owns the write — an in-kernel scatter could touch physical
    blocks no table references, i.e. free or prefix-cache-retained blocks
    whose content must survive).  The causal intra-chunk mask places query
    ``t`` at logical position ``cache_len + t``, so the scattered frontier
    rows participate exactly like :func:`attention_decode_paged`'s gather.
    Returns (B, T, K, G, hd) in q.dtype.
    """
    B, T, K, G, hd = q.shape
    nb = block_table.shape[1]
    bs = cache_k.shape[1]
    scale = scale if scale is not None else hd**-0.5
    tab = block_table.astype(jnp.int32)
    clen = cache_len.astype(jnp.int32)
    wnd = jnp.asarray(window, jnp.int32).reshape(1)
    col = jnp.arange(bs * K, dtype=jnp.int32)
    same = (jnp.arange(K * G, dtype=jnp.int32)[:, None] // G == col[None] % K)
    same = same.astype(jnp.int32)  # (K*G, bs*K)
    row = (col // K)[None]  # (1, bs*K)

    kernel = functools.partial(_kernel, bs=bs, nb=nb, softcap=softcap, scale=scale)
    fixed = lambda b, t, j, tab, cl, w: (0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, T, nb),
        in_specs=[
            pl.BlockSpec((1, 1, K * G, hd), lambda b, t, j, tab, cl, w: (b, t, 0, 0)),
            pl.BlockSpec((1, bs, K, hd), lambda b, t, j, tab, cl, w: (tab[b, j], 0, 0, 0)),
            pl.BlockSpec((1, bs, K, hd), lambda b, t, j, tab, cl, w: (tab[b, j], 0, 0, 0)),
            pl.BlockSpec((K * G, bs * K), fixed),
            pl.BlockSpec((1, bs * K), fixed),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, K * G, hd), lambda b, t, j, tab, cl, w: (b, t, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((K * G, 1), jnp.float32),
            pltpu.VMEM((K * G, 1), jnp.float32),
            pltpu.VMEM((K * G, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, T, K * G, hd), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(tab, clen, wnd, q.reshape(B, T, K * G, hd), cache_k, cache_v, same, row)
    return out.reshape(B, T, K, G, hd)
