"""Block-sparse GLASS FFN decode kernel (Pallas, TPU target).

The TPU-native execution of a GLASS mask: FFN hidden units are grouped into
blocks of ``block_size`` (>= 128 = lane width); the mask keeps whole blocks
(see core.fusion.select_blocks).  The kernel receives the *active block index
list* via scalar prefetch and streams only the active (d x bs) weight tiles
HBM->VMEM — inactive blocks are never read, which is exactly the paper's
"compact FFN residency" I/O story, expressed with MXU-shaped tiles.

    y = (act(x @ Wg[:, blk]) * (x @ Wu[:, blk])) @ Wd[blk, :]   summed over
    active blocks blk.

Grid: one step per active block; the f32 accumulator lives in the output ref
(TPU grids execute sequentially, so revisiting the output block is safe).

``block_scale`` is the per-request density hook: each listed block's
contribution is multiplied by a per-(row, tile) f32 before accumulation.
The engine selects blocks at its CAPACITY density and scales a lower-density
request's dropped tiles by exactly 0.0 — a zero contribution added to the
accumulator is bitwise a no-op, so a scaled row equals running the shorter
list outright, while every row still shares one fixed-width compiled grid.
(The tiles are still streamed; per-request density trades I/O for not
recompiling per request.  ``None`` keeps the original unscaled program.)

Batched decode: the rows' lists differ, but they share most tiles.  The
shared-list kernel then walks the UNION of the rows' kept tiles
(``ops.ffn_union``) with a ``(nb, B)`` scale table: step ``i`` streams tile
``idx[i]`` once and applies each row's own scale, 0.0 for a row that does
not keep it.  Every kept tile is read from HBM once per step for all rows.

VMEM budget per step (worst assigned case d = 8192, bs = 128, B <= 128):
x 2 MiB + 3 weight tiles 6 MiB + acc 4 MiB ~= 12 MiB before the pipeline
double-buffers the tiles.  Whether a shape fits the chip's scoped VMEM is
the TPU compiler's call: tests/test_tpu_compile.py compiles llama3-8b widths.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_ACTS: dict[str, Callable] = {
    "silu": jax.nn.silu,
    "gelu": functools.partial(jax.nn.gelu, approximate=True),
    "relu": jax.nn.relu,
    "relu2": lambda t: jnp.square(jax.nn.relu(t)),
}


def _tile_contrib(x, wg_ref, wu_ref, wd_ref, *, act: str, gated: bool):
    up = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
    if gated:
        gate = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
        h = _ACTS[act](gate) * up
    else:
        h = _ACTS[act](up)
    return jnp.dot(
        h.astype(wd_ref.dtype), wd_ref[...], preferred_element_type=jnp.float32
    )


def _kernel(*refs, act: str, gated: bool, rowwise: bool, smem_scale: bool,
            row_scale: bool, guarded: bool):
    n_pre = 1 + smem_scale + guarded  # block ids [, per-step scales] [, length]
    pre, refs = refs[:n_pre], refs[n_pre:]
    if row_scale:
        sc_ref, refs = refs[0], refs[1:]
    x_ref, wg_ref, wu_ref, wd_ref, o_ref = refs
    i = pl.program_id(1 if rowwise else 0)  # position in the active list

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    def _accumulate():
        x = x_ref[0] if rowwise else x_ref[...]
        contrib = _tile_contrib(x, wg_ref, wu_ref, wd_ref, act=act, gated=gated)
        if smem_scale:
            # the per-step scale lives in SMEM (scalar prefetch): a dynamic
            # lane index into a VMEM block is not provably 128-aligned, so
            # Mosaic refuses it
            k = pl.program_id(0) * pl.num_programs(1) + i if rowwise else i
            contrib = pre[1][k] * contrib
        elif row_scale:
            contrib = sc_ref[0] * contrib  # this tile's (B, 1) column of scales
        o_ref[...] += contrib[None] if rowwise else contrib

    if guarded:  # steps past the list's real length only pad the grid
        pl.when(i < pre[-1][0])(_accumulate)
    else:
        _accumulate()


def _call(x, w_up, w_down, idx, w_gate, block_scale, n_active, *, act,
          block_size, interpret, grid, x_block, x_map, tile):
    """Shared pallas_call for both kernels: ``tile(*grid_ids, idx)`` is the
    active block id a grid step streams, ``x_map`` places the x/out block.
    A 1-D ``block_scale`` rides in SMEM as one scalar per grid step; a
    ``(nb, B)`` one (shared grid only) is a VMEM ``(1, B, 1)`` block per
    step.  ``n_active`` (shared grid only) is the list's real length: later
    steps repeat its last id and compute nothing.  The call is named
    ``glass_ffn_rowwise`` (a 2-D grid) or ``glass_ffn_shared``: on a TPU its
    custom call's HLO instruction takes the name, and so does the operation
    in a profiler trace."""
    d = x.shape[-1]
    gated = w_gate is not None
    if not gated:  # dummy ref so the kernel signature stays uniform
        w_gate = w_up
    row_scale = block_scale is not None and block_scale.ndim == 2 and len(grid) == 1
    smem_scale = block_scale is not None and not row_scale
    guarded = n_active is not None
    n = len(grid)
    # index maps receive every scalar-prefetch ref; only the block ids steer
    col = lambda *a: (0, tile(*a[:n], a[n]))
    row = lambda *a: (tile(*a[:n], a[n]), 0)
    xm = lambda *a: x_map(*a[:n])
    in_specs = [
        pl.BlockSpec(x_block, xm),  # x: resident
        pl.BlockSpec((d, block_size), col),  # w_gate tile
        pl.BlockSpec((d, block_size), col),  # w_up tile
        pl.BlockSpec((block_size, d), row),  # w_down tile
    ]
    scalars = (idx,)
    if smem_scale:
        scalars += (jnp.asarray(block_scale, jnp.float32).reshape(idx.shape),)
    if guarded:
        scalars += (jnp.asarray(n_active, jnp.int32).reshape(1),)
    operands = (x, w_gate, w_up, w_down)
    if row_scale:
        nb, B = block_scale.shape

        def sc_map(i, *pre):
            if guarded:  # padded steps keep the last real block: no fetch
                i = jnp.maximum(jnp.minimum(i, pre[-1][0] - 1), 0)
            return (i, 0, 0)

        # the last two block dims equal the array's, which Mosaic accepts
        in_specs.insert(0, pl.BlockSpec((1, B, 1), sc_map))
        operands = (jnp.asarray(block_scale, jnp.float32).reshape(nb, B, 1),) + operands
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars), grid=grid, in_specs=in_specs,
        out_specs=pl.BlockSpec(x_block, xm),
    )
    fn = pl.pallas_call(
        functools.partial(
            _kernel, act=act, gated=gated, rowwise=n == 2, smem_scale=smem_scale,
            row_scale=row_scale, guarded=guarded,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.float32),
        interpret=interpret,
        name="glass_ffn_rowwise" if n == 2 else "glass_ffn_shared",
    )
    return fn(*scalars, *operands)


def glass_ffn_block_sparse(
    x: jax.Array,  # (B, d)
    w_up: jax.Array,  # (d, m)
    w_down: jax.Array,  # (m, d)
    block_idx: jax.Array,  # (nb_active,) int32 — active block ids
    w_gate: jax.Array | None = None,  # (d, m)
    *,
    block_scale: jax.Array | None = None,  # (nb_active,) or (nb_active, B) f32
    n_active: jax.Array | None = None,  # int32 scalar: real length of block_idx
    act: str = "silu",
    block_size: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Returns (B, d) f32. Only active weight blocks are read from HBM, each
    once for all B rows.

    ``block_scale`` of shape ``(nb_active,)`` multiplies a tile's
    contribution for every row; ``(nb_active, B)`` gives each row its own
    multiplier, so one list can be the union of several rows' lists (see
    ``ops.ffn_union``): a row's 0.0 on a tile it does not keep adds exactly
    nothing, and each row sums its own tiles in list order.  With
    ``n_active``, grid steps at or past it compute nothing; the list pads
    them by repeating its last real id, so they fetch nothing either."""
    B, d = x.shape
    assert w_up.shape[1] % block_size == 0, (w_up.shape, block_size)
    if block_scale is not None and block_scale.ndim == 2:
        assert block_scale.shape == (block_idx.shape[0], B), (block_scale.shape, B)
    return _call(
        x, w_up, w_down, block_idx, w_gate, block_scale, n_active, act=act,
        block_size=block_size, interpret=interpret, grid=(block_idx.shape[0],),
        x_block=(B, d), x_map=lambda i: (0, 0), tile=lambda i, idx: idx[i],
    )


def glass_ffn_block_sparse_rowwise(
    x: jax.Array,  # (B, d) — B serving slots, each with its OWN block list
    w_up: jax.Array,  # (d, m)
    w_down: jax.Array,  # (m, d)
    block_idx: jax.Array,  # (B, nb_active) int32 — per-row active block ids
    w_gate: jax.Array | None = None,  # (d, m)
    *,
    block_scale: jax.Array | None = None,  # (B, nb_active) f32 tile multipliers
    act: str = "silu",
    block_size: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Per-row block-sparse GLASS FFN: the continuous-batching decode path.

    Each serving slot carries its own prompt-adaptive mask, so the active
    block list differs per row.  Grid (B, nb): step (b, i) streams row b's
    i-th active weight tiles; the row's f32 accumulator lives in its output
    block (consecutive grid steps revisit it, which is safe on TPU's
    sequential grid).  Rows travel as ``(B, 1, d)`` so that a row's block
    spans the array's last two dims (Mosaic's (8, 128) rule), and the block
    lists and scales as flat ``B * nb`` SMEM vectors.  Rows are processed
    independently, so a tile that two rows keep is streamed twice; the
    serving decode streams the union of the rows' lists once through
    :func:`glass_ffn_block_sparse` instead.  ``block_scale`` multiplies row
    b's i-th tile contribution (per-request GLASS density nested inside the
    capacity-tier list; 0.0 exactly drops a tile).  Returns (B, d) f32.
    """
    B, d = x.shape
    assert w_up.shape[1] % block_size == 0, (w_up.shape, block_size)
    assert block_idx.shape[0] == B, (block_idx.shape, B)
    nb = block_idx.shape[1]
    if block_scale is not None:
        assert block_scale.shape == block_idx.shape, (block_scale.shape, block_idx.shape)
    out = _call(
        x.reshape(B, 1, d), w_up, w_down, block_idx.astype(jnp.int32).reshape(B * nb),
        w_gate, block_scale, None, act=act, block_size=block_size, interpret=interpret,
        grid=(B, nb), x_block=(1, 1, d), x_map=lambda b, i: (b, 0, 0),
        tile=lambda b, i, idx: idx[b * nb + i],
    )
    return out.reshape(B, d)
