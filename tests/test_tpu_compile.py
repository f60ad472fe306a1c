"""Compile the serving kernels for a described TPU v5e chip.

Nothing runs: the TPU compiler installed with jaxlib compiles each Pallas
kernel of the serving path for a chip that is described, not attached, at
llama3-8b widths in bf16.  What interpret mode cannot show, this does: a
block that breaks Mosaic's (8, 128) tiling rule, a dynamic lane index it
cannot prove aligned, or a kernel that overflows VMEM is refused here.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every xdist worker imports
this file.  The persistent compilation cache is off around these compiles,
because an entry written for a described chip cannot be read back without one.
"""
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.glass_ffn import (
    glass_ffn_block_sparse,
    glass_ffn_block_sparse_rowwise,
)
from repro.kernels.ops import ffn_union
from repro.kernels.paged_attention import paged_attention

# llama3-8b: d_model, d_ff, KV heads, queries per KV head, head dim
D, F, K, G, HD = 4096, 14336, 8, 4, 128
SLOTS, KV_BLOCK, FFN_BLOCK = 8, 16, 128
ACTIVE = F // FFN_BLOCK // 2  # density 0.5
POOL_BLOCKS, TABLE = 8 * 16 + 1, 16  # 8 slots x 256 rows + trash block


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # the TPU compiler otherwise writes its logs under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe means skip
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _ffn_shapes(one_chip, rowwise):
    s = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    lists = (SLOTS, ACTIVE) if rowwise else (ACTIVE,)
    return dict(
        x=s((SLOTS, D)), w_up=s((D, F)), w_down=s((F, D)), w_gate=s((D, F)),
        idx=s(lists, jnp.int32), scale=s(lists, jnp.float32),
    )


@pytest.mark.parametrize("rowwise", [False, True], ids=["shared", "rowwise"])
@pytest.mark.parametrize("scaled", [False, True], ids=["unscaled", "scaled"])
def test_glass_ffn_compiles_for_v5e(one_chip, rowwise, scaled):
    kernel = glass_ffn_block_sparse_rowwise if rowwise else glass_ffn_block_sparse
    a = _ffn_shapes(one_chip, rowwise)

    def fn(x, w_up, w_down, idx, w_gate, scale):
        return kernel(x, w_up, w_down, idx, w_gate,
                      block_scale=scale if scaled else None, block_size=FFN_BLOCK)

    compiled = _compile(fn, a["x"], a["w_up"], a["w_down"], a["idx"], a["w_gate"],
                        a["scale"])
    assert compiled.out_info.shape == (SLOTS, D)


@pytest.mark.parametrize("rows, d_ff", [(16, 14336), (80, 14336), (16, 11008)],
                         ids=["mistral-decode", "mistral-verify", "yi"])
def test_union_glass_ffn_compiles_for_v5e(one_chip, rows, d_ff):
    """The shared-list kernel over a union of the rows' lists: a (tiles,
    rows) scale table delivered a (1, rows, 1) block a step and the union's
    length as a scalar, at Mistral-7B widths (112 tiles; 16 rows decoding,
    80 = 16 slots x 5 verify positions) and Yi-9B's (86 tiles)."""
    n = d_ff // FFN_BLOCK
    s = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def fn(x, w_up, w_down, w_gate, ids, scale, count):
        return glass_ffn_block_sparse(x, w_up, w_down, ids, w_gate, block_scale=scale,
                                      n_active=count, block_size=FFN_BLOCK)

    compiled = _compile(fn, s((rows, D)), s((D, d_ff)), s((d_ff, D)), s((D, d_ff)),
                        s((n,), jnp.int32), s((n, rows), jnp.float32),
                        s((), jnp.int32))
    assert compiled.out_info.shape == (rows, D)
    assert "glass_ffn_shared" in compiled.as_text()


def test_ffn_union_compiles_for_v5e(one_chip):
    """The union of 16 slots' lists (56 of 112 tiles each, 16 layers)."""
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    L, n = 16, 14336 // FFN_BLOCK
    compiled = jax.jit(partial(ffn_union, n_tiles=n)).lower(
        s((L, 16, n // 2), jnp.int32), s((L, 16, n // 2), jnp.float32),
        s((16,), jnp.bool_)).compile()
    ids, count, scale = compiled.out_info
    assert (ids.shape, count.shape, scale.shape) == ((L, n), (L,), (L, n, 16))


@pytest.mark.parametrize("T", [1, 3], ids=["decode", "verify"])
def test_paged_attention_compiles_for_v5e(one_chip, T):
    s = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    pool = s((POOL_BLOCKS, KV_BLOCK, K, HD))
    compiled = _compile(
        paged_attention, s((SLOTS, T, K, G, HD)), pool, pool,
        s((SLOTS, TABLE), jnp.int32), s((SLOTS,), jnp.int32), s((1,), jnp.int32),
    )
    assert compiled.out_info.shape == (SLOTS, T, K, G, HD)
