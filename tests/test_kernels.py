"""Pallas kernel allclose vs jnp oracles (interpret=True) + shape/dtype sweeps."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.flash_attention import flash_attention
from repro.kernels.glass_ffn import glass_ffn_block_sparse
from repro.kernels.local_stats import local_stats
from repro.kernels.ref import flash_attention_ref, glass_ffn_ref, local_stats_ref

KEY = jax.random.key(0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,d,m,bs,act,gated", [
    (4, 128, 512, 128, "silu", True),
    (8, 256, 1024, 128, "gelu", True),
    (1, 128, 512, 256, "relu2", False),
    (16, 64, 256, 128, "relu", True),
])
def test_glass_ffn_sweep(B, d, m, bs, act, gated, dtype):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, d), dtype)
    wu = (jax.random.normal(ks[1], (d, m), jnp.float32) * 0.05).astype(dtype)
    wg = (jax.random.normal(ks[2], (d, m), jnp.float32) * 0.05).astype(dtype) if gated else None
    wd = (jax.random.normal(ks[3], (d, m // bs and d) if False else (m, d), jnp.float32) * 0.05).astype(dtype)
    nb = m // bs
    bidx = jnp.sort(jax.random.choice(ks[4], nb, (max(1, nb // 2),), replace=False)).astype(jnp.int32)
    out = glass_ffn_block_sparse(x, wu, wd, bidx, wg, act=act, block_size=bs, interpret=True)
    ref = glass_ffn_ref(x, wu, wd, bidx, wg, act=act, block_size=bs)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=tol, rtol=tol)



def _row_lists(rng, B, n_tiles, nb, p_zero):
    """B sorted lists of nb distinct tile ids and their {0.0, 1.0} scales."""
    ids = np.stack([np.sort(rng.choice(n_tiles, nb, replace=False)) for _ in range(B)])
    scale = (rng.rand(B, nb) >= p_zero).astype(np.float32)
    return ids.astype(np.int32), scale


@pytest.mark.parametrize("B,T,cleared,dtype", [
    (5, 1, 0, jnp.float32),
    (5, 1, 0, jnp.bfloat16),
    (6, 1, 2, jnp.float32),  # two cleared rows: zero lists, zero scales
    (1, 1, 0, jnp.float32),
    (3, 3, 0, jnp.float32),  # T queries a row: (B, T) flattened to B*T rows
    (4, 2, 1, jnp.bfloat16),
])
def test_glass_ffn_union_matches_rowwise(B, T, cleared, dtype):
    """One shared-list grid over the union of the rows' lists, with a
    per-row scale table, computes what the rowwise grid computes on each
    row's own list (scales of 0.0 included); cleared rows read 0.0."""
    from repro.kernels.glass_ffn import glass_ffn_block_sparse_rowwise
    from repro.kernels.ops import ffn_union

    d, m, bs, nb = 64, 512, 32, 6
    rng = np.random.RandomState(B * 10 + T + cleared)
    ks = jax.random.split(jax.random.fold_in(KEY, B * T), 4)
    w = lambda k, shape: (jax.random.normal(k, shape, jnp.float32) * 0.1).astype(dtype)
    wu, wg, wd = w(ks[0], (d, m)), w(ks[1], (d, m)), w(ks[2], (m, d))
    x = jax.random.normal(ks[3], (B * T, d), dtype)
    ids, scale = _row_lists(rng, B, m // bs, nb, 0.3)
    ids[B - cleared:], scale[B - cleared:] = 0, 0.0
    u_ids, count, table = ffn_union(jnp.asarray(ids)[None], jnp.asarray(scale)[None],
                                    n_tiles=m // bs)
    got = glass_ffn_block_sparse(
        x, wu, wd, u_ids[0], wg, block_scale=jnp.repeat(table[0], T, axis=1),
        n_active=count[0], block_size=bs, interpret=True)
    want = glass_ffn_block_sparse_rowwise(
        x, wu, wd, jnp.asarray(np.repeat(ids, T, axis=0)), wg,
        block_scale=jnp.asarray(np.repeat(scale, T, axis=0)), block_size=bs,
        interpret=True)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol, rtol=tol)
    live = (B - cleared) * T
    assert not np.asarray(got)[live:].any()
    assert int(count[0]) == len({t for r in range(B) for t, s in zip(ids[r], scale[r]) if s})


@pytest.mark.parametrize("case", ["random", "unkept_tile", "decoding_mask", "empty_layer"])
def test_ffn_union_matches_brute_force(case):
    """ffn_union's ids, count and scale table against a NumPy brute force:
    a tile listed only at scale 0.0, or only by rows that do not decode, is
    not in the union; a layer that keeps nothing has count 0."""
    from repro.kernels.ops import ffn_union

    rng = np.random.RandomState(len(case))
    L, B, n_tiles, nb = 3, 4, 12, 4
    ids = np.stack([_row_lists(rng, B, n_tiles, nb, 0.25)[0] for _ in range(L)])
    scale = np.stack([_row_lists(rng, B, n_tiles, nb, 0.25)[1] for _ in range(L)])
    rows = np.ones(B, bool)
    if case == "unkept_tile":  # tile 11 listed by row 0 alone, at scale 0.0
        ids[0] = [[0, 3, 5, 11], [0, 2, 3, 5], [1, 3, 5, 7], [0, 1, 2, 3]]
        scale[0] = 1.0
        scale[0, 0, 3] = 0.0
    if case == "decoding_mask":
        rows[[1, 3]] = False
    if case == "empty_layer":
        scale[1] = 0.0
    u_ids, count, table = (np.asarray(a) for a in ffn_union(
        jnp.asarray(ids), jnp.asarray(scale), jnp.asarray(rows), n_tiles=n_tiles))
    assert u_ids.shape == (L, n_tiles) and table.shape == (L, n_tiles, B)
    for layer in range(L):
        keep = sorted({int(t) for b in range(B) if rows[b]
                       for t, s in zip(ids[layer, b], scale[layer, b]) if s > 0})
        n = len(keep)
        assert count[layer] == n
        pad = keep[-1] if keep else 0
        assert u_ids[layer].tolist() == keep + [pad] * (n_tiles - n)
        want = np.zeros((n_tiles, B), np.float32)
        for p, t in enumerate(keep):
            for b in np.flatnonzero(rows):
                hit = ids[layer, b] == t
                want[p, b] = scale[layer, b][hit].sum()
        np.testing.assert_array_equal(table[layer], want)
    if case == "unkept_tile":
        assert 11 not in u_ids[0, : count[0]]
    if case == "empty_layer":
        assert count[1] == 0 and not table[1].any()


@given(
    st.sampled_from([64, 128, 256]),
    st.sampled_from([32, 64]),
    st.booleans(),
    st.sampled_from([None, 32]),
    st.sampled_from([None, 30.0]),
)
@settings(max_examples=12, deadline=None)
def test_flash_attention_property(S, hd, causal, window, softcap):
    B, H = 2, 2
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, S, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, H, S, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, H, S, hd), jnp.float32)
    o = flash_attention(q, k, v, causal=causal, window=window, softcap=softcap,
                        block_q=32, block_k=32, interpret=True)
    r = flash_attention_ref(q, k, v, causal=causal, window=window, softcap=softcap)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_cross_lengths(dtype):
    """Sq != Skv (e.g. chunked prefill against a longer kv)."""
    B, H, Sq, Skv, hd = 1, 2, 64, 128, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, Sq, hd), dtype)
    k = jax.random.normal(ks[1], (B, H, Skv, hd), dtype)
    v = jax.random.normal(ks[2], (B, H, Skv, hd), dtype)
    o = flash_attention(q, k, v, causal=True, block_q=32, block_k=32, interpret=True)
    r = flash_attention_ref(q, k, v, causal=True)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(o, np.float32), np.asarray(r, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("T,m,bt,bm", [(256, 512, 64, 128), (128, 1024, 128, 256), (512, 256, 256, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_local_stats_sweep(T, m, bt, bm, dtype):
    h = jax.random.normal(jax.random.fold_in(KEY, T + m), (T, m), dtype)
    s = local_stats(h, block_t=bt, block_m=bm, interpret=True)
    r = local_stats_ref(h)
    np.testing.assert_allclose(np.asarray(s), np.asarray(r), atol=1e-3, rtol=1e-3)


def test_ops_jit_wrappers():
    """The jit'd ops layer dispatches with static flags and interpret default."""
    from repro.kernels import flash_attention as fa_op
    from repro.kernels import glass_ffn as gf_op
    from repro.kernels import local_stats as ls_op

    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (4, 128))
    wu = jax.random.normal(ks[1], (128, 512)) * 0.05
    wg = jax.random.normal(ks[2], (128, 512)) * 0.05
    wd = jax.random.normal(ks[3], (512, 128)) * 0.05
    bidx = jnp.asarray([0, 3], jnp.int32)
    out = gf_op(x, wu, wd, bidx, wg)
    ref = glass_ffn_ref(x, wu, wd, bidx, wg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    q = jax.random.normal(ks[4], (1, 2, 64, 32))
    o = fa_op(q, q, q, block_q=32, block_k=32)
    r = flash_attention_ref(q, q, q)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=2e-5, rtol=2e-5)

    h = jax.random.normal(ks[0], (128, 256))
    np.testing.assert_allclose(
        np.asarray(ls_op(h, block_t=64, block_m=128)),
        np.asarray(local_stats_ref(h)), atol=1e-4, rtol=1e-4,
    )


@pytest.mark.parametrize("module", ["repro.kernels.ops", "repro.serve.cluster", "benchmarks.tables"])
def test_import_initialises_no_backend(module):
    """Importing the kernels, the serving stack or a benchmark that spawns
    workers must not claim a device: on a TPU host the process that first
    initialises a backend holds the chip, and the interpret-or-compile
    choice is made when a kernel is traced, not when its module loads."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    root = Path(__file__).resolve().parent.parent
    code = (
        f"import {module}\n"
        "from jax._src import xla_bridge\n"
        "print(sorted(xla_bridge._backends))\n"
    )
    env = dict(os.environ, PYTHONPATH=os.pathsep.join([str(root / "src"), str(root)]))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                         env=env, timeout=300, cwd=root)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "[]", out.stdout
