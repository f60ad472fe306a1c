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
