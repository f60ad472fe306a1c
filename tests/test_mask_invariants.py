"""Property-style invariants of ``build_masks`` across model families.

Checks the paper's selection contract (Sec. 3.4): exactly k unique sorted
units per layer at any density; lam=0 reduces to GRIFFIN (local-only
ranking, prior-independent); lam=1 reduces to the static global mask
(local-independent); and the slot-stacked batched path is exactly the
per-request path.
"""
import jax
import jax.numpy as jnp
import numpy as np

from hypothesis import given, settings, strategies as st

from repro.core import GlassConfig, build_masks
from repro.core.fusion import select_topk

L, M, E = 3, 64, 4

# family -> per-layer stat/prior shapes (hybrid collapses to one shared signal)
FAMILY_SHAPES = {
    "dense": (L, M),
    "moe": (L, E, M),
    "hybrid": (M,),
}


def _stats(shape, seed):
    key = jax.random.key(seed)
    sum_abs = jnp.abs(jax.random.normal(key, shape))
    count = jnp.asarray(7.0)
    return {"sum_abs": sum_abs, "count": count}


def _prior(shape, seed):
    return jnp.abs(jax.random.normal(jax.random.key(seed), shape))


@given(
    st.sampled_from(list(FAMILY_SHAPES)),
    st.floats(0.05, 0.95),
    st.floats(0.0, 1.0),
    st.integers(0, 5),
)
@settings(max_examples=30, deadline=None)
def test_selects_exactly_k_unique_sorted(family, density, lam, seed):
    shape = FAMILY_SHAPES[family]
    ms = build_masks(_stats(shape, seed), _prior(shape, seed + 100),
                     GlassConfig(density=density, lam=lam))
    k = max(1, int(round(density * M)))
    idx = np.asarray(ms.idx).reshape(-1, ms.idx.shape[-1])
    mask = np.asarray(ms.mask).reshape(-1, M)
    assert idx.shape[-1] == k
    for row, mrow in zip(idx, mask):
        assert len(set(row.tolist())) == k  # unique
        assert (np.diff(row) > 0).all()  # strictly sorted ascending
        assert mrow.sum() == k and set(np.nonzero(mrow)[0]) == set(row.tolist())


@given(st.sampled_from(list(FAMILY_SHAPES)), st.integers(0, 5))
@settings(max_examples=20, deadline=None)
def test_lam0_is_local_only_griffin(family, seed):
    """lam=0: selection == top-k of the local signal, for ANY prior."""
    shape = FAMILY_SHAPES[family]
    stats = _stats(shape, seed)
    g = GlassConfig(density=0.5, lam=0.0)
    ms1 = build_masks(stats, _prior(shape, seed + 1), g)
    ms2 = build_masks(stats, _prior(shape, seed + 2), g)
    np.testing.assert_array_equal(np.asarray(ms1.idx), np.asarray(ms2.idx))
    local = stats["sum_abs"] / 7.0
    want, _ = select_topk(local.reshape(-1, M), g.k_of(M))
    np.testing.assert_array_equal(
        np.asarray(ms1.idx).reshape(-1, g.k_of(M)), np.asarray(want)
    )


@given(st.sampled_from(list(FAMILY_SHAPES)), st.integers(0, 5))
@settings(max_examples=20, deadline=None)
def test_lam1_is_static_global(family, seed):
    """lam=1: selection == top-k of the prior, for ANY local stats."""
    shape = FAMILY_SHAPES[family]
    prior = _prior(shape, seed)
    g = GlassConfig(density=0.5, lam=1.0)
    ms1 = build_masks(_stats(shape, seed + 1), prior, g)
    ms2 = build_masks(_stats(shape, seed + 2), prior, g)
    np.testing.assert_array_equal(np.asarray(ms1.idx), np.asarray(ms2.idx))
    want, _ = select_topk(prior.reshape(-1, M), g.k_of(M))
    np.testing.assert_array_equal(
        np.asarray(ms1.idx).reshape(-1, g.k_of(M)), np.asarray(want)
    )


@given(st.sampled_from(list(FAMILY_SHAPES)), st.floats(0.0, 1.0), st.integers(0, 3))
@settings(max_examples=15, deadline=None)
def test_slot_axis_matches_per_request(family, lam, seed):
    """The continuous-batching batched build == per-request builds."""
    shape = FAMILY_SHAPES[family]
    prior = _prior(shape, seed + 50)
    g = GlassConfig(density=0.5, lam=lam)
    stats = [_stats(shape, seed + i) for i in range(3)]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *stats)
    ms = build_masks(stacked, prior, g, slot_axis=True)
    for j, st_j in enumerate(stats):
        ref = build_masks(st_j, prior, g)
        np.testing.assert_array_equal(np.asarray(ref.idx), np.asarray(ms.idx[:, j]))
        np.testing.assert_array_equal(np.asarray(ref.mask), np.asarray(ms.mask[:, j]))
