"""Rank-aggregation properties (paper Sec. 3.4 / App. A)."""
import itertools

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.fusion import (
    GlassConfig,
    block_aggregate,
    glass_scores,
    jaccard,
    ranks_ascending,
    select,
    select_blocks,
    select_shard_balanced,
    select_topk,
)

floats = st.floats(min_value=-1e3, max_value=1e3, allow_nan=False, width=32)


@given(st.lists(floats, min_size=2, max_size=64))
@settings(max_examples=50, deadline=None)
def test_ranks_are_permutation(vals):
    r = np.asarray(ranks_ascending(jnp.asarray(vals, jnp.float32)))
    assert sorted(r.tolist()) == list(range(1, len(vals) + 1))


well_scaled = st.floats(min_value=-1e3, max_value=1e3, allow_nan=False, width=32).filter(
    lambda v: v == 0.0 or abs(v) > 1e-2  # keep the f32 affine transform strictly monotone
)


@given(st.lists(well_scaled, min_size=3, max_size=32, unique=True), st.floats(0.5, 5.0))
@settings(max_examples=40, deadline=None)
def test_rank_monotone_invariance(vals, scale):
    """Fusion is invariant to monotone transforms of either signal."""
    x = jnp.asarray(vals, jnp.float32)
    r1 = np.asarray(ranks_ascending(x))
    r2 = np.asarray(ranks_ascending(x * scale + 7.0))
    np.testing.assert_array_equal(r1, r2)


def test_tie_break_by_index():
    x = jnp.asarray([1.0, 2.0, 2.0, 0.5])
    r = np.asarray(ranks_ascending(x))
    # ties (2.0, 2.0): lower index gets the lower rank
    assert r[1] < r[2]
    idx, _ = select_topk(x, 2)
    assert idx.tolist() == [1, 2]


def test_map_consensus_equals_borda_bruteforce():
    """App. A: argmin over permutations of the Mallows objective == sorting
    by the weighted rank sum (checked exhaustively for m = 5)."""
    rng = np.random.default_rng(0)
    m = 5
    for _ in range(5):
        local = rng.normal(size=m)
        glob = rng.normal(size=m)
        bl, bg = 0.3, 0.7
        rl = np.asarray(ranks_ascending(jnp.asarray(local, jnp.float32)))
        rg = np.asarray(ranks_ascending(jnp.asarray(glob, jnp.float32)))
        best, best_val = None, np.inf
        for perm in itertools.permutations(range(m)):
            r = np.empty(m)
            for rank_pos, j in enumerate(perm):
                r[j] = rank_pos + 1
            val = bl * np.sum((rl - r) ** 2) + bg * np.sum((rg - r) ** 2)
            if val < best_val - 1e-12:
                best_val, best = val, r
        s = bl * rl + bg * rg
        # MAP rank order == descending fused-score order
        order_map = np.argsort(-best)
        order_borda = np.argsort(-s, kind="stable")
        np.testing.assert_array_equal(order_map, order_borda)


def test_lambda_endpoints():
    rng = np.random.default_rng(1)
    local = jnp.asarray(rng.normal(size=16), jnp.float32)
    glob = jnp.asarray(rng.normal(size=16), jnp.float32)
    s0 = glass_scores(local, glob, lam=0.0)
    s1 = glass_scores(local, glob, lam=1.0)
    np.testing.assert_array_equal(np.argsort(-s0), np.argsort(-np.asarray(ranks_ascending(local))))
    np.testing.assert_array_equal(np.argsort(-s1), np.argsort(-np.asarray(ranks_ascending(glob))))


@given(st.integers(1, 7), st.integers(2, 6))
@settings(max_examples=20, deadline=None)
def test_block_selection_density(nb_keep, bs):
    m = 8 * bs
    scores = jnp.asarray(np.random.default_rng(0).normal(size=m), jnp.float32)
    k = nb_keep * bs
    bidx, mask = select_blocks(scores, k, bs)
    assert float(mask.sum()) == k
    # mask is block-structured
    mm = np.asarray(mask).reshape(8, bs)
    assert set(np.unique(mm.sum(1))) <= {0.0, float(bs)}


def test_shard_balanced_counts():
    rng = np.random.default_rng(2)
    scores = jnp.asarray(rng.normal(size=(3, 64)), jnp.float32)
    idx, mask = select_shard_balanced(scores, 32, 4)
    per_shard = np.asarray(mask).reshape(3, 4, 16).sum(-1)
    assert (per_shard == 8).all()
    assert idx.shape == (3, 32)


def test_jaccard():
    a = jnp.asarray([1, 1, 0, 0], jnp.float32)
    b = jnp.asarray([1, 0, 1, 0], jnp.float32)
    assert float(jaccard(a, b)) == pytest.approx(1 / 3)
