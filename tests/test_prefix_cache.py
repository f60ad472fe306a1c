"""Prefix caching with copy-on-write block tables: the shared-prefix
invariant suite.

A cache-hit ("warm") prefill must be indistinguishable from a cold one —
not approximately, BIT-identically — because the cached entries carry the
exact artifacts an uncached prefill would have produced at the fork point:
whole KV blocks (immutable after registration; copy-on-write tables never
write shared blocks), the running GLASS stat left-fold at a block+chunk
aligned boundary, and the recurrent-state rows (rwkv6 / hybrid) at the
same position.  The suite enforces that across all four model families:

  * warm prefill reproduces the cold engine's fused GLASS mask rows, its
    gathered logical KV rows, its recurrent-state rows, and its greedy
    token stream, all bit-exact (np equality, not allclose);
  * concurrent requests share ONE physical copy of a common prefix
    (refcount 2 on the shared blocks, disjoint private tails);
  * the invariants survive swap/recompute preemption, speculative
    rollback (which must refuse to un-scatter a shared block), and
    mid-prefill abort while holding shared blocks;
  * a drained pool leaks nothing: every cache-indexed block sits at
    refcount 0, and evicting the index returns the allocator to its
    initial all-free state.

The CI lane runs this module twice: ``PREFIX_GLASS_MODE=fused`` (per-slot
fused masks / compact weights) and ``PREFIX_GLASS_MODE=block_sparse`` (the
dense family rerouted through block selection + the pallas block-sparse
decode kernel).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import GlassConfig
from repro.models import ModelConfig, build_model
from repro.serve.engine import Engine, PagedEngine
from repro.serve.kv_pool import BlockPool, PrefixCache
from repro.serve.lifecycle import PreemptionConfig, ReqState
from repro.serve.scheduler import Request

pytestmark = pytest.mark.prefix_cache

PREFIX_LANE = os.environ.get("PREFIX_GLASS_MODE", "fused")  # fused | block_sparse

BASE = dict(n_layers=2, d_model=48, n_heads=4, n_kv_heads=2, head_dim=12,
            d_ff=96, vocab_size=101, dtype="float32", remat="none")
DENSE = ModelConfig(name="pc-dense", family="dense", **BASE)
MOE = ModelConfig(name="pc-moe", family="moe", n_experts=4, n_experts_per_tok=2,
                  moe_strategy="dense", **BASE)
SSM = ModelConfig(name="pc-ssm", family="ssm", rwkv_headdim=12, **BASE)
HYBRID = ModelConfig(name="pc-hybrid", family="hybrid", attn_every=2,
                     ssm_state=16, mamba_headdim=12, **{**BASE, "n_layers": 4})

FAMILIES = {
    "dense": (DENSE, "compact"),
    "moe": (MOE, "masked"),
    "rwkv6": (SSM, "masked"),
    "hybrid": (HYBRID, "compact"),
}

# block_size == chunk_tokens == 4: every block boundary is chunk-aligned,
# so every full cached block is a legal resume point
BS = 4
CT = 4


def _family_setup(family):
    cfg, mode = FAMILIES[family]
    sel, bsz = "neuron", 128
    if PREFIX_LANE == "block_sparse" and cfg.family == "dense":
        mode, sel, bsz = "block_sparse", "block", 32
    return cfg, mode, sel, bsz


def _prior_for(cfg: ModelConfig):
    if cfg.family == "moe":
        shape = (cfg.n_layers, cfg.n_experts, cfg.d_ff)
    elif cfg.family == "hybrid":
        shape = (cfg.d_ff,)
    else:
        shape = (cfg.n_layers, cfg.d_ff)
    return jnp.abs(jax.random.normal(jax.random.key(7), shape))


def _engine(family, *, prefix_cache, max_slots=2, num_blocks=None,
            preemption=None, spec_k=0, draft_ratio=None, max_len=32,
            decode_chunk=8):
    cfg, mode, sel, bsz = _family_setup(family)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    glass = GlassConfig(density=0.5, selection=sel, block_size=bsz,
                        draft_ratio=draft_ratio)
    eng = PagedEngine(model, params, max_slots=max_slots, max_len=max_len,
                      block_size=BS, num_blocks=num_blocks, chunk_tokens=CT,
                      glass=glass, global_prior=_prior_for(cfg),
                      glass_mode=mode, preemption=preemption, spec_k=spec_k,
                      decode_chunk=decode_chunk, prefix_cache=prefix_cache)
    ref = Engine(model, params, glass=glass, global_prior=_prior_for(cfg),
                 glass_mode=mode)
    return eng, ref


def _prompt(n, seed=0, lo=3):
    return np.random.RandomState(seed).randint(lo, 101, size=n).astype(np.int32)


def _step_until(eng, uid, state, min_outputs=0, limit=400):
    done = []
    for _ in range(limit):
        done += eng.step()
        e = eng.lc.entries.get(uid)
        if e is not None and e.state is state and len(e.outputs) >= min_outputs:
            return e, done
    raise AssertionError(f"uid {uid} never reached {state}")


def _logical_kv_rows(pool, slot, nrows):
    """Host copy of the slot's first ``nrows`` LOGICAL KV rows, gathered
    through its block table — physical block ids cancel out, so two pools
    agree here iff the row contents agree."""
    if not pool.has_paged:
        return []
    bs = pool.block_size
    ids = [int(pool.block_table[slot, r // bs]) for r in range(nrows)]
    offs = [r % bs for r in range(nrows)]
    out = []
    for leaf, ax, pg in zip(
        jax.tree.leaves(pool.cache), jax.tree.leaves(pool.axes),
        jax.tree.leaves(pool.paged),
    ):
        if not pg:
            continue
        a = np.asarray(leaf)
        out.append(np.stack([
            np.take(np.take(a, [ids[i]], axis=ax), [offs[i]], axis=ax + 1)
            for i in range(nrows)
        ]))
    return out


def _state_rows(pool, slot):
    """Host copy of the slot's recurrent-state rows (non-paged leaves)."""
    out = []
    for leaf, ax, pg in zip(
        jax.tree.leaves(pool.cache), jax.tree.leaves(pool.axes),
        jax.tree.leaves(pool.paged),
    ):
        if not pg:
            out.append(np.take(np.asarray(leaf), [slot], axis=ax))
    return out


def _glass_rows(eng, slot):
    gs = eng.glass_slots
    if gs is None or gs.arena is None:
        return None
    ax = gs.slot_axis
    return [np.take(np.asarray(a), [slot], axis=ax) for a in jax.tree.leaves(gs.arena)]


def _assert_drained_clean(eng):
    """Leak regression: after a drain the pool's only live blocks are the
    cache-retained ones (all refcount 0), and evicting the whole index
    returns the allocator to its initial all-free state."""
    pool = eng.pool
    assert not pool.active.any()
    assert (pool.lengths == 0).all()
    pc = pool.prefix_cache
    alloc = pool.allocator
    cached = [e.block for e in pc.entries.values() if e.block >= 0]
    if alloc is None:
        # pure-state pool: entries are block-less snapshots, nothing to leak
        assert not cached
        return
    assert len(cached) == len(set(cached))  # one entry per physical block
    for b in cached:
        assert alloc.refcount(b) == 0  # index holds only refcount-0 entries
    assert alloc.n_live == len(cached)
    # the incremental reclaimable counter agrees with a full index scan
    assert pool.n_reclaimable_blocks == sum(
        1 for b in pc.by_block if alloc.refcount(b) == 0
    ) == len(cached)
    pc.evict_for(alloc, alloc.n_live + 1)
    assert len([e for e in pc.entries.values() if e.block >= 0]) == 0
    assert alloc.n_live == 0
    assert alloc.n_free == pool.num_blocks - 1
    assert pool.n_reclaimable_blocks == 0


# -- warm-vs-cold bit-identity across families --------------------------------


@pytest.mark.parametrize("family", list(FAMILIES), ids=list(FAMILIES))
def test_warm_prefill_bit_identical(family):
    """A cache-hit prefill reproduces the cold engine's fused GLASS mask,
    logical KV rows, recurrent-state rows, and greedy stream bit-exactly."""
    shared = _prompt(12, seed=3)  # 3 full blocks, chunk-aligned fork
    tail = _prompt(3, seed=4)
    prompt2 = np.concatenate([shared, tail])

    warm, ref = _engine(family, prefix_cache=True)
    cold, _ = _engine(family, prefix_cache=False)

    # populate: request 1 writes the shared prefix into the cache
    done1 = warm.run([Request(uid=1, prompt=shared, max_new=3)])
    assert len(warm.pool.prefix_cache.entries) >= 2  # full blocks registered
    baseline_inserts = warm.pool.prefix_cache.inserts

    warm.submit(Request(uid=2, prompt=prompt2, max_new=4))
    cold.submit(Request(uid=2, prompt=prompt2, max_new=4))
    ew, dw = _step_until(warm, 2, ReqState.RUNNING, min_outputs=1)
    ec, dc = _step_until(cold, 2, ReqState.RUNNING, min_outputs=1)

    # the admission actually hit: prefill resumed at the fork
    pc = warm.pool.prefix_cache
    assert pc.hits >= 1 and pc.tokens_saved >= 8
    assert ew.cached_rows >= 8 and ew.cached_rows % CT == 0

    # bit-identity at the finalize instant
    assert len(ew.outputs) == len(ec.outputs)
    assert ew.outputs == ec.outputs
    gw, gc = _glass_rows(warm, ew.slot), _glass_rows(cold, ec.slot)
    assert (gw is None) == (gc is None)
    if gw is not None:
        for a, b in zip(gw, gc):
            np.testing.assert_array_equal(a, b)
    if warm._mode == "block_sparse":
        assert ew.glass_key == ec.glass_key
    for a, b in zip(
        _logical_kv_rows(warm.pool, ew.slot, len(prompt2)),
        _logical_kv_rows(cold.pool, ec.slot, len(prompt2)),
    ):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(_state_rows(warm.pool, ew.slot), _state_rows(cold.pool, ec.slot)):
        np.testing.assert_array_equal(a, b)

    # full greedy streams: warm == cold == single-request reference
    done_w = {o.uid: o for o in dw if o.finished}
    done_w.update(warm.run())
    done_c = {o.uid: o for o in dc if o.finished}
    done_c.update(cold.run())
    np.testing.assert_array_equal(done_w[2].tokens, done_c[2].tokens)
    want = ref.generate(jnp.asarray(prompt2)[None], 4).tokens[0]
    np.testing.assert_array_equal(want, done_w[2].tokens)
    want1 = ref.generate(jnp.asarray(shared)[None], 3).tokens[0]
    np.testing.assert_array_equal(want1, done1[1].tokens)
    # dedup: the warm request re-registered nothing for the shared chain
    assert pc.inserts <= baseline_inserts + 1  # at most its private tail
    _assert_drained_clean(warm)


@pytest.mark.parametrize("family", ["dense", "rwkv6"], ids=["dense", "rwkv6"])
def test_concurrent_requests_share_one_physical_prefix(family):
    """Two live requests over a common prefix hold the SAME physical
    blocks (refcount 2) — copy-on-write, not copy — and their private
    tails stay disjoint.  Streams still match single-request serving."""
    shared = _prompt(8, seed=5)
    p1 = np.concatenate([shared, _prompt(3, seed=6)])
    p2 = np.concatenate([shared, _prompt(3, seed=7)])
    eng, ref = _engine(family, prefix_cache=True)
    eng.submit(Request(uid=1, prompt=p1, max_new=4, arrival=0))
    # arrives after request 1 has prefilled the shared blocks
    eng.submit(Request(uid=2, prompt=p2, max_new=4, arrival=3))
    # the 3-token private tail warm-prefills in ONE chunk, so PREFILLING is
    # not observable between steps — catch uid 2 at its first decode instead
    e2, early = _step_until(eng, 2, ReqState.RUNNING, min_outputs=1)
    e1 = eng.lc.entries[1]
    assert e1.slot >= 0  # both live: sharing is observable right now
    if eng.pool.has_paged:
        assert e2.cached_rows == 8  # hit on 2 full blocks
        nsh = 8 // BS
        t1 = list(eng.pool.block_table[e1.slot, :nsh])
        t2 = list(eng.pool.block_table[e2.slot, :nsh])
        assert t1 == t2  # one physical copy
        for b in t1:
            assert eng.pool.allocator.refcount(b) == 2
            assert b in eng.pool.prefix_cache.by_block
        priv1 = set(eng.pool._held[e1.slot][nsh:])
        priv2 = set(eng.pool._held[e2.slot][nsh:])
        assert not (priv1 & priv2)  # tails never shared
    done = {o.uid: o for o in early if o.finished}
    done.update(eng.run())
    for uid, p in [(1, p1), (2, p2)]:
        want = ref.generate(jnp.asarray(p)[None], 4).tokens[0]
        np.testing.assert_array_equal(want, done[uid].tokens, err_msg=f"uid={uid}")
    _assert_drained_clean(eng)


# -- invariants through preemption / rollback / abort -------------------------


@pytest.mark.parametrize("family", list(FAMILIES), ids=list(FAMILIES))
@pytest.mark.parametrize("kind", ["swap", "recompute"])
def test_prefix_cache_through_preemption(family, kind):
    """Preempting a warm (cache-hit) request and resuming it preserves
    stream parity: swap keeps its shared-block references device-side
    (only private blocks travel to host), recompute re-admits through the
    cache — possibly forking deeper than the first admission did."""
    shared = _prompt(8, seed=11)
    p1 = np.concatenate([shared, _prompt(3, seed=12)])
    p2 = np.concatenate([shared, _prompt(3, seed=13)])
    eng, ref = _engine(family, prefix_cache=True,
                       preemption=PreemptionConfig(mode=kind))
    done = {}
    for o in eng.run([Request(uid=1, prompt=p1, max_new=6)]).values():
        done[o.uid] = o
    eng.submit(Request(uid=2, prompt=p2, max_new=8))
    e, early = _step_until(eng, 2, ReqState.RUNNING, min_outputs=2)
    assert e.cached_rows == 8  # admission forked on the 2 shared blocks
    kept_before = eng.pool.blocks_in_use
    eng._preempt(e, kind)
    if kind == "swap":
        assert e.state is ReqState.PREEMPTED_SWAPPED
        if eng.pool.has_paged:
            # shared blocks stayed on device, pinned by the kept references
            assert len(e.swap.kept) >= 1
            for _, b in e.swap.kept:
                assert eng.pool.allocator.refcount(b) >= 1
    else:
        assert e.state is ReqState.PREEMPTED_RECOMPUTE
    done.update({o.uid: o for o in early if o.finished})
    done.update(eng.run())
    for uid, p, n in [(1, p1, 6), (2, p2, 8)]:
        want = ref.generate(jnp.asarray(p)[None], n).tokens[0]
        np.testing.assert_array_equal(want, done[uid].tokens, err_msg=f"uid={uid}")
    assert eng.pool.blocks_in_use <= kept_before  # nothing leaked by the cycle
    _assert_drained_clean(eng)


@pytest.mark.parametrize("family", ["dense", "hybrid"], ids=["dense", "hybrid"])
def test_speculative_rollback_never_touches_shared_blocks(family):
    """Speculative decode over warm requests: rejected-draft rollback
    un-scatters only private rows — the pool-level guard would raise if a
    shared/cached block were addressed — and streams stay parity-exact."""
    shared = _prompt(8, seed=21)
    p1 = np.concatenate([shared, _prompt(3, seed=22)])
    p2 = np.concatenate([shared, _prompt(3, seed=23)])
    eng, _ = _engine(family, prefix_cache=True, spec_k=2, draft_ratio=0.5)
    base, _ = _engine(family, prefix_cache=True, spec_k=0, draft_ratio=0.5)
    reqs = [Request(uid=1, prompt=p1, max_new=6), Request(uid=2, prompt=p2, max_new=6)]
    done = eng.run([Request(uid=r.uid, prompt=r.prompt, max_new=r.max_new)
                    for r in reqs])
    want = base.run([Request(uid=r.uid, prompt=r.prompt, max_new=r.max_new)
                     for r in reqs])
    for r in reqs:
        np.testing.assert_array_equal(want[r.uid].tokens, done[r.uid].tokens,
                                      err_msg=f"uid={r.uid}")
    assert eng.spec_ticks >= 1
    _assert_drained_clean(eng)


def test_mid_prefill_abort_holding_shared_blocks():
    """Aborting a warm request mid-prefill releases exactly the references
    it held: the cache chain survives (including entries the aborted
    request itself registered), and a follow-up request resumes from the
    deepened chain to a bit-correct stream."""
    shared = _prompt(8, seed=31)
    p2 = np.concatenate([shared, _prompt(12, seed=32)])
    eng, ref = _engine(family="dense", prefix_cache=True, max_len=48)
    eng.run([Request(uid=1, prompt=shared, max_new=2)])
    eng.submit(Request(uid=2, prompt=p2, max_new=4))
    e, _ = _step_until(eng, 2, ReqState.PREFILLING)
    eng.step()  # push past the fork so it registers private blocks
    assert e.state is ReqState.PREFILLING
    assert 8 < e.prefill_pos < len(p2)
    entries_before = len(eng.pool.prefix_cache.entries)
    out = eng.abort(2)
    assert out is not None and out.finish_reason == "aborted"
    # the chain survived the abort — nothing was freed out from under it
    assert len(eng.pool.prefix_cache.entries) == entries_before
    # ... and it is still servable: deeper fork (the aborted request's own
    # registrations), same bits
    done = eng.run([Request(uid=3, prompt=p2, max_new=4)])
    want = ref.generate(jnp.asarray(p2)[None], 4).tokens[0]
    np.testing.assert_array_equal(want, done[3].tokens)
    pc = eng.pool.prefix_cache
    assert pc.hits >= 2
    assert pc.tokens_saved >= 8 + 12  # uid 3 forked past uid 2's fork point
    # NOTE: _assert_drained_clean evicts the whole index, so it must come last
    _assert_drained_clean(eng)


def test_abort_while_swapped_releases_shared_references():
    """A swapped-out warm request holds device-side references on its
    shared blocks; aborting it in that state must drop exactly those."""
    shared = _prompt(8, seed=41)
    p2 = np.concatenate([shared, _prompt(3, seed=42)])
    eng, _ = _engine(family="dense", prefix_cache=True,
                     preemption=PreemptionConfig(mode="swap"))
    eng.run([Request(uid=1, prompt=shared, max_new=2)])
    eng.submit(Request(uid=2, prompt=p2, max_new=6)
               )
    e, _ = _step_until(eng, 2, ReqState.RUNNING, min_outputs=1)
    eng._preempt(e, "swap")
    assert e.state is ReqState.PREEMPTED_SWAPPED and len(e.swap.kept) >= 1
    shared_ids = [b for _, b in e.swap.kept]
    for b in shared_ids:
        assert eng.pool.allocator.refcount(b) == 1  # pinned by the swap
    out = eng.abort(2)
    assert out is not None
    for b in shared_ids:
        assert eng.pool.allocator.refcount(b) == 0  # reference dropped
    _assert_drained_clean(eng)


# -- eviction under pressure --------------------------------------------------


def test_cache_eviction_under_block_pressure():
    """When the free stack runs dry, allocation reclaims refcount-0 cached
    blocks leaf-first (LRU) instead of failing — and a post-eviction
    lookup of the evicted prefix degrades to a (correct) shallower hit or
    miss, never to wrong KV."""
    eng, ref = _engine(family="dense", prefix_cache=True, max_slots=2,
                       num_blocks=9, max_len=24)
    # 16-token prompts: request 1 retains 4 cached blocks, leaving 4 free of
    # the 8 usable — request 2 needs 5, so the free stack alone can't serve it
    pa = _prompt(16, seed=51)
    pb = _prompt(16, seed=52)
    done = eng.run([Request(uid=1, prompt=pa, max_new=3)])
    cached0 = len(eng.pool.prefix_cache.entries)
    assert cached0 >= 2
    # an unrelated prompt needs more blocks than the free stack holds:
    # admission must evict cached blocks rather than stall
    done2 = eng.run([Request(uid=2, prompt=pb, max_new=3)])
    assert eng.pool.prefix_cache.evictions >= 1
    for uid, p, d in [(1, pa, done), (2, pb, done2)]:
        want = ref.generate(jnp.asarray(p)[None], 3).tokens[0]
        np.testing.assert_array_equal(want, d[uid].tokens)
    # whatever survives is still internally consistent
    _assert_drained_clean(eng)


def test_admit_prefix_degrades_cleanly_when_chain_is_the_only_slack():
    """``admit_prefix`` pins the hit chain before allocating the private
    remainder; when the chain was the pool's only reclaimable slack that
    allocation must fail all-or-nothing: None back, every refcount, the
    retained counter, and the free-slot stack exactly restored — and a
    cold admission of the first-chunk footprint must then succeed by
    evicting the unpinnable chain."""
    model = build_model(DENSE)
    pool = BlockPool(model, max_slots=3, max_len=32, block_size=4,
                     num_blocks=7, prefix_cache=True)
    prompt = _prompt(20, seed=81)
    s0 = pool.admit(16)
    pool.lengths[s0] = 16
    pool.register_prefix(s0, prompt, 16, resumable=True)
    s1 = pool.admit(8)  # blocker: consumes the remaining free blocks
    assert pool.n_free_blocks == 0
    pool.free(s0)  # chain retained at refcount 0: the only slack
    assert pool.n_reclaimable_blocks == 4
    fork, entries = pool.lookup_prefix(prompt, CT)
    assert fork == 16 and len(entries) == 4
    free_slots = list(pool._free_slots)
    assert pool.admit_prefix(20, entries) is None
    assert pool.n_reclaimable_blocks == 4  # references dropped back
    for e in entries:
        assert pool.allocator.refcount(e.block) == 0
    assert pool._free_slots == free_slots and pool.n_free_blocks == 0
    pool.cancel_prefix_hit(fork)
    pc = pool.prefix_cache
    assert pc.hits == 0 and pc.misses == 1 and pc.tokens_saved == 0
    s2 = pool.admit(4)  # cold path: eviction reclaims the chain
    assert s2 is not None and pc.evictions >= 1
    pool.free(s2)
    pool.free(s1)


def test_warm_admission_falls_back_cold_under_pin_pressure():
    """Engine regression: a cache hit whose chain is the pool's only
    reclaimable slack used to crash the admission tick (``admit_prefix``
    -> None -> assert).  The engine must instead degrade that admission
    to a cold prefill — evicting the unpinnable chain under its own
    allocation — with telemetry canceled back to a miss and streams still
    reference-identical."""
    eng, ref = _engine(family="dense", prefix_cache=True, max_slots=2,
                       num_blocks=8, max_len=32, decode_chunk=1)
    pa = _prompt(16, seed=91)
    pd = _prompt(8, seed=92)
    pw = np.concatenate([pa, _prompt(4, seed=93)])
    done = dict(eng.run([Request(uid=1, prompt=pa, max_new=1)]))
    assert eng.pool.n_reclaimable_blocks == 4  # uid 1's chain is retained
    # drive an unrelated request until it drains the free stack to zero
    # while the chain is the entire remaining (reclaimable) supply
    eng.submit(Request(uid=2, prompt=pd, max_new=6))
    for _ in range(100):
        eng.step()
        e2 = eng.lc.entries.get(2)
        if (e2 is not None and e2.state is ReqState.RUNNING
                and eng.pool.n_free_blocks == 0):
            break
    else:
        raise AssertionError("never reached the zero-free pressure window")
    eng.submit(Request(uid=3, prompt=pw, max_new=2))
    eng.step()  # admission tick: warm bind fails, cold fallback admits
    e3 = eng.lc.entries[3]
    assert e3.slot >= 0 and e3.cached_rows == 0  # admitted, cold
    pc = eng.pool.prefix_cache
    assert pc.hits == 0  # the unbindable hit was canceled back to a miss
    done.update(eng.run())
    for uid, p, n in [(1, pa, 1), (2, pd, 6), (3, pw, 2)]:
        want = ref.generate(jnp.asarray(p)[None], n).tokens[0]
        np.testing.assert_array_equal(want, done[uid].tokens, err_msg=f"uid={uid}")
    _assert_drained_clean(eng)


def test_blockless_cap_evicts_lru_leaves_first():
    """Unit: block-less chains (pure-state families) are capped by LRU
    leaf-first eviction at insert time — the oldest chain goes, the
    newest survives whole."""
    pc = PrefixCache(4, max_blockless=4)
    a, b, c = (np.arange(s, s + 8, dtype=np.int32) for s in (0, 8, 16))
    pc.insert_chain(a, 8, None, resumable=True)  # 2 entries
    pc.insert_chain(b, 8, None, resumable=True)  # 4 entries: at cap
    assert len(pc.entries) == 4
    pc.insert_chain(c, 8, None, resumable=True)  # 6 -> evict chain a
    assert len(pc.entries) == 4 and pc.evictions == 2
    ext = lambda p: np.concatenate([p, np.zeros(2, np.int32)])
    assert pc.lookup(ext(a), 4)[0] == 0  # evicted: clean miss
    assert pc.lookup(ext(b), 4)[0] == 8  # survivors intact
    assert pc.lookup(ext(c), 4)[0] == 8


def test_blockless_cache_is_bounded_for_pure_state_family():
    """rwkv6 regression: block-less entries carry full state-row resume
    snapshots and see no allocation pressure (no paged blocks), so
    without a cap a stream of distinct prompts would grow device memory
    without bound.  The cap holds, and post-eviction lookups still serve
    bit-identical streams."""
    eng, ref = _engine(family="rwkv6", prefix_cache=True)
    eng.pool.prefix_cache.max_blockless = 5
    prompts = [_prompt(12, seed=100 + i) for i in range(8)]
    for i, p in enumerate(prompts):
        eng.run([Request(uid=i, prompt=p, max_new=2)])
    pc = eng.pool.prefix_cache
    assert len(pc.entries) <= 5 and pc.evictions >= 1
    # an evicted-chain prompt degrades to a shallower hit or miss, never
    # to wrong state
    done = eng.run([Request(uid=99, prompt=prompts[0], max_new=2)])
    want = ref.generate(jnp.asarray(prompts[0])[None], 2).tokens[0]
    np.testing.assert_array_equal(want, done[99].tokens)
    _assert_drained_clean(eng)


def test_swap_out_all_shared_reports_zero_paged_bytes():
    """Telemetry regression: a request whose every block is
    cache-registered swaps out zero private blocks, and the padded
    trash-block gather must not be booked as live bytes moved."""
    model = build_model(DENSE)
    pool = BlockPool(model, max_slots=2, max_len=32, block_size=4,
                     num_blocks=7, prefix_cache=True)
    prompt = _prompt(8, seed=71)
    slot = pool.admit(8)
    pool.lengths[slot] = 8
    pool.register_prefix(slot, prompt, 8, resumable=True)
    sw = pool.swap_out(slot)
    assert sw.n_blocks == 0 and len(sw.kept) == 2
    assert sw.nbytes == 0  # dense family: every cache leaf is paged
    s2 = pool.swap_in(sw)
    assert s2 is not None and pool.held_blocks(s2) == 2
    assert int(pool.lengths[s2]) == 8
    pool.free(s2)


# -- pool-leak regression over randomized shared-prefix workloads -------------


@settings(max_examples=6, deadline=None)
@given(
    st.integers(min_value=0, max_value=2 ** 31 - 1),
    st.lists(st.tuples(st.integers(min_value=0, max_value=2),  # prefix family
                       st.integers(min_value=0, max_value=9),  # tail length
                       st.integers(min_value=1, max_value=5),  # max_new
                       st.integers(min_value=0, max_value=6)),  # arrival
             min_size=1, max_size=6),
)
def test_pool_leak_regression_randomized_shared_prefix(seed, spec):
    """Drain a randomized shared-prefix workload on a tight pool (eviction
    + preemption in play): streams match single-request serving, and the
    drained pool holds ONLY refcount-0 cache-indexed blocks — evicting the
    index restores the initial free stack exactly."""
    rng = np.random.RandomState(seed % (2 ** 31))
    prefixes = [rng.randint(3, 101, size=8).astype(np.int32) for _ in range(3)]
    eng, ref = _engine(family="dense", prefix_cache=True, max_slots=2,
                       num_blocks=13, max_len=32,
                       preemption=PreemptionConfig(mode="recompute"))
    reqs = []
    for i, (fam, tl, mn, arr) in enumerate(spec):
        tail = rng.randint(3, 101, size=tl).astype(np.int32)
        p = np.concatenate([prefixes[fam], tail])
        reqs.append(Request(uid=i, prompt=p, max_new=mn, arrival=arr))
    done = eng.run(reqs)
    for r in reqs:
        want = ref.generate(jnp.asarray(r.prompt)[None], r.max_new).tokens[0]
        np.testing.assert_array_equal(want, done[r.uid].tokens,
                                      err_msg=f"uid={r.uid}")
    _assert_drained_clean(eng)


# -- telemetry ----------------------------------------------------------------


def test_hit_rate_and_tokens_saved_telemetry():
    """N requests over one shared prefix: first misses, the rest hit —
    hit rate (N-1)/N and tokens_saved = (N-1) * fork."""
    shared = _prompt(12, seed=61)
    eng, _ = _engine(family="dense", prefix_cache=True)
    N = 5
    for i in range(N):
        tail = _prompt(2, seed=70 + i, lo=4)
        eng.run([Request(uid=i, prompt=np.concatenate([shared, tail]), max_new=2)])
    pc = eng.pool.prefix_cache
    assert pc.misses == 1 and pc.hits == N - 1
    assert pc.hit_rate == pytest.approx((N - 1) / N)
    assert pc.tokens_saved == (N - 1) * 12
