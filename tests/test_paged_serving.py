"""Paged KV block-table serving: allocator safety, chunked-prefill parity,
bounded per-tick prefill work, block-sparse decode, and admission policies.

The load-bearing property mirrors PR 1's: for greedy decoding the
``PagedEngine`` (block table + chunked prefill) must be TOKEN-IDENTICAL to
the slot-arena ``ContinuousEngine`` and to single-request static serving —
regardless of chunk boundaries, block reuse, interleaved prefill/decode
ticks, or which other requests share the pool.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import GlassConfig
from repro.models import ModelConfig, build_model
from repro.serve.engine import ContinuousEngine, Engine, PagedEngine
from repro.serve.kv_pool import BlockAllocator, BlockPool, paged_layout
from repro.serve.scheduler import AdmissionPolicy, Request, Scheduler

BASE = dict(n_layers=2, d_model=48, n_heads=4, n_kv_heads=2, head_dim=12,
            d_ff=96, vocab_size=101, dtype="float32", remat="none")
DENSE = ModelConfig(name="pg-dense", family="dense", **BASE)
MOE = ModelConfig(name="pg-moe", family="moe", n_experts=4, n_experts_per_tok=2,
                  moe_strategy="dense", **BASE)
SSM = ModelConfig(name="pg-ssm", family="ssm", rwkv_headdim=12, **BASE)
HYBRID = ModelConfig(name="pg-hybrid", family="hybrid", attn_every=2,
                     ssm_state=16, mamba_headdim=12, **{**BASE, "n_layers": 4})


def _prior_for(cfg: ModelConfig):
    if cfg.family == "moe":
        shape = (cfg.n_layers, cfg.n_experts, cfg.d_ff)
    elif cfg.family == "hybrid":
        shape = (cfg.d_ff,)
    else:
        shape = (cfg.n_layers, cfg.d_ff)
    return jnp.abs(jax.random.normal(jax.random.key(7), shape))


def _requests(spec, seed=0):
    """spec: list of (prompt_len, max_new, arrival)."""
    rng = np.random.RandomState(seed)
    return [
        Request(uid=i, prompt=rng.randint(3, 101, size=l).astype(np.int32),
                max_new=n, arrival=a)
        for i, (l, n, a) in enumerate(spec)
    ]


def _assert_paged_parity(cfg, glass, mode, spec, *, chunk_tokens=3, max_slots=2,
                         block_size=8, num_blocks=None):
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    prior = _prior_for(cfg) if glass else None
    reqs = _requests(spec)
    eng = PagedEngine(model, params, max_slots=max_slots, max_len=32,
                      block_size=block_size, num_blocks=num_blocks,
                      chunk_tokens=chunk_tokens, glass=glass,
                      global_prior=prior, glass_mode=mode)
    done = eng.run(reqs)
    ref = Engine(model, params, glass=glass, global_prior=prior, glass_mode=mode)
    for r in reqs:
        want = ref.generate(jnp.asarray(r.prompt)[None], r.max_new).tokens[0]
        np.testing.assert_array_equal(want, done[r.uid].tokens, err_msg=f"uid={r.uid}")
    return eng


# -- block allocator ----------------------------------------------------------


@settings(max_examples=25, deadline=None)
@given(
    st.integers(min_value=2, max_value=13),
    st.lists(st.tuples(st.booleans(), st.integers(min_value=0, max_value=5)),
             max_size=40),
)
def test_block_allocator_properties(nb, ops):
    """Random alloc/free interleavings: handed-out blocks stay disjoint,
    the trash block is never handed out, and accounting balances."""
    alloc = BlockAllocator(nb)
    held = []  # list of lists
    for do_alloc, n in ops:
        if do_alloc or not held:
            got = alloc.alloc(n)
            total_held = sum(len(h) for h in held)
            if n <= nb - 1 - total_held:
                assert got is not None and len(got) == n
                held.append(got)
            else:
                assert got is None  # all-or-nothing
        else:
            alloc.release(held.pop(0))  # sole owner: decref-to-zero + free
        flat = [b for h in held for b in h]
        assert len(flat) == len(set(flat))  # no block owned twice
        assert BlockAllocator.TRASH not in flat
        assert alloc.n_free + alloc.n_live == nb - 1
        assert alloc.n_live == len(flat)


def test_block_allocator_double_free_raises():
    alloc = BlockAllocator(6)
    a = alloc.alloc(2)
    alloc.release(a)
    with pytest.raises(ValueError):
        alloc.free(a)  # double free (no longer live)
    with pytest.raises(ValueError):
        alloc.free([99])  # foreign id
    b = alloc.alloc(5)
    assert b is not None and BlockAllocator.TRASH not in b
    assert alloc.alloc(1) is None
    with pytest.raises(ValueError):
        alloc.free(b)  # still referenced: strict free refuses owned blocks


@settings(max_examples=40, deadline=None)
@given(
    st.integers(min_value=2, max_value=13),
    st.lists(
        st.tuples(st.integers(min_value=0, max_value=3),
                  st.integers(min_value=0, max_value=5)),
        max_size=60,
    ),
)
def test_block_allocator_refcount_properties(nb, ops):
    """Random fork/share/free/evict interleavings over the refcounted
    allocator: no double-free, a referenced block is never freed, zeroed
    blocks stay live until explicitly freed (the cache-retention state),
    and draining all owners returns every block to the free stack."""
    alloc = BlockAllocator(nb)
    held = []  # list of lists: each inner list is one ownership reference
    retained = []  # refcount-0 blocks kept live (simulated cache entries)
    for op, n in ops:
        if op == 0 or not (held or retained):  # alloc
            got = alloc.alloc(n)
            in_use = len({b for h in held for b in h} | set(retained))
            if n <= nb - 1 - in_use:
                assert got is not None and len(got) == n
                for b in got:
                    assert alloc.refcount(b) == 1
                held.append(got)
            else:
                assert got is None  # all-or-nothing
        elif op == 1 and held:  # share: a second owner increfs the chain
            src = held[n % len(held)]
            alloc.incref(src)
            held.append(list(src))
        elif op == 2 and held:  # drop one reference
            h = held.pop(n % len(held))
            before = {b: alloc.refcount(b) for b in set(h)}
            zeroed = alloc.decref(h)
            for b in set(h):
                if before[b] == h.count(b):  # this was the last owner
                    assert b in zeroed or h.count(b) > 1
            for b in zeroed:
                assert alloc.refcount(b) == 0  # live but unowned
                with pytest.raises(ValueError):
                    alloc.free([b, b])  # double id in one free call
            retained.extend(dict.fromkeys(zeroed))
        elif retained:  # evict one retained block
            b = retained.pop(n % len(retained))
            alloc.free([b])
            with pytest.raises(ValueError):
                alloc.free([b])  # double free
        owned = {b for h in held for b in h}
        for h in held:
            for b in set(h):
                assert alloc.refcount(b) >= 1
        for b in retained:
            if b not in owned:
                assert alloc.refcount(b) == 0
        assert BlockAllocator.TRASH not in owned
        assert alloc.n_free + alloc.n_live == nb - 1
    # drain: releasing every reference then freeing retained blocks returns
    # the allocator to its initial all-free state
    for h in held:
        retained.extend(alloc.decref(h))
    for b in retained:
        assert alloc.refcount(b) == 0  # every refcount reached zero by drain
        alloc.free([b])
    assert alloc.n_live == 0
    assert alloc.n_free == nb - 1


@pytest.mark.parametrize("cfg", [DENSE, SSM, HYBRID], ids=["dense", "ssm", "hybrid"])
def test_paged_layout_discovery(cfg):
    """Leaves with a sequence axis are paged; recurrent state is not, and
    the discovered axes index the real batch/seq dims."""
    model = build_model(cfg)
    axes, seq_axes, paged = paged_layout(model, max_len=16)
    cache = jax.eval_shape(lambda: model.init_cache(3, 16))
    any_paged = False
    for leaf, ax, sq, pg in zip(jax.tree.leaves(cache), jax.tree.leaves(axes),
                                jax.tree.leaves(seq_axes), jax.tree.leaves(paged)):
        assert leaf.shape[ax] == 3
        if pg:
            any_paged = True
            assert leaf.shape[sq] == 16 and sq == ax + 1
    assert any_paged == (cfg.family != "ssm")


def test_block_pool_admit_free_roundtrip():
    model = build_model(DENSE)
    pool = BlockPool(model, max_slots=2, max_len=32, block_size=8, num_blocks=7)
    s0 = pool.admit(20)  # 3 blocks
    s1 = pool.admit(17)  # 3 blocks
    assert {s0, s1} == {0, 1}
    assert pool.blocks_in_use == 6 and pool.n_free_blocks == 0
    assert pool.admit(1) is None  # out of slots AND blocks
    assert not pool.fits(8)
    table0 = pool.block_table[s0].copy()
    assert (table0[:3] > 0).all() and (table0[3:] == 0).all()
    pool.free(s0)
    assert pool.blocks_in_use == 3 and pool.n_free_blocks == 3
    with pytest.raises(ValueError):
        pool.free(s0)  # not active
    s2 = pool.admit(24)
    assert s2 == s0 and pool.peak_blocks == 6


# -- chunked-prefill + paged decode parity ------------------------------------

STAGGERED = [(7, 5, 0), (6, 3, 1), (5, 6, 2)]


def test_paged_parity_dense_glass():
    eng = _assert_paged_parity(DENSE, GlassConfig(density=0.5), "compact", STAGGERED)
    # chunked prefill really ran multi-chunk (prompt 7 > chunk 3)
    assert eng.max_prefill_tokens_per_tick == 3


def test_paged_parity_dense_no_glass():
    _assert_paged_parity(DENSE, None, "compact", STAGGERED)


@pytest.mark.parametrize("mode", ["masked", "compact"])
def test_chunked_prefill_parity_moe_slow(mode):
    _assert_paged_parity(MOE, GlassConfig(density=0.5), mode, STAGGERED)


def test_chunked_prefill_parity_ssm_slow():
    _assert_paged_parity(SSM, GlassConfig(density=0.5), "masked", STAGGERED)


def test_chunked_prefill_parity_hybrid_slow():
    _assert_paged_parity(HYBRID, GlassConfig(density=0.5), "compact", STAGGERED)


def test_block_reuse_no_kv_leak_slow():
    """A tight pool (blocks for ~1.5 requests) forces every request to reuse
    the previous occupants' blocks; outputs must match fresh single-request
    serving, so no KV can leak through reused blocks."""
    model = build_model(DENSE)
    params = model.init(jax.random.key(0))
    prior = _prior_for(DENSE)
    spec = [(8, 6, 0), (4, 3, 0), (6, 8, 0)]  # shrinking then growing footprints
    reqs = _requests(spec)
    eng = PagedEngine(model, params, max_slots=2, max_len=32, block_size=8,
                      num_blocks=4, chunk_tokens=4,
                      glass=GlassConfig(density=0.5), global_prior=prior)
    done = eng.run(reqs)
    assert eng.pool.peak_blocks <= 3
    ref = Engine(model, params, glass=GlassConfig(density=0.5), global_prior=prior)
    for r in reqs:
        want = ref.generate(jnp.asarray(r.prompt)[None], r.max_new).tokens[0]
        np.testing.assert_array_equal(want, done[r.uid].tokens, err_msg=f"uid={r.uid}")


def test_prefill_work_bounded_long_prompt():
    """A long prompt must be admitted in bounded chunks with decode ticks of
    a live request interleaved between them — bounded admission latency."""
    model = build_model(DENSE)
    params = model.init(jax.random.key(0))
    rng = np.random.RandomState(3)
    short = Request(uid=0, prompt=rng.randint(3, 101, size=4).astype(np.int32),
                    max_new=12, arrival=0)
    long_ = Request(uid=1, prompt=rng.randint(3, 101, size=24).astype(np.int32),
                    max_new=3, arrival=2)
    eng = PagedEngine(model, params, max_slots=2, max_len=32, block_size=8,
                      chunk_tokens=4)
    done = eng.run([short, long_])
    assert eng.max_prefill_tokens_per_tick <= 4  # per-tick prefill work bound
    # the short request kept decoding during the 6 chunk ticks: it finished
    # well before a serial (prefill-long-first) schedule would allow
    assert done[0].finished_step <= short.arrival + 1 + short.max_new + 2
    ref = Engine(model, params)
    for r in (short, long_):
        want = ref.generate(jnp.asarray(r.prompt)[None], r.max_new).tokens[0]
        np.testing.assert_array_equal(want, done[r.uid].tokens)
    # allocated-KV accounting: the paged pool integrated strictly less
    # memory over time than the always-fully-allocated slot arena would
    arena_row_ticks = eng.pool.max_slots * eng.pool.max_len * eng.t
    assert 0 < eng.kv_row_ticks < arena_row_ticks


# -- block-sparse decode path -------------------------------------------------


def test_block_sparse_rowwise_kernel_matches_oracle():
    from repro.kernels.ops import glass_ffn_rowwise

    rng = np.random.RandomState(0)
    B, d, m, bs = 4, 16, 128, 32
    x = jnp.asarray(rng.randn(B, d), jnp.float32)
    wu = jnp.asarray(rng.randn(d, m), jnp.float32)
    wd = jnp.asarray(rng.randn(m, d), jnp.float32)
    wg = jnp.asarray(rng.randn(d, m), jnp.float32)
    bidx = jnp.asarray([[0, 2], [1, 3], [0, 1], [2, 3]], jnp.int32)
    out = glass_ffn_rowwise(x, wu, wd, bidx, wg, act="silu", block_size=bs,
                            interpret=True)
    for b in range(B):
        mask = np.zeros(m, np.float32)
        for blk in np.asarray(bidx[b]):
            mask[blk * bs : (blk + 1) * bs] = 1.0
        h = np.asarray(jax.nn.silu(x[b] @ wg)) * np.asarray(x[b] @ wu) * mask
        np.testing.assert_allclose(out[b], h @ wd, rtol=2e-5, atol=2e-5)


def test_paged_block_sparse_matches_masked_slow():
    """block_sparse (pallas kernel on per-slot block lists) and masked
    (dense matmul times the same block mask) are the same function."""
    model = build_model(DENSE)
    params = model.init(jax.random.key(0))
    prior = _prior_for(DENSE)
    gc = GlassConfig(density=0.5, selection="block", block_size=32)
    reqs = _requests(STAGGERED)
    outs = {}
    for mode in ("block_sparse", "masked"):
        eng = PagedEngine(model, params, max_slots=2, max_len=32, block_size=8,
                          chunk_tokens=3, glass=gc, global_prior=prior,
                          glass_mode=mode)
        outs[mode] = eng.run(reqs)
    for r in reqs:
        np.testing.assert_array_equal(outs["block_sparse"][r.uid].tokens,
                                      outs["masked"][r.uid].tokens)


def test_block_sparse_rejects_bad_config():
    model = build_model(MOE)
    params = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    with pytest.raises(NotImplementedError):
        PagedEngine(model, params, glass=GlassConfig(density=0.5, selection="block"),
                    global_prior=_prior_for(MOE), glass_mode="block_sparse")
    dmodel = build_model(DENSE)
    with pytest.raises(ValueError):
        PagedEngine(dmodel, params, glass=GlassConfig(density=0.5),  # neuron selection
                    global_prior=_prior_for(DENSE), glass_mode="block_sparse")
    with pytest.raises(ValueError):
        # block selection yields block ids: gathering compact weights with
        # them would silently select the wrong units
        PagedEngine(dmodel, params,
                    glass=GlassConfig(density=0.5, selection="block", block_size=32),
                    global_prior=_prior_for(DENSE), glass_mode="compact")


# -- admission policies -------------------------------------------------------


def _policy_requests():
    reqs = [
        Request(uid=0, prompt=np.zeros(4, np.int32), max_new=4, priority=0),
        Request(uid=1, prompt=np.zeros(4, np.int32), max_new=4, priority=5,
                deadline=30),
        Request(uid=2, prompt=np.zeros(4, np.int32), max_new=4, priority=1,
                deadline=10),
    ]
    return reqs


def test_admission_policy_fifo():
    s = Scheduler(max_len=32, policy=AdmissionPolicy.FIFO)
    for r in _policy_requests():
        s.submit(r)
    assert [r.uid for r in s.pop_admissible(0, 3)] == [0, 1, 2]


def test_admission_policy_priority():
    s = Scheduler(max_len=32, policy=AdmissionPolicy.PRIORITY)
    for r in _policy_requests():
        s.submit(r)
    assert [r.uid for r in s.pop_admissible(0, 3)] == [1, 2, 0]


def test_admission_policy_deadline():
    s = Scheduler(max_len=32, policy=AdmissionPolicy.DEADLINE)
    for r in _policy_requests():
        s.submit(r)
    # EDF: uid2 (deadline 10), uid1 (30), uid0 (no deadline -> last)
    assert [r.uid for r in s.pop_admissible(0, 3)] == [2, 1, 0]


def test_run_validates_block_capacity():
    """run() must route through PagedEngine.submit's capacity check: an
    over-capacity request raises a ValueError naming the shortfall instead
    of spinning until the drain-budget RuntimeError."""
    model = build_model(DENSE)
    params = model.init(jax.random.key(0))
    eng = PagedEngine(model, params, max_slots=1, max_len=32, block_size=8,
                      num_blocks=3, chunk_tokens=4)
    with pytest.raises(ValueError, match="blocks > pool capacity"):
        eng.run([Request(uid=0, prompt=np.zeros(20, np.int32), max_new=10)])


def test_admission_pop_never_compares_requests():
    """Regression: picking a non-head request must remove it by index, not
    by equality — deque.remove would invoke the dataclass __eq__, which
    compares the ndarray prompt and raises whenever two queued requests
    share a uid (e.g. a retried submission)."""
    s = Scheduler(max_len=32, policy=AdmissionPolicy.DEADLINE)
    s.submit(Request(uid=7, prompt=np.zeros(4, np.int32), max_new=4, deadline=50))
    s.submit(Request(uid=7, prompt=np.ones(4, np.int32), max_new=4, deadline=5))
    got = s.pop_admissible(0, 2)
    assert [r.deadline for r in got] == [5, 50]


def test_admission_fits_filter_skips_not_blocks():
    """A request that doesn't fit is skipped (stays queued), later smaller
    ones are admitted, and capacity consumed by a pick is visible to the
    next pick."""
    s = Scheduler(max_len=64, policy=AdmissionPolicy.FIFO)
    big = Request(uid=0, prompt=np.zeros(40, np.int32), max_new=8)
    small1 = Request(uid=1, prompt=np.zeros(4, np.int32), max_new=4)
    small2 = Request(uid=2, prompt=np.zeros(4, np.int32), max_new=4)
    for r in (big, small1, small2):
        s.submit(r)
    free = [14]  # free KV rows; each small request needs 7, big needs 47

    def fits(r):
        return len(r.prompt) + r.max_new - 1 <= free[0]

    got = []
    while True:
        picked = s.pop_admissible(0, 1, fits=fits)
        if not picked:
            break
        free[0] -= len(picked[0].prompt) + picked[0].max_new - 1
        got.append(picked[0].uid)
    assert got == [1, 2]  # big skipped, still queued
    assert [r.uid for r in s.queue] == [0]


def test_paged_engine_priority_order_slow():
    """With one slot, PRIORITY admission must serve the high-priority
    request first even though it was submitted last."""
    model = build_model(DENSE)
    params = model.init(jax.random.key(0))
    rng = np.random.RandomState(0)
    reqs = [
        Request(uid=i, prompt=rng.randint(3, 101, size=4).astype(np.int32),
                max_new=3, priority=p)
        for i, p in enumerate([0, 0, 9])
    ]
    eng = PagedEngine(model, params, max_slots=1, max_len=16, block_size=8,
                      chunk_tokens=8, policy=AdmissionPolicy.PRIORITY)
    done = eng.run(reqs)
    assert done[2].finished_step < done[0].finished_step
    assert done[2].finished_step < done[1].finished_step


# -- Engine jit-cache invalidation --------------------------------------------


def test_engine_params_identity_evicts_jit_cache():
    model = build_model(DENSE)
    p1 = model.init(jax.random.key(0))
    p2 = model.init(jax.random.key(1))
    eng = Engine(model, p1)
    prompts = jnp.asarray(np.arange(4, dtype=np.int32))[None] + 3
    out1 = eng.generate(prompts, 4).tokens
    assert len(eng._jits) > 0
    eng.params = p2  # new identity -> cache must be evicted
    assert len(eng._jits) == 0
    out2 = eng.generate(prompts, 4).tokens
    fresh = Engine(model, p2).generate(prompts, 4).tokens
    np.testing.assert_array_equal(out2, fresh)
    assert not np.array_equal(out1, out2)  # different weights really served
    eng.params = p2  # same identity -> cache kept
    assert len(eng._jits) > 0
