"""Request-lifecycle state machine: incremental block allocation, preemption
with swap/recompute, and resume parity.

Token-identical greedy streams can hide serving-state corruption (argmax
absorbs small numeric damage), so the load-bearing tests here assert
STATE-LEVEL invariants:

  * swap-out -> swap-in restores the request's gathered KV block contents
    and recurrent-state rows BIT-identical (np equality, not allclose);
  * recompute replays the prompt through chunked prefill and reproduces the
    IDENTICAL fused GLASS mask (running sums over the same chunk
    boundaries), then re-feeds the generated prefix as forced tokens;
  * allocate-on-boundary never leaks or double-allocates blocks, keeps the
    block table consistent with the holdings, and admissions never breach
    the watermark reserve.

Token parity vs fresh single-request serving is asserted on top, for both
preemption kinds, across all four model families.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import GlassConfig
from repro.models import ModelConfig, build_model
from repro.serve.engine import Engine, PagedEngine
from repro.serve.kv_pool import BlockPool
from repro.serve.lifecycle import (
    Lifecycle,
    PreemptionConfig,
    ReqState,
    preemption_kind,
)
from repro.serve.scheduler import AdmissionPolicy, Request, Scheduler

BASE = dict(n_layers=2, d_model=48, n_heads=4, n_kv_heads=2, head_dim=12,
            d_ff=96, vocab_size=101, dtype="float32", remat="none")
DENSE = ModelConfig(name="lc-dense", family="dense", **BASE)
MOE = ModelConfig(name="lc-moe", family="moe", n_experts=4, n_experts_per_tok=2,
                  moe_strategy="dense", **BASE)
SSM = ModelConfig(name="lc-ssm", family="ssm", rwkv_headdim=12, **BASE)
HYBRID = ModelConfig(name="lc-hybrid", family="hybrid", attn_every=2,
                     ssm_state=16, mamba_headdim=12, **{**BASE, "n_layers": 4})

FAMILIES = {
    "dense": (DENSE, "compact"),
    "moe": (MOE, "masked"),
    "rwkv6": (SSM, "masked"),
    "hybrid": (HYBRID, "compact"),
}


def _prior_for(cfg: ModelConfig):
    if cfg.family == "moe":
        shape = (cfg.n_layers, cfg.n_experts, cfg.d_ff)
    elif cfg.family == "hybrid":
        shape = (cfg.d_ff,)
    else:
        shape = (cfg.n_layers, cfg.d_ff)
    return jnp.abs(jax.random.normal(jax.random.key(7), shape))


def _requests(spec, seed=0):
    rng = np.random.RandomState(seed)
    return [
        Request(uid=i, prompt=rng.randint(3, 101, size=l).astype(np.int32),
                max_new=n, arrival=a)
        for i, (l, n, a) in enumerate(spec)
    ]


def _request_device_state(pool: BlockPool, slot: int):
    """Host copy of everything the pool holds for ``slot``: its KV blocks
    (whole blocks, in table order) and its recurrent-state rows."""
    held = list(pool._held.get(slot, ()))
    out = []
    for leaf, ax, pg in zip(
        jax.tree.leaves(pool.cache), jax.tree.leaves(pool.axes),
        jax.tree.leaves(pool.paged),
    ):
        a = np.asarray(leaf)
        out.append(np.take(a, held, axis=ax) if pg else np.take(a, [slot], axis=ax))
    return out


def _glass_rows(eng: PagedEngine, slot: int):
    gs = eng.glass_slots
    if gs is None or gs.arena is None:
        return None
    ax = gs.slot_axis
    return [np.take(np.asarray(a), [slot], axis=ax) for a in jax.tree.leaves(gs.arena)]


def _step_until(eng, uid, state, min_outputs=0, limit=300):
    done = []
    for _ in range(limit):
        done += eng.step()
        e = eng.lc.entries.get(uid)
        if e is not None and e.state is state and len(e.outputs) >= min_outputs:
            return e, done
    raise AssertionError(f"uid {uid} never reached {state} with >= {min_outputs} outputs")


# -- lifecycle state machine --------------------------------------------------


def test_lifecycle_transition_legality():
    lc = Lifecycle()
    e = lc.add(Request(uid=0, prompt=np.zeros(4, np.int32), max_new=2))
    assert e.state is ReqState.WAITING
    with pytest.raises(ValueError, match="illegal transition"):
        lc.to(e, ReqState.RUNNING)  # must prefill first
    lc.to(e, ReqState.PREFILLING)
    with pytest.raises(ValueError, match="illegal transition"):
        lc.to(e, ReqState.SPECULATING)  # speculation is a RUNNING sub-phase
    # mid-prefill swap-out is the migration handoff: partial prefill travels
    # to another engine at a chunk boundary instead of being recomputed
    lc.to(e, ReqState.PREEMPTED_SWAPPED)
    with pytest.raises(ValueError, match="illegal transition"):
        lc.to(e, ReqState.PREFILLING)  # swapped resumes via swap-in only
    lc.to(e, ReqState.MIGRATING)
    lc.to(e, ReqState.PREFILLING)  # destination resumes the partial prefill
    lc.to(e, ReqState.RUNNING)
    lc.to(e, ReqState.PREEMPTED_SWAPPED)
    # swap-store cap overflow: the swapped store is dropped and the victim
    # degrades to recompute (counted as a degrade, not a new preemption)
    lc.to(e, ReqState.PREEMPTED_RECOMPUTE)
    lc.to(e, ReqState.PREFILLING)
    lc.to(e, ReqState.RUNNING)
    lc.to(e, ReqState.FINISHED)
    with pytest.raises(ValueError, match="illegal transition"):
        lc.to(e, ReqState.RUNNING)
    # duplicate live uid is rejected; a finished uid may be re-registered
    e2 = lc.add(Request(uid=0, prompt=np.zeros(4, np.int32), max_new=2))
    lc.to(e2, ReqState.PREFILLING)
    with pytest.raises(ValueError, match="already live"):
        lc.add(Request(uid=0, prompt=np.zeros(4, np.int32), max_new=2))
    assert lc.counts[("running", "preempted_swapped")] == 1
    assert lc.counts[("prefilling", "preempted_swapped")] == 1
    assert lc.counts[("preempted_swapped", "preempted_recompute")] == 1
    assert lc.preempted() == 2 and lc.preempted(kind="swap") == 2
    assert lc.preempted(kind="recompute") == 0  # degrade is not a new event


def test_submit_rejects_live_uid_allows_finished_reuse():
    """uids key the lifecycle entries: resubmitting an in-flight uid fails
    fast at submit(); a finished uid is pruned and reusable (so warmup +
    measured waves through one engine instance keep working)."""
    model = build_model(DENSE)
    params = model.init(jax.random.key(0))
    eng = PagedEngine(model, params, max_slots=2, max_len=32, block_size=8,
                      chunk_tokens=4)
    r = Request(uid=3, prompt=np.arange(4, dtype=np.int32) + 3, max_new=3)
    eng.submit(r)
    with pytest.raises(ValueError, match="already in flight"):
        # duplicate while still QUEUED (no lifecycle entry exists yet)
        eng.submit(Request(uid=3, prompt=np.arange(6, dtype=np.int32) + 3, max_new=2))
    eng.step()  # now admitted and in flight
    with pytest.raises(ValueError, match="already in flight"):
        eng.submit(Request(uid=3, prompt=np.arange(6, dtype=np.int32) + 3, max_new=2))
    done = eng.run()
    assert done[3].tokens.shape == (3,)
    assert 3 not in eng.lc.entries  # FINISHED entries are pruned
    done2 = eng.run([Request(uid=3, prompt=np.arange(4, dtype=np.int32) + 3,
                             max_new=2)])
    assert done2[3].tokens.shape == (2,)


def test_preemption_cost_model():
    cfg = PreemptionConfig(mode="auto", swap_cost_per_block=2.0,
                           recompute_cost_per_token=1.0)
    assert preemption_kind(cfg, blocks_held=2, tokens_to_replay=100) == "swap"
    assert preemption_kind(cfg, blocks_held=10, tokens_to_replay=3) == "recompute"
    assert preemption_kind(PreemptionConfig(mode="swap"), 100, 1) == "swap"
    assert preemption_kind(PreemptionConfig(mode="recompute"), 1, 100) == "recompute"
    with pytest.raises(ValueError):
        PreemptionConfig(mode="bogus")


def test_victim_selection_mirrors_admission_order():
    reqs = [
        Request(uid=0, prompt=np.zeros(4, np.int32), max_new=4, priority=5, deadline=10),
        Request(uid=1, prompt=np.zeros(4, np.int32), max_new=4, priority=1, deadline=50),
        Request(uid=2, prompt=np.zeros(4, np.int32), max_new=4, priority=3, deadline=None),
    ]
    for policy, want in [
        (AdmissionPolicy.FIFO, 2),      # newest submission yields first
        (AdmissionPolicy.PRIORITY, 1),  # lowest priority yields first
        (AdmissionPolicy.DEADLINE, 2),  # deadline-less = latest deadline
    ]:
        s = Scheduler(max_len=32, policy=policy)
        for r in reqs:
            s.submit(r)
        assert s.select_victim(reqs).uid == want, policy
    assert Scheduler(max_len=32).select_victim([]) is None


# -- allocate-on-boundary property tests --------------------------------------


@settings(max_examples=12, deadline=None)
@given(st.lists(st.tuples(st.integers(min_value=0, max_value=3),
                          st.integers(min_value=1, max_value=40)), max_size=30))
def test_boundary_allocation_properties(ops):
    """Random admit/grow/free interleavings on a tight pool: block holdings
    stay disjoint, the block table prefix mirrors the holdings, accounting
    balances, growth is all-or-nothing, and watermark-gated admissions
    never leave fewer than ``watermark`` free blocks."""
    model = build_model(DENSE)
    pool = BlockPool(model, max_slots=3, max_len=64, block_size=8,
                     num_blocks=9, watermark=2)
    rows: dict = {}  # slot -> rows currently ensured
    for op, arg in ops:
        if op == 0 and pool.n_free_slots:  # admit (watermark-gated)
            was_idle = not pool.active.any()
            if pool.fits_admission(arg):
                free0 = pool.n_free_blocks
                slot = pool.admit(arg)
                assert slot is not None
                rows[slot] = arg
                # never breached by an admission — except the liveness
                # waiver on an idle pool (nobody to preempt, so the
                # reserve must not starve a big first chunk)
                assert was_idle or pool.n_free_blocks >= pool.watermark
                assert free0 - pool.n_free_blocks == pool.blocks_needed(arg)
        elif op == 1 and rows:  # grow (may consume the reserve)
            slot = sorted(rows)[arg % len(rows)]
            target = min(rows[slot] + arg, pool.max_len)
            held0 = pool.held_blocks(slot)
            ok = pool.ensure_capacity(slot, target)
            if ok:
                rows[slot] = max(rows[slot], target)
                assert pool.held_blocks(slot) == pool.blocks_needed(rows[slot])
            else:  # all-or-nothing: a failed grow changes nothing
                assert pool.held_blocks(slot) == held0
        elif op == 2 and rows:  # free
            slot = sorted(rows)[arg % len(rows)]
            pool.free(slot)
            del rows[slot]
        # global invariants after every op
        flat = [b for s in rows for b in pool._held[s]]
        assert len(flat) == len(set(flat))  # no block owned twice
        assert 0 not in flat  # trash never handed out
        assert pool.allocator.n_free + pool.allocator.n_live == pool.num_blocks - 1
        assert pool.allocator.n_live == len(flat)
        for s in rows:  # table prefix == holdings, rest trash
            held = pool._held[s]
            assert list(pool.block_table[s, : len(held)]) == held
            assert (pool.block_table[s, len(held):] == 0).all()


def test_ensure_capacity_is_boundary_granular():
    """Growth allocates exactly one block per crossed boundary, never the
    full worst case."""
    model = build_model(DENSE)
    pool = BlockPool(model, max_slots=2, max_len=64, block_size=8, num_blocks=9)
    slot = pool.admit(4)  # first chunk: 1 block
    assert pool.held_blocks(slot) == 1
    assert pool.ensure_capacity(slot, 8) and pool.held_blocks(slot) == 1
    assert pool.ensure_capacity(slot, 9) and pool.held_blocks(slot) == 2
    assert pool.ensure_capacity(slot, 24) and pool.held_blocks(slot) == 3
    assert pool.ensure_capacity(slot, 6) and pool.held_blocks(slot) == 3  # shrink = no-op
    # exhaustion: all-or-nothing failure leaves holdings unchanged
    other = pool.admit(40)  # 5 blocks -> pool full
    assert pool.n_free_blocks == 0
    assert not pool.ensure_capacity(slot, 64)
    assert pool.held_blocks(slot) == 3
    pool.free(other)
    with pytest.raises(ValueError):
        pool.ensure_capacity(other, 8)  # inactive slot


# -- swap / recompute state parity (all four families) ------------------------


def _pressure_engine(cfg, mode, *, preemption, seed=1):
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    prior = _prior_for(cfg)
    glass = GlassConfig(density=0.5)
    eng = PagedEngine(model, params, max_slots=2, max_len=32, block_size=8,
                      chunk_tokens=3, glass=glass, global_prior=prior,
                      glass_mode=mode, preemption=preemption)
    ref = Engine(model, params, glass=glass, global_prior=prior, glass_mode=mode)
    return eng, ref


def _swap_roundtrip(cfg, mode):
    eng, ref = _pressure_engine(cfg, mode, preemption=PreemptionConfig(mode="swap"))
    reqs = _requests([(7, 8, 0), (5, 6, 0)])
    for r in reqs:
        eng.submit(r)
    e, early = _step_until(eng, 0, ReqState.RUNNING, min_outputs=2)
    slot = e.slot
    before = _request_device_state(eng.pool, slot)
    glass_before = _glass_rows(eng, slot)
    outputs_before = list(e.outputs)
    eng._preempt(e, "swap")
    assert e.state is ReqState.PREEMPTED_SWAPPED and e.slot == -1
    assert e.swap is not None and e.swap.nbytes > 0
    eng._swap_in_tick()
    assert e.state is ReqState.RUNNING and e.slot >= 0
    after = _request_device_state(eng.pool, e.slot)
    # STATE-level invariant: whole-block KV contents and recurrent-state
    # rows restored BIT-identical (block ids may differ; contents may not)
    assert len(before) == len(after)
    for b, a in zip(before, after):
        np.testing.assert_array_equal(b, a)
    if glass_before is not None:
        for b, a in zip(glass_before, _glass_rows(eng, e.slot)):
            np.testing.assert_array_equal(b, a)
    assert e.outputs == outputs_before  # host progress untouched
    done = {f.uid: f for f in early}
    done.update(eng.run())  # drain the rest
    for r in reqs:
        want = ref.generate(jnp.asarray(r.prompt)[None], r.max_new).tokens[0]
        np.testing.assert_array_equal(want, done[r.uid].tokens, err_msg=f"uid={r.uid}")
    assert eng.lc.preempted(kind="swap") >= 1


def _recompute_roundtrip(cfg, mode):
    eng, ref = _pressure_engine(cfg, mode, preemption=PreemptionConfig(mode="recompute"))
    reqs = _requests([(7, 8, 0), (5, 6, 0)])
    for r in reqs:
        eng.submit(r)
    e, _ = _step_until(eng, 0, ReqState.RUNNING, min_outputs=2)
    glass_before = _glass_rows(eng, e.slot)
    outputs_before = list(e.outputs)
    eng._preempt(e, "recompute")
    assert e.state is ReqState.PREEMPTED_RECOMPUTE and e.slot == -1
    assert e.outputs == outputs_before  # the prefix to replay
    e, early = _step_until(eng, 0, ReqState.RUNNING)
    # STATE-level invariant: the replayed chunked prefill (same chunk
    # boundaries over the same prompt tokens) rebuilt the IDENTICAL fused
    # GLASS mask — bit-equal rows, not argmax-equal tokens
    if glass_before is not None:
        for b, a in zip(glass_before, _glass_rows(eng, e.slot)):
            np.testing.assert_array_equal(b, a)
    # the step that resumed the request may already have decoded a forced
    # tick, so replay progress is bounded, and the recorded prefix is a
    # prefix of the stream — never re-appended, never diverged
    assert 0 <= e.replay_left <= len(outputs_before) - 1
    assert e.outputs[: len(outputs_before)] == outputs_before
    done = {f.uid: f for f in early}
    done.update(eng.run())
    for r in reqs:
        want = ref.generate(jnp.asarray(r.prompt)[None], r.max_new).tokens[0]
        np.testing.assert_array_equal(want, done[r.uid].tokens, err_msg=f"uid={r.uid}")
    assert done[0].tokens.shape[0] == reqs[0].max_new
    assert list(done[0].tokens[: len(outputs_before)]) == outputs_before
    assert eng.lc.preempted(kind="recompute") >= 1
    assert eng.recompute_tokens > 0


def test_swap_roundtrip_state_parity_dense():
    _swap_roundtrip(*FAMILIES["dense"])


def test_recompute_roundtrip_mask_parity_dense():
    _recompute_roundtrip(*FAMILIES["dense"])


@pytest.mark.parametrize("family", ["moe", "rwkv6", "hybrid"])
def test_swap_roundtrip_state_parity_slow(family):
    _swap_roundtrip(*FAMILIES[family])


@pytest.mark.parametrize("family", ["moe", "rwkv6", "hybrid"])
def test_recompute_roundtrip_mask_parity_slow(family):
    _recompute_roundtrip(*FAMILIES[family])


# -- engine-driven preemption under pressure ----------------------------------


@pytest.mark.parametrize("kind", ["swap", "recompute", "auto"])
def test_pressure_parity_engine_driven_slow(kind):
    """A pool too small for the offered load: the engine must preempt on
    its own and every stream must still match fresh single-request
    serving exactly."""
    model = build_model(DENSE)
    params = model.init(jax.random.key(0))
    prior = _prior_for(DENSE)
    glass = GlassConfig(density=0.5)
    rng = np.random.RandomState(3)
    reqs = [
        Request(uid=i, prompt=rng.randint(3, 101, size=8).astype(np.int32),
                max_new=10, arrival=0)
        for i in range(4)
    ]
    eng = PagedEngine(model, params, max_slots=3, max_len=32, block_size=8,
                      num_blocks=7, chunk_tokens=4, glass=glass,
                      global_prior=prior, preemption=PreemptionConfig(mode=kind))
    done = eng.run(reqs)
    assert eng.preempt_count > 0  # pressure really forced preemptions
    ref = Engine(model, params, glass=glass, global_prior=prior)
    for r in reqs:
        want = ref.generate(jnp.asarray(r.prompt)[None], r.max_new).tokens[0]
        np.testing.assert_array_equal(want, done[r.uid].tokens, err_msg=f"uid={r.uid}")


def test_watermark_waived_on_idle_pool_no_starvation():
    """Regression: a request whose first chunk + watermark exceed usable
    blocks must still be served once the pool is idle — the reserve exists
    to protect running requests, not to starve admission forever."""
    model = build_model(DENSE)
    params = model.init(jax.random.key(0))
    # 2 usable blocks; chunk 16 -> first chunk needs 2 blocks; watermark 1
    eng = PagedEngine(model, params, max_slots=2, max_len=16, block_size=8,
                      num_blocks=3, chunk_tokens=16)
    rng = np.random.RandomState(0)
    reqs = [Request(uid=i, prompt=rng.randint(3, 101, size=16).astype(np.int32),
                    max_new=1, arrival=0) for i in range(2)]
    done = eng.run(reqs)  # would RuntimeError('did not drain') if starved
    assert sorted(done) == [0, 1]
    ref = Engine(model, params)
    for r in reqs:
        want = ref.generate(jnp.asarray(r.prompt)[None], r.max_new).tokens[0]
        np.testing.assert_array_equal(want, done[r.uid].tokens)


def test_fits_accounts_watermark_and_swapins():
    """Satellite fix: the admission filter must reserve the watermark AND
    the blocks owed to swapped-out requests awaiting swap-in."""
    model = build_model(DENSE)
    params = model.init(jax.random.key(0))
    eng = PagedEngine(model, params, max_slots=3, max_len=32, block_size=8,
                      num_blocks=8, chunk_tokens=4,
                      preemption=PreemptionConfig(mode="swap", watermark_blocks=1))
    r0 = Request(uid=0, prompt=np.arange(8, dtype=np.int32) + 3, max_new=12)
    eng.submit(r0)
    e, _ = _step_until(eng, 0, ReqState.RUNNING, min_outputs=1)
    eng._preempt(e, "swap")
    reserved = e.swap.n_blocks
    assert reserved > 0
    probe = Request(uid=1, prompt=np.arange(4, dtype=np.int32) + 3, max_new=4)
    probe._submit_seq = 999
    # first-chunk need (1 block) + watermark (waived while the pool is
    # idle) + swap reserve bound admission: the blocks owed to the swapped
    # request are never handed to a newcomer
    wm = eng.pool.watermark if eng.pool.active.any() else 0
    assert eng._fits(probe) == (1 + wm + reserved <= eng.pool.n_free_blocks)
    free = eng.pool.n_free_blocks
    assert free == eng.pool.num_blocks - 1  # everything was released by the swap
    # under full-need admission the same probe would check its static need
    eng.alloc_mode = "full"
    assert eng._fits(probe) == eng.pool.fits(len(probe.prompt) + probe.max_new - 1)


def test_incremental_admits_more_than_full_slow():
    """Acceptance: under arrival rate > capacity, incremental+preemption
    admits strictly more than full-need admission (lower admission waits,
    more requests in flight early) with zero token-stream divergence."""
    model = build_model(DENSE)
    params = model.init(jax.random.key(0))
    prior = _prior_for(DENSE)
    glass = GlassConfig(density=0.5)
    rng = np.random.RandomState(5)
    reqs = [
        Request(uid=i, prompt=rng.randint(3, 101, size=8).astype(np.int32),
                max_new=12, arrival=0)
        for i in range(6)
    ]
    waits = {}
    outs = {}
    for mode in ("incremental", "full"):
        eng = PagedEngine(model, params, max_slots=4, max_len=32, block_size=8,
                          num_blocks=10, chunk_tokens=4, glass=glass,
                          global_prior=prior, alloc_mode=mode)
        outs[mode] = eng.run([Request(r.uid, r.prompt, r.max_new, r.arrival)
                              for r in reqs])
        waits[mode] = sorted(eng.admission_waits)
        if mode == "incremental":
            assert eng.preempt_count > 0
    # strictly more admitted per tick: every admission happens no later,
    # at least one strictly earlier
    assert all(i <= f for i, f in zip(waits["incremental"], waits["full"]))
    assert sum(waits["incremental"]) < sum(waits["full"])
    # and zero divergence for the preempted-and-resumed streams
    ref = Engine(model, params, glass=glass, global_prior=prior)
    for r in reqs:
        want = ref.generate(jnp.asarray(r.prompt)[None], r.max_new).tokens[0]
        for mode in ("incremental", "full"):
            np.testing.assert_array_equal(want, outs[mode][r.uid].tokens,
                                          err_msg=f"{mode} uid={r.uid}")


# -- block-sparse decode over the union of the rows' lists --------------------


def test_grouped_block_sparse_step_builder_matches_ungrouped():
    """launch.steps.make_decode_step_block_sparse on per-row block lists —
    one grid over their union, each row under its own scales — must pick
    the tokens each row picks when it decodes alone on its own list (the
    rowwise computation: a one-row grid over the row's tiles)."""
    from repro.launch.steps import make_decode_step_block_sparse

    model = build_model(DENSE)
    params = model.init(jax.random.key(0))
    B = 3
    tok = jnp.asarray([[5], [5], [9]], jnp.int32)
    clen = jnp.zeros((B,), jnp.int32)
    # rows 0 and 1 share a block list; row 2's differs, so the union of a
    # layer's lists is larger than any one of them
    bidx = jnp.asarray(
        [[[0, 2], [0, 2], [1, 2]], [[0, 1], [0, 1], [0, 2]]], jnp.int32
    )  # (L, B, nb)
    step = jax.jit(make_decode_step_block_sparse(model, block_size=32))
    got, _ = step(params, model.init_cache(B, 16), tok, clen, bidx)
    for b in range(B):
        want, _ = step(params, model.init_cache(1, 16), tok[b:b + 1], clen[b:b + 1],
                       bidx[:, b])
        np.testing.assert_array_equal(np.asarray(want)[0], np.asarray(got)[b],
                                      err_msg=f"row {b}")


def _sampled_roundtrip(kind):
    """Satellite: a seeded sampled stream is token-identical through
    swap/recompute preemption vs an undisturbed engine — and not just
    tokens: the per-slot counter-based RNG position and every KV row the
    request owns match at the comparison point (argmax luck cannot hide
    state corruption when the stream is sampled)."""
    from tests.test_speculative_decode import _gathered_rows

    from repro.serve.sampling import SamplingParams

    model = build_model(DENSE)
    params = model.init(jax.random.key(0))
    prior = _prior_for(DENSE)
    glass = GlassConfig(density=0.5)
    sp = SamplingParams(temperature=0.9, top_k=30, seed=2024)
    prompt = np.random.RandomState(9).randint(3, 101, size=7).astype(np.int32)

    def mk():
        return PagedEngine(model, params, max_slots=2, max_len=64, block_size=8,
                           chunk_tokens=3, glass=glass, global_prior=prior,
                           preemption=PreemptionConfig(mode=kind),
                           decode_chunk=1)

    churn = mk()
    uid = churn.add_request(prompt.copy(), 14, sampling=sp)
    e, _ = _step_until(churn, uid, ReqState.RUNNING, min_outputs=3)
    churn._preempt(e, kind)
    e, _ = _step_until(churn, uid, ReqState.RUNNING)
    # drive past any recompute replay so fresh sampled tokens follow churn
    guard = 0
    while e.replay_left or len(e.outputs) < 8:
        churn.step()
        guard += 1
        assert guard < 200 and uid in churn.lc.entries
    g, n = len(e.outputs), int(churn.pool.lengths[e.slot])
    assert e.rng_pos == g  # the PRNG counter tracks accepted tokens exactly
    base = mk()
    base.add_request(prompt.copy(), 14, sampling=sp, uid=uid)
    guard = 0
    while True:
        eb = base.lc.entries.get(uid)
        if eb is not None and eb.state is ReqState.RUNNING and len(eb.outputs) >= g:
            break
        base.step()
        guard += 1
        assert guard < 400
    # token stream, RNG counter, and KV rows all match the undisturbed run
    assert eb.outputs[:g] == e.outputs
    assert eb.rng_pos == len(eb.outputs)
    if len(eb.outputs) == g:
        for a, b in zip(_gathered_rows(churn.pool, e.slot, n),
                        _gathered_rows(base.pool, eb.slot, n)):
            np.testing.assert_array_equal(a, b)
    done = churn.run()
    done_base = base.run()
    np.testing.assert_array_equal(done_base[uid].tokens, done[uid].tokens)
    assert churn.lc.preempted(kind=kind) >= 1


@pytest.mark.sampling
def test_sampled_stream_deterministic_through_swap():
    _sampled_roundtrip("swap")


@pytest.mark.sampling
def test_sampled_stream_deterministic_through_recompute_slow():
    _sampled_roundtrip("recompute")


@pytest.mark.sampling
def test_sampled_pressure_parity_engine_driven_slow():
    """Sampled + greedy mixed load on a pool too small for it: organic
    preemption must leave every stream — sampled ones included —
    identical to fresh single-request serving."""
    from repro.serve.sampling import SamplingParams

    model = build_model(DENSE)
    params = model.init(jax.random.key(0))
    prior = _prior_for(DENSE)
    glass = GlassConfig(density=0.5)
    rng = np.random.RandomState(3)
    prompts = [rng.randint(3, 101, size=8).astype(np.int32) for _ in range(4)]
    sps = [None, SamplingParams(temperature=1.0, seed=7),
           SamplingParams(temperature=0.8, top_k=40, seed=8), None]

    def serve(eng, which):
        outs = {}
        for i in which:
            eng.add_request(prompts[i], 10, sampling=sps[i], uid=i)
        guard = 0
        while eng._work_remaining():
            guard += 1
            assert guard < 900
            for o in eng.step():
                if o.finished:
                    outs[o.uid] = o
        return outs

    eng = PagedEngine(model, params, max_slots=3, max_len=32, block_size=8,
                      num_blocks=7, chunk_tokens=4, glass=glass,
                      global_prior=prior, preemption=PreemptionConfig(mode="auto"))
    done = serve(eng, range(4))
    assert eng.preempt_count > 0
    for i in range(4):
        solo = PagedEngine(model, params, max_slots=3, max_len=32, block_size=8,
                           chunk_tokens=4, glass=glass, global_prior=prior)
        want = serve(solo, [i])[i]
        np.testing.assert_array_equal(want.tokens, done[i].tokens,
                                      err_msg=f"uid={i}")


def test_block_sparse_groups_identical_lists_slow():
    """Decode rows whose active-block lists coincide share their tiles in
    the union grid, which streams each kept tile once a step: fewer tiles
    than the rows' lists hold together.  Every row stays token-identical
    to the masked reference, the row with a different list too."""
    model = build_model(DENSE)
    params = model.init(jax.random.key(0))
    prior = _prior_for(DENSE)
    gc = GlassConfig(density=0.5, selection="block", block_size=32)
    rng = np.random.RandomState(0)
    shared_prompt = rng.randint(3, 101, size=6).astype(np.int32)
    other_prompt = rng.randint(3, 101, size=6).astype(np.int32)
    reqs = [
        Request(uid=0, prompt=shared_prompt.copy(), max_new=8, arrival=0),
        Request(uid=1, prompt=shared_prompt.copy(), max_new=8, arrival=0),
        Request(uid=2, prompt=other_prompt, max_new=8, arrival=0),
    ]
    outs = {}
    together = [0]  # tiles the decoding rows' lists hold, summed over steps
    for mode in ("block_sparse", "masked"):
        eng = PagedEngine(model, params, max_slots=3, max_len=32, block_size=8,
                          chunk_tokens=3, glass=gc, global_prior=prior,
                          glass_mode=mode)
        if mode == "block_sparse":
            count = eng._count_decode

            def spy(run, lengths, H, T, nb, count=count):
                together[0] += H * T * sum(int(e.ffn_tiles.sum()) for e in run)
                count(run, lengths, H, T, nb)

            eng._count_decode = spy
        outs[mode] = eng.run([Request(r.uid, r.prompt, r.max_new, r.arrival)
                              for r in reqs])
        if mode == "block_sparse":
            c = eng.counters()
    assert c["ffn_tiles_read"] == c["ffn_tiles_union"] > 0
    assert c["ffn_tiles_read"] < together[0]
    for r in reqs:
        np.testing.assert_array_equal(outs["block_sparse"][r.uid].tokens,
                                      outs["masked"][r.uid].tokens,
                                      err_msg=f"uid={r.uid}")
