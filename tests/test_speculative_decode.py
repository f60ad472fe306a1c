"""Self-speculative multi-token decode: tiered GLASS draft/verify + the
state-invariant rollback suite.

Greedy token parity with the non-speculative paged path is necessary but
NOT sufficient — argmax absorbs state corruption (see the memory of PR 3's
parity tests) — so the load-bearing tests here compare a speculative engine
against a never-speculated reference engine at the STATE level:

  * every logical KV row up to the accepted frontier is BIT-identical
    (np equality, not allclose), gathered through each engine's own block
    table so block-id assignment differences cannot mask corruption;
  * rows past the frontier inside held blocks are exactly zero (rejected
    speculative writes were un-scattered, not merely masked);
  * recurrent-state rows (rwkv6 state/shifts, hybrid ssm/conv) are
    BIT-identical after the pre-draft-carry fix-up replay;
  * block holdings equal ``blocks_needed(lengths)`` and the allocator free
    STACK (order included) matches the reference — reverse-order release
    means a rolled-back pool hands out identical block ids from here on;
  * the pool never leaks or double-frees across random accept lengths
    0..k and random mid-speculation preemption.

The CI lane runs this module twice: ``SPEC_GLASS_MODE=fused`` (per-slot
fused masks / compact weights) and ``SPEC_GLASS_MODE=block_sparse`` (the
dense family switches to block selection + the pallas block-sparse decode
kernel, whose draft/target active-block lists must nest).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import GlassConfig, build_tiered_masks
from repro.models import ModelConfig, build_model
from repro.serve.engine import Engine, PagedEngine
from repro.serve.kv_pool import BlockPool
from repro.serve.lifecycle import ReqState
from repro.serve.scheduler import Request

pytestmark = pytest.mark.speculative

SPEC_LANE = os.environ.get("SPEC_GLASS_MODE", "fused")  # fused | block_sparse
# gather | paged_pallas — CI runs the serving suites under both; families
# without an attention KV pool (rwkv6) always take the gather default
ATTN_MODE = os.environ.get("ATTN_MODE", "gather")

BASE = dict(n_layers=2, d_model=48, n_heads=4, n_kv_heads=2, head_dim=12,
            d_ff=96, vocab_size=101, dtype="float32", remat="none")
DENSE = ModelConfig(name="sp-dense", family="dense", **BASE)
MOE = ModelConfig(name="sp-moe", family="moe", n_experts=4, n_experts_per_tok=2,
                  moe_strategy="dense", **BASE)
SSM = ModelConfig(name="sp-ssm", family="ssm", rwkv_headdim=12, **BASE)
HYBRID = ModelConfig(name="sp-hybrid", family="hybrid", attn_every=2,
                     ssm_state=16, mamba_headdim=12, **{**BASE, "n_layers": 4})

FAMILIES = {
    "dense": (DENSE, "compact"),
    "moe": (MOE, "masked"),
    "rwkv6": (SSM, "masked"),
    "hybrid": (HYBRID, "compact"),
}


def _family_setup(family):
    """(cfg, glass_mode, selection, ffn_block_size) under the active lane.
    The block_sparse lane reroutes the dense family through block selection
    + the pallas kernel; the other families keep their fused-mask modes."""
    cfg, mode = FAMILIES[family]
    sel, bsz = "neuron", 128
    if SPEC_LANE == "block_sparse" and cfg.family == "dense":
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


def _glass(sel="neuron", bsz=128, draft_ratio=0.5, density=0.5):
    return GlassConfig(density=density, draft_ratio=draft_ratio,
                       selection=sel, block_size=bsz)


def _engines(family, *, spec_k, draft_ratio=0.5, max_slots=2, max_len=64,
             num_blocks=None, decode_chunk=8, seed=0):
    cfg, mode, sel, bsz = _family_setup(family)
    model = build_model(cfg)
    params = model.init(jax.random.key(seed))
    prior = _prior_for(cfg)
    glass = _glass(sel, bsz, draft_ratio)
    attn = ATTN_MODE if cfg.family != "ssm" else "gather"
    eng = PagedEngine(model, params, max_slots=max_slots, max_len=max_len,
                      block_size=8, num_blocks=num_blocks, chunk_tokens=4,
                      glass=glass, global_prior=prior, glass_mode=mode,
                      spec_k=spec_k, decode_chunk=decode_chunk, attn_mode=attn)
    return model, params, prior, glass, eng


def _reference(model, params, prior, glass, family):
    cfg, mode, sel, bsz = _family_setup(family)
    return Engine(model, params, glass=GlassConfig(density=glass.density,
                                                   selection=sel, block_size=bsz),
                  global_prior=prior, glass_mode=mode)


def _requests(spec, seed=0):
    rng = np.random.RandomState(seed)
    return [
        Request(uid=i, prompt=rng.randint(3, 101, size=l).astype(np.int32),
                max_new=n, arrival=a)
        for i, (l, n, a) in enumerate(spec)
    ]


def _gathered_rows(pool: BlockPool, slot: int, n: int):
    """Host copy of the slot's logical KV rows [0, n) gathered through ITS
    OWN block table, plus its recurrent-state rows — the block-assignment-
    agnostic view two engines must agree on bit-for-bit."""
    out = []
    bs = pool.block_size
    for leaf, ax, pg in zip(
        jax.tree.leaves(pool.cache), jax.tree.leaves(pool.axes),
        jax.tree.leaves(pool.paged),
    ):
        a = np.asarray(leaf)
        if pg:
            rows = [
                np.take(a, [int(pool.block_table[slot, r // bs])], axis=ax)
                .take([r % bs], axis=ax + 1)
                for r in range(n)
            ]
            out.append(np.concatenate(rows, axis=ax) if rows else a[0:0])
        else:
            out.append(np.take(a, [slot], axis=ax))
    return out


def _residue_is_zero(pool: BlockPool, slot: int, n: int) -> bool:
    """Rows past the frontier inside the slot's held blocks must be exactly
    zero — proof the rollback un-scattered rejected writes."""
    if not pool.has_paged:
        return True
    bs = pool.block_size
    cap = pool.held_blocks(slot) * bs
    for leaf, ax, pg in zip(
        jax.tree.leaves(pool.cache), jax.tree.leaves(pool.axes),
        jax.tree.leaves(pool.paged),
    ):
        if not pg:
            continue
        a = np.asarray(leaf)
        for r in range(n, cap):
            blk = int(pool.block_table[slot, r // bs])
            row = np.take(a, [blk], axis=ax).take([r % bs], axis=ax + 1)
            if row.any():
                return False
    return True


def _assert_allocator_balanced(pool: BlockPool):
    if not pool.has_paged:
        return
    held = [b for blocks in pool._held.values() for b in blocks]
    assert len(held) == len(set(held)), "block owned twice"
    assert 0 not in held, "trash block handed out"
    assert pool.allocator.n_live == len(held)
    assert pool.allocator.n_free + pool.allocator.n_live == pool.num_blocks - 1


# -- tiered mask construction -------------------------------------------------


@pytest.mark.parametrize("selection,bsz", [("neuron", 128), ("block", 32)])
def test_tiered_masks_nest_per_layer_per_slot(selection, bsz):
    """Draft-tier active units (block ids under selection='block') must be a
    SUBSET of the target tier's, per layer per slot — the nesting that makes
    the draft pass a strictly cheaper approximation and keeps block-sparse
    decode's active-block lists nested."""
    rng = np.random.RandomState(0)
    L, B, m = 3, 4, 128
    stats = {
        "sum_abs": jnp.asarray(rng.rand(B, L, m).astype(np.float32)),
        "count": jnp.asarray(np.full((B,), 17.0, np.float32)),
    }
    prior = jnp.abs(jax.random.normal(jax.random.key(3), (L, m)))
    gcfg = GlassConfig(density=0.5, draft_ratio=0.5, selection=selection,
                       block_size=bsz)
    tgt, dft = build_tiered_masks(stats, prior, gcfg, slot_axis=True)
    ti, di = np.asarray(tgt.idx), np.asarray(dft.idx)
    assert di.shape[-1] < ti.shape[-1]  # the draft tier really is smaller
    for l in range(L):
        for b in range(B):
            t_set = set(ti[l, b].tolist())
            d_set = set(di[l, b].tolist())
            assert d_set <= t_set, (selection, l, b, sorted(d_set - t_set))
    # masks nest too: everywhere the draft keeps a unit, the target does
    tm, dm = np.asarray(tgt.mask), np.asarray(dft.mask)
    assert np.all(tm[dm > 0.5] > 0.5)
    # both tiers ranked the IDENTICAL fused scores
    np.testing.assert_array_equal(np.asarray(tgt.scores), np.asarray(dft.scores))


def test_tiered_config_validation():
    with pytest.raises(ValueError, match="draft_ratio"):
        GlassConfig(draft_ratio=0.0)
    with pytest.raises(ValueError, match="draft_ratio"):
        GlassConfig(draft_ratio=1.5)
    with pytest.raises(ValueError, match="draft_ratio"):
        GlassConfig().draft_config()
    d = GlassConfig(density=0.5, draft_ratio=0.5).draft_config()
    assert d.density == 0.25 and d.draft_ratio is None
    with pytest.raises(ValueError, match="draft_ratio"):
        build_tiered_masks({}, None, GlassConfig())
    with pytest.raises(ValueError, match="draft_ratio"):
        PagedEngine(build_model(DENSE), build_model(DENSE).init(jax.random.key(0)),
                    max_len=32, glass=GlassConfig(density=0.5),
                    global_prior=_prior_for(DENSE), spec_k=2)


# -- model-level multi-token verify -------------------------------------------


def test_verify_steps_bitwise_matches_sequential():
    """Model.verify_steps must return the SAME greedy verdicts and leave the
    cache BIT-identical to T individual JITTED decode steps — the contract
    the engine-level rollback exactness rests on.  The reference steps must
    be jitted: verify_steps is inline-compiled (unrolled, never a scan body)
    precisely so it matches other inline-compiled programs bit-for-bit, and
    eager op-by-op dispatch fuses nothing so it sits outside that contract
    (the engine only ever runs jitted programs)."""
    model = build_model(DENSE)
    params = model.init(jax.random.key(0))
    toks = jnp.asarray(np.random.RandomState(0).randint(3, 101, size=(1, 5)),
                       jnp.int32)
    _, cache0, _ = model.prefill(params, {"tokens": toks}, 16)
    feed = jnp.asarray(np.random.RandomState(1).randint(3, 101, size=(1, 4)),
                       jnp.int32)
    greedy, cache_v = jax.jit(
        lambda p, c, t: model.verify_steps(p, t, c, jnp.int32(5))
    )(params, cache0, feed)
    step = jax.jit(model.decode_step)
    cache_s = cache0
    seq = []
    for j in range(4):
        lg, cache_s = step(params, feed[:, j : j + 1], cache_s,
                           jnp.int32(5 + j))
        seq.append(int(jnp.argmax(lg[0, -1].astype(jnp.float32))))
    assert list(np.asarray(greedy)[0]) == seq
    for a, b in zip(jax.tree.leaves(cache_v), jax.tree.leaves(cache_s)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_make_verify_step_builder_masked():
    from repro.launch.steps import make_verify_step

    model = build_model(DENSE)
    params = model.init(jax.random.key(0))
    toks = jnp.asarray(np.random.RandomState(0).randint(3, 101, size=(1, 4)),
                       jnp.int32)
    _, cache, _ = model.prefill(params, {"tokens": toks}, 16)
    feed = jnp.asarray([[9, 11, 13]], jnp.int32)
    mask = jnp.ones((DENSE.n_layers, DENSE.d_ff), jnp.float32)
    verify = make_verify_step(model, glass_mode="masked")
    g_masked, _ = verify(params, cache, feed, jnp.int32(4), mask)
    plain = make_verify_step(model)
    g_plain, _ = plain(params, cache, feed, jnp.int32(4))
    # an all-ones mask is a no-op: both programs agree exactly
    np.testing.assert_array_equal(np.asarray(g_masked), np.asarray(g_plain))
    with pytest.raises(ValueError):
        make_verify_step(model, glass_mode="bogus")


# -- greedy token parity (speculative vs plain vs single-request) -------------


def _parity_case(family, spec_k=2, draft_ratio=0.5):
    model, params, prior, glass, eng = _engines(family, spec_k=spec_k,
                                                draft_ratio=draft_ratio,
                                                max_slots=2, max_len=64)
    reqs = _requests([(6, 10, 0), (5, 8, 0), (7, 6, 2)])
    done = eng.run([Request(r.uid, r.prompt, r.max_new, r.arrival) for r in reqs])
    assert eng.spec_ticks > 0, "the speculative path never ran"
    t = eng.spec_telemetry
    assert 0.0 <= t["draft_acceptance_rate"] <= 1.0
    # every speculative slot-round emits its accepted drafts plus one bonus
    assert t["emitted_tokens"] == t["accepted_tokens"] + eng.spec_slot_ticks
    ref = _reference(model, params, prior, glass, family)
    for r in reqs:
        want = ref.generate(jnp.asarray(r.prompt)[None], r.max_new).tokens[0]
        np.testing.assert_array_equal(np.asarray(want), done[r.uid].tokens,
                                      err_msg=f"uid={r.uid}")
    if eng.pool.has_paged:
        assert eng.pool.allocator.n_live == 0  # drained clean
        _assert_allocator_balanced(eng.pool)


def test_spec_token_parity_dense():
    _parity_case("dense")


@pytest.mark.parametrize("family", ["moe", "rwkv6", "hybrid"])
def test_spec_token_parity_slow(family):
    _parity_case(family)


# -- bit-identical state invariants vs a never-speculated engine ---------------


def _force_rollback_round(eng, e):
    """One speculative round whose first draft proposal is corrupted on the
    host.  ANY token id is a legal draft proposal, so the target tier must
    reject at position 0 and the rollback machinery (state fix-up,
    un-scatter, shrink) must erase the round — deterministically, instead
    of hoping the draft tier disagrees on a tiny random-init model (rwkv6's
    channel-mix barely moves the argmax there, so organic acceptance can
    be 100%)."""
    before = eng.spec_rollbacks
    for bump in (1, 2, 3):  # retry iff the corrupted token WAS the verdict
        run = [e]
        k = eng._spec_possible(run)
        k = eng._spec_capacity(run, k)
        assert k >= 1
        eng._spec_draft(run, k)
        ck = e.spec_ckpt
        e.outputs[ck.out_len] = (e.outputs[ck.out_len] + bump) % 101
        eng._spec_verify(run, k, [])
        assert e.state is ReqState.RUNNING
        if eng.spec_rollbacks > before:
            return
    raise AssertionError("a corrupted draft was accepted three times")


def _state_invariant_case(family, *, spec_k=3, draft_ratio=0.2, max_new=48,
                          spec_steps=8):
    """Drive a speculative engine, force at least one rejected round, then
    drive a fresh never-speculated engine (decode_chunk=1 so it can stop at
    the exact same progress) and compare EVERYTHING the pool holds for the
    request."""
    model, params, prior, glass, spec = _engines(family, spec_k=spec_k,
                                                 draft_ratio=draft_ratio,
                                                 max_slots=2, max_len=64)
    _, _, _, _, base = _engines(family, spec_k=0, draft_ratio=draft_ratio,
                                max_slots=2, max_len=64, decode_chunk=1)
    prompt = np.random.RandomState(1).randint(3, 101, size=6).astype(np.int32)
    spec.submit(Request(uid=0, prompt=prompt.copy(), max_new=max_new))
    for _ in range(spec_steps):
        spec.step()
        if 0 not in spec.lc.entries:
            break
    e = spec.lc.entries.get(0)
    assert e is not None, "request finished before the comparison point; " \
        "raise max_new or lower spec_steps"
    assert e.state is ReqState.RUNNING
    _force_rollback_round(spec, e)
    assert spec.spec_rollbacks > 0
    g, n = len(e.outputs), int(spec.pool.lengths[e.slot])
    base.submit(Request(uid=0, prompt=prompt.copy(), max_new=max_new))
    for _ in range(400):
        eb = base.lc.entries.get(0)
        if eb is not None and eb.state is ReqState.RUNNING and len(eb.outputs) >= g:
            break
        base.step()
    eb = base.lc.entries[0]
    assert len(eb.outputs) == g
    # token stream: necessary, not sufficient
    assert eb.outputs == e.outputs
    assert int(base.pool.lengths[eb.slot]) == n
    # STATE level: every logical KV row + recurrent-state row bit-identical
    for a, b in zip(_gathered_rows(spec.pool, e.slot, n),
                    _gathered_rows(base.pool, eb.slot, n)):
        np.testing.assert_array_equal(a, b)
    # rejected speculative writes were un-scattered, not merely masked
    assert _residue_is_zero(spec.pool, e.slot, n)
    if spec.pool.has_paged:
        # holdings exact, accounting balanced, and the free STACK matches
        # the never-speculated engine's (reverse-order release) — identical
        # block ids get handed out from here on
        assert spec.pool.held_blocks(e.slot) == spec.pool.blocks_needed(n)
        _assert_allocator_balanced(spec.pool)
        assert spec.pool.allocator._free == base.pool.allocator._free
        assert spec.pool._held[e.slot] == base.pool._held[eb.slot]
    # GLASS target rows of the slot agree (same stats, same prior)
    gs, gb = spec.glass_slots, base.glass_slots
    ax = gs.slot_axis
    for a, b in zip(jax.tree.leaves(gs.arena), jax.tree.leaves(gb.arena)):
        np.testing.assert_array_equal(
            np.take(np.asarray(a), [e.slot], axis=ax),
            np.take(np.asarray(b), [eb.slot], axis=ax),
        )


def test_spec_state_invariants_dense():
    _state_invariant_case("dense")


@pytest.mark.parametrize("family", ["rwkv6", "hybrid"])
def test_spec_state_invariants_slow(family):
    # rwkv6 accepts aggressively on random weights; a harsher draft tier
    # (draft_ratio 0.1) keeps rollbacks happening within the window
    _state_invariant_case(family, draft_ratio=0.1, max_new=56, spec_steps=6)


# -- mid-speculation preemption: the requeue footgun --------------------------


def _enter_speculation(eng, uid):
    """Drive until RUNNING with some progress, then run ONLY the draft half
    of a speculative round — the engine is now frozen mid-speculation."""
    for _ in range(200):
        eng.step()
        e = eng.lc.entries.get(uid)
        if e is not None and e.state is ReqState.RUNNING and len(e.outputs) >= 2:
            break
    else:
        raise AssertionError("never reached RUNNING")
    run = [e]
    k = eng._spec_possible(run)
    assert k > 0
    k = eng._spec_capacity(run, k)
    assert k > 0
    eng._spec_draft(run, k)
    assert e.state is ReqState.SPECULATING and e.spec_len == k
    return e, k


@pytest.mark.parametrize("kind", ["recompute", "swap"])
def test_midspec_preemption_slices_speculated_tokens(kind):
    """Regression (the requeue footgun): preempting a mid-speculation victim
    must slice the provisional draft tokens off ``outputs`` BEFORE the
    recompute requeue (which replays outputs as forced tokens) or the swap
    capture — and the resumed stream must match single-request serving
    exactly."""
    model, params, prior, glass, eng = _engines("dense", spec_k=3,
                                                draft_ratio=0.2, max_len=64)
    r = _requests([(6, 12, 0)])[0]
    eng.submit(r)
    e, k = _enter_speculation(eng, 0)
    out_before = list(e.outputs[: -k])
    rows_before = e.spec_ckpt.rows
    eng._preempt(e, kind)
    # the provisional (unverified) tokens are GONE from the resume state
    assert e.outputs == out_before
    assert e.spec_len == 0 and e.spec_ckpt is None
    if kind == "recompute":
        assert e.state is ReqState.PREEMPTED_RECOMPUTE
        # the forced-token replay will re-feed exactly the accepted prefix
        assert all(q is e.req for q in eng.scheduler.queue)
    else:
        assert e.state is ReqState.PREEMPTED_SWAPPED
        # the swap captured the rolled-back footprint, not speculative growth
        assert e.swap.n_blocks == eng.pool.blocks_needed(rows_before)
    _assert_allocator_balanced(eng.pool)
    done = eng.run()
    ref = _reference(model, params, prior, glass, "dense")
    want = ref.generate(jnp.asarray(r.prompt)[None], r.max_new).tokens[0]
    np.testing.assert_array_equal(np.asarray(want), done[0].tokens)
    assert eng.pool.allocator.n_live == 0
    assert eng.spec_rollbacks > 0


def test_midspec_finish_is_illegal():
    """A SPECULATING entry cannot jump straight to FINISHED — the lifecycle
    forces the engine through rollback/commit (back to RUNNING) first."""
    model, params, prior, glass, eng = _engines("dense", spec_k=3,
                                                draft_ratio=0.2, max_len=64)
    eng.submit(_requests([(6, 12, 0)])[0])
    e, _ = _enter_speculation(eng, 0)
    with pytest.raises(ValueError, match="illegal transition"):
        eng.lc.to(e, ReqState.FINISHED)
    with pytest.raises(ValueError, match="illegal transition"):
        eng.lc.to(e, ReqState.PREEMPTED_RECOMPUTE)
    eng._rollback_speculation(e)
    assert e.state is ReqState.RUNNING
    done = eng.run()
    ref = _reference(model, params, prior, glass, "dense")
    want = ref.generate(jnp.asarray(done[0].prompt)[None], 12).tokens[0]
    np.testing.assert_array_equal(np.asarray(want), done[0].tokens)


def test_spec_full_alloc_mode_keeps_reservation():
    """Regression: under ``alloc_mode="full"`` admission reserves the whole
    footprint and NOTHING re-allocates later, so a speculative rollback must
    not shrink the holding — shrinking freed reserved blocks and zeroed
    their table entries, sending every later KV write to the trash block
    (streams diverged from the non-speculative full-mode engine)."""
    cfg, mode, sel, bsz = _family_setup("dense")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    prior = _prior_for(cfg)
    reqs = _requests([(6, 24, 0), (5, 20, 0)])
    outs = {}
    for spec_k in (0, 2):
        eng = PagedEngine(model, params, max_slots=2, max_len=64, block_size=8,
                          chunk_tokens=4, glass=_glass(sel, bsz, 0.2),
                          global_prior=prior, glass_mode=mode,
                          alloc_mode="full", spec_k=spec_k)
        outs[spec_k] = eng.run([Request(r.uid, r.prompt, r.max_new, r.arrival)
                                for r in reqs])
        if spec_k:
            assert eng.spec_rollbacks > 0  # rollback really exercised
            assert eng.pool.allocator.n_live == 0
    for r in reqs:
        np.testing.assert_array_equal(outs[0][r.uid].tokens,
                                      outs[2][r.uid].tokens,
                                      err_msg=f"uid={r.uid}")


# -- pool-level rollback property test ----------------------------------------


@settings(max_examples=12, deadline=None)
@given(st.lists(st.tuples(st.integers(min_value=0, max_value=4),
                          st.integers(min_value=0, max_value=5)),
                max_size=20))
def test_spec_rollback_pool_property(ops):
    """Random speculative rounds at the pool level: ensure k+1 rows of
    growth, write garbage into the speculative rows, accept a random prefix
    (0..k), roll the rest back.  The pool must never leak or double-free,
    holdings must track the accepted frontier exactly, and rolled-back rows
    must read back zero."""
    model = build_model(DENSE)
    pool = BlockPool(model, max_slots=2, max_len=64, block_size=8, num_blocks=9)
    slot = pool.admit(4)
    pool.lengths[slot] = 4
    free_stack0 = list(pool.allocator._free)
    n = 4
    for k_raw, a_raw in ops:
        k = k_raw
        a = min(a_raw, k)
        if n + k + 1 > pool.max_len:
            break
        if not pool.ensure_capacity(slot, n + k + 1):
            break
        # scribble into every speculative row (draft + verify writes)
        pages = [int(pool.block_table[slot, r // 8]) for r in range(n, n + k + 1)]
        offs = [r % 8 for r in range(n, n + k + 1)]
        def scribble(leaf, ax, pg):
            if not pg:
                return leaf
            idx = (slice(None),) * ax + (np.asarray(pages), np.asarray(offs))
            return leaf.at[idx].set(7.0)
        pool.cache = jax.tree.map(scribble, pool.cache, pool.axes, pool.paged)
        # accept a, reject the rest
        pool.rollback_rows(slot, n + a + 1, n + k + 1)
        pool.shrink_to(slot, n + a + 1)
        n = n + a + 1
        pool.lengths[slot] = n
        assert pool.held_blocks(slot) == pool.blocks_needed(n)
        _assert_allocator_balanced(pool)
        assert _residue_is_zero(pool, slot, n)
    # full rollback to the start: the free stack returns to its exact
    # pre-speculation order (reverse-order release), so a parallel
    # never-speculated pool would hand out identical ids
    pool.rollback_rows(slot, 4, n)
    pool.shrink_to(slot, 4)
    pool.lengths[slot] = 4
    assert pool.held_blocks(slot) == pool.blocks_needed(4)
    assert pool.allocator._free == free_stack0
    with pytest.raises(ValueError):
        pool.rollback_rows(1 - slot, 0, 1)  # inactive slot
    with pytest.raises(ValueError):
        pool.shrink_to(1 - slot, 0)


# -- sampled speculation: positional verdicts under SamplingParams ------------


def _sampled_sp(seed=4242):
    from repro.serve.sampling import SamplingParams

    return SamplingParams(temperature=0.9, top_k=40, seed=seed)


@pytest.mark.sampling
def test_sampled_spec_token_parity():
    """Satellite: under per-request sampling, the target verdict is the
    counter-based positional sample from the pre-override logits — so a
    speculating engine's sampled streams are token-identical to a
    non-speculative engine's (speculation invisible under sampling, the
    same contract as greedy)."""
    model, params, prior, glass, spec = _engines("dense", spec_k=2,
                                                 draft_ratio=0.5)
    _, _, _, _, base = _engines("dense", spec_k=0, draft_ratio=0.5)
    rng = np.random.RandomState(11)
    prompts = [rng.randint(3, 101, size=6).astype(np.int32) for _ in range(3)]

    def serve(eng, spec_on):
        from repro.core import GlassParams

        outs = {}
        for i, p in enumerate(prompts):
            eng.add_request(p.copy(), 10, uid=i, sampling=_sampled_sp(100 + i),
                            glass=GlassParams(spec_k=2 if spec_on else 0))
        guard = 0
        while eng._work_remaining():
            guard += 1
            assert guard < 600
            for o in eng.step():
                if o.finished:
                    outs[o.uid] = o
        return outs

    got = serve(spec, True)
    assert spec.spec_ticks > 0, "the speculative path never ran"
    assert spec.spec_accepted > 0, "sampled drafts never matched the verdict"
    want = serve(base, False)
    for i in range(3):
        np.testing.assert_array_equal(want[i].tokens, got[i].tokens,
                                      err_msg=f"uid={i}")
    _assert_allocator_balanced(spec.pool)
    assert spec.pool.allocator.n_live == 0


@pytest.mark.sampling
def test_sampled_spec_state_invariants():
    """Seeded sampled stream + forced rollback rounds: the pool must be
    bit-identical to a never-speculated engine serving the same sampled
    request — KV rows, residue, holdings, free stack, AND the per-slot
    RNG counter (provisional drafts never advance it; rollback rewinds
    it with the outputs)."""
    model, params, prior, glass, spec = _engines("dense", spec_k=3,
                                                 draft_ratio=0.2, max_len=64)
    _, _, _, _, base = _engines("dense", spec_k=0, draft_ratio=0.2,
                                max_len=64, decode_chunk=1)
    prompt = np.random.RandomState(21).randint(3, 101, size=6).astype(np.int32)
    uid = spec.add_request(prompt.copy(), 48, sampling=_sampled_sp())
    for _ in range(8):
        spec.step()
        if uid not in spec.lc.entries:
            break
    e = spec.lc.entries.get(uid)
    assert e is not None and e.state is ReqState.RUNNING
    assert e.rng_pos == len(e.outputs)
    _force_rollback_round(spec, e)
    assert spec.spec_rollbacks > 0
    assert e.rng_pos == len(e.outputs)  # rollback rewound the counter too
    g, n = len(e.outputs), int(spec.pool.lengths[e.slot])
    base.add_request(prompt.copy(), 48, sampling=_sampled_sp(), uid=uid)
    for _ in range(400):
        eb = base.lc.entries.get(uid)
        if eb is not None and eb.state is ReqState.RUNNING and len(eb.outputs) >= g:
            break
        base.step()
    eb = base.lc.entries[uid]
    assert len(eb.outputs) == g
    assert eb.outputs == e.outputs  # sampled tokens, not argmax luck
    assert eb.rng_pos == e.rng_pos == g
    assert int(base.pool.lengths[eb.slot]) == n
    for a, b in zip(_gathered_rows(spec.pool, e.slot, n),
                    _gathered_rows(base.pool, eb.slot, n)):
        np.testing.assert_array_equal(a, b)
    assert _residue_is_zero(spec.pool, e.slot, n)
    if spec.pool.has_paged:
        assert spec.pool.held_blocks(e.slot) == spec.pool.blocks_needed(n)
        _assert_allocator_balanced(spec.pool)
        assert spec.pool.allocator._free == base.pool.allocator._free


@pytest.mark.sampling
def test_sampled_midspec_preemption_slices_and_resumes():
    """Mid-speculation preemption of a SAMPLED request: provisional draft
    tokens are sliced off, and the resumed stream still matches the
    undisturbed non-speculative engine (counter-based draws survive the
    recompute replay)."""
    model, params, prior, glass, eng = _engines("dense", spec_k=3,
                                                draft_ratio=0.2, max_len=64)
    prompt = np.random.RandomState(31).randint(3, 101, size=6).astype(np.int32)
    uid = eng.add_request(prompt.copy(), 12, sampling=_sampled_sp(9))
    e, k = _enter_speculation(eng, uid)
    out_before = list(e.outputs[:-k])
    eng._preempt(e, "recompute")
    assert e.outputs == out_before
    assert e.rng_pos == len(e.outputs)
    done = eng.run()
    _, _, _, _, base = _engines("dense", spec_k=0, draft_ratio=0.2, max_len=64)
    base.add_request(prompt.copy(), 12, sampling=_sampled_sp(9), uid=uid)
    want = base.run()
    np.testing.assert_array_equal(want[uid].tokens, done[uid].tokens)
    assert eng.pool.allocator.n_live == 0
    assert eng.spec_rollbacks > 0


# -- engine-driven stress: speculation + pressure preemption ------------------


def test_spec_under_pressure_parity_slow():
    """A pool too small for the offered load with speculation ON: organic
    preemption interleaves with speculative rounds (the capacity hunt may
    shrink k or evict a victim) and every stream must still match fresh
    single-request serving exactly, with the pool accounting clean."""
    model, params, prior, glass, eng = _engines(
        "dense", spec_k=3, draft_ratio=0.2, max_slots=3, max_len=32,
        num_blocks=6,
    )
    rng = np.random.RandomState(3)
    reqs = [
        Request(uid=i, prompt=rng.randint(3, 101, size=8).astype(np.int32),
                max_new=10, arrival=0)
        for i in range(4)
    ]
    done = eng.run(reqs)
    assert eng.preempt_count > 0  # pressure really forced preemptions
    assert eng.spec_ticks > 0  # and speculation really ran
    ref = _reference(model, params, prior, glass, "dense")
    for r in reqs:
        want = ref.generate(jnp.asarray(r.prompt)[None], r.max_new).tokens[0]
        np.testing.assert_array_equal(np.asarray(want), done[r.uid].tokens,
                                      err_msg=f"uid={r.uid}")
    assert eng.pool.allocator.n_live == 0
    _assert_allocator_balanced(eng.pool)


def test_midspec_preemption_random_seeds_never_leak_slow():
    """Property-style: across seeds, freeze the engine mid-speculation,
    preempt with a random kind, drain, and assert parity + zero leaks."""
    for seed in range(3):
        kind = ["recompute", "swap"][seed % 2]
        model, params, prior, glass, eng = _engines(
            "dense", spec_k=2 + seed % 2, draft_ratio=0.2, max_len=64,
            seed=seed,
        )
        r = Request(uid=0,
                    prompt=np.random.RandomState(seed).randint(
                        3, 101, size=5 + seed).astype(np.int32),
                    max_new=11)
        eng.submit(r)
        e, _ = _enter_speculation(eng, 0)
        eng._preempt(e, kind)
        _assert_allocator_balanced(eng.pool)
        done = eng.run()
        ref = _reference(model, params, prior, glass, "dense")
        want = ref.generate(jnp.asarray(r.prompt)[None], r.max_new).tokens[0]
        np.testing.assert_array_equal(np.asarray(want), done[0].tokens,
                                      err_msg=f"seed={seed} kind={kind}")
        assert eng.pool.allocator.n_live == 0
