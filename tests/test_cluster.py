"""Replica-sharded serving: the cluster invariant suite.

A :class:`~repro.serve.cluster.ClusterEngine` is a scheduling construct,
never a numerics one — so the suite's spine is bit-identity: a request
served by an N-replica cluster, *including one migrated between replicas
mid-flight*, must produce the token stream an undisturbed single engine
produces, np-equal, across all four model families and both greedy and
seeded-sampled decoding.  Around that:

  * migration legs: mid-decode (GLASS slot rows ride the ticket),
    mid-speculation (rollback first — the only legal SPECULATING exit),
    mid-prefill (chunk-aligned handoff, partial stat left-fold resumes
    at the destination over the same chunk boundaries);
  * abort while MIGRATING releases both pools completely (a full-swap
    ticket pins nothing on either side);
  * a hypothesis property: any drained cluster returns every replica's
    pool to its initial all-free state (slots, blocks, lengths);
  * global-queue policy parity: an N=1 cluster admits in exactly the
    single-engine order (the dispatcher adds routing, not reordering);
  * ``BlockPool.peek_prefix`` is a pure probe (the dispatcher calls it
    against every replica per admission: no LRU bump, no hit/miss skew);
  * the swap-store byte cap degrades the OLDEST swapped request to
    recompute, with telemetry and unchanged streams;
  * with a real ``data``-axis mesh, replica KV arenas commit to distinct
    devices (subprocess test: 8 forced host devices).

CI runs this module as its own lane (``-m cluster``) with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``; everything but
the placement test also passes on one device (replicas then share it —
correct, just serialized).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.helpers import run_with_devices
from hypothesis import given, settings, strategies as st

from repro.core import GlassConfig
from repro.models import ModelConfig, build_model
from repro.serve.cluster import ClusterEngine, MigrationConfig
from repro.serve.engine import PagedEngine
from repro.serve.lifecycle import PreemptionConfig, ReqState
from repro.serve.sampling import SamplingParams

pytestmark = pytest.mark.cluster

BASE = dict(n_layers=2, d_model=48, n_heads=4, n_kv_heads=2, head_dim=12,
            d_ff=96, vocab_size=101, dtype="float32", remat="none")
DENSE = ModelConfig(name="cl-dense", family="dense", **BASE)
MOE = ModelConfig(name="cl-moe", family="moe", n_experts=4, n_experts_per_tok=2,
                  moe_strategy="dense", **BASE)
SSM = ModelConfig(name="cl-ssm", family="ssm", rwkv_headdim=12, **BASE)
HYBRID = ModelConfig(name="cl-hybrid", family="hybrid", attn_every=2,
                     ssm_state=16, mamba_headdim=12, **{**BASE, "n_layers": 4})

FAMILIES = {
    "dense": (DENSE, "compact"),
    "moe": (MOE, "masked"),
    "rwkv6": (SSM, "masked"),
    "hybrid": (HYBRID, "compact"),
}

BS = 4  # block_size == chunk_tokens: every block boundary chunk-aligned
CT = 4

_BUILT = {}


def _model(cfg):
    if cfg.name not in _BUILT:
        model = build_model(cfg)
        _BUILT[cfg.name] = (model, model.init(jax.random.key(0)))
    return _BUILT[cfg.name]


def _prior_for(cfg: ModelConfig):
    if cfg.family == "moe":
        shape = (cfg.n_layers, cfg.n_experts, cfg.d_ff)
    elif cfg.family == "hybrid":
        shape = (cfg.d_ff,)
    else:
        shape = (cfg.n_layers, cfg.d_ff)
    return jnp.abs(jax.random.normal(jax.random.key(7), shape))


def _engine_kw(family, **over):
    cfg, mode = FAMILIES[family]
    model, params = _model(cfg)
    glass = GlassConfig(density=0.5, selection="neuron", block_size=128,
                        draft_ratio=over.pop("draft_ratio", None))
    kw = dict(max_slots=2, max_len=32, block_size=BS, chunk_tokens=CT,
              glass=glass, global_prior=_prior_for(cfg), glass_mode=mode)
    kw.update(over)
    return model, params, kw


def _cluster(family, n_replicas=2, migration=None, **over):
    model, params, kw = _engine_kw(family, **over)
    return ClusterEngine(
        model, params, n_replicas=n_replicas,
        migration=migration or MigrationConfig(enabled=False), **kw,
    )


def _single(family, **over):
    model, params, kw = _engine_kw(family, **over)
    return PagedEngine(model, params, **kw)


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(3, 101, size=n).astype(np.int32)


def _step_until(cl, uid, state, min_outputs=0, limit=300):
    """Step the CLUSTER until ``uid``'s entry (on its owner) hits
    ``state`` with at least ``min_outputs`` tokens; returns (entry, owner)."""
    for _ in range(limit):
        cl.step()
        owner = cl._owner.get(uid)
        if owner is None:
            continue
        e = cl.replicas[owner].lc.entries.get(uid)
        if e is not None and e.state is state and len(e.outputs) >= min_outputs:
            return e, owner
    raise AssertionError(f"uid {uid} never reached {state} on any replica")


def _assert_pool_pristine(eng):
    """The replica pool is back to its initial all-free state (no prefix
    cache in these engines: nothing may be retained)."""
    pool = eng.pool
    assert not pool.active.any()
    assert (pool.lengths == 0).all()
    assert pool.n_free_slots == pool.max_slots
    if pool.allocator is not None:
        assert pool.n_free_blocks == pool.num_blocks - 1
        assert pool.allocator.n_live == 0


# -- migration bit-identity across families and sampling policies -------------


@pytest.mark.parametrize("family", list(FAMILIES), ids=list(FAMILIES))
@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
def test_migration_bit_identity(family, sampled):
    """A request migrated between replicas mid-decode streams the exact
    tokens an undisturbed single engine streams — greedy and seeded-
    sampled (counter-based PRNG: position-keyed draws survive the move)."""
    sp = SamplingParams(temperature=0.8, top_k=20, seed=42) if sampled else None
    prompts = [_prompt(6, seed=1), _prompt(7, seed=2)]

    ref = _single(family)
    for i, p in enumerate(prompts):
        ref.add_request(p, 8, uid=i, sampling=sp)
    want = {u: np.asarray(f.tokens) for u, f in ref.run().items()}

    cl = _cluster(family)
    for i, p in enumerate(prompts):
        cl.add_request(p, 8, uid=i, sampling=sp)
    e, owner = _step_until(cl, 0, ReqState.RUNNING, min_outputs=2)
    moved_at = len(e.outputs)
    cl.migrate(0, 1 - owner)
    assert cl._owner[0] == 1 - owner
    assert cl.replicas[owner].migrations_out == 1
    assert cl.replicas[1 - owner].migrations_in == 1
    assert cl.migrations == 1 and cl.migration_bytes > 0
    done = cl.run()
    for u in want:
        np.testing.assert_array_equal(want[u], done[u].tokens, err_msg=f"uid={u}")
    assert moved_at < len(want[0])  # the move really happened mid-stream


def test_mid_speculation_migration():
    """A SPECULATING victim rolls back to its last accepted token before
    leaving (provisional draft tokens never cross engines), and the
    migrated stream still equals the undisturbed speculative run."""
    kw = dict(draft_ratio=0.5, spec_k=2)
    ref = _single("dense", **kw)
    ref.add_request(_prompt(6, seed=1), 8, uid=0)
    want = np.asarray(ref.run()[0].tokens)

    cl = _cluster("dense", **kw)
    cl.add_request(_prompt(6, seed=1), 8, uid=0)
    e, owner = _step_until(cl, 0, ReqState.RUNNING, min_outputs=1)
    src = cl.replicas[owner]
    src._spec_draft([e], 2)  # force mid-speculation: provisional drafts out
    assert e.state is ReqState.SPECULATING and e.spec_len == 2
    n_before = len(e.outputs) - e.spec_len
    cl.migrate(0, 1 - owner)
    dst_e = cl.replicas[1 - owner].lc.entries[0]
    assert len(dst_e.outputs) == n_before  # drafts rolled back, not shipped
    done = cl.run()
    np.testing.assert_array_equal(want, done[0].tokens)


@pytest.mark.parametrize("family", ["dense", "rwkv6"], ids=["dense", "rwkv6"])
def test_mid_prefill_migration(family):
    """A PREFILLING request hands off at its current chunk boundary: the
    partial GLASS stat left-fold travels with the ticket and keeps
    accumulating at the destination over the SAME chunk boundaries, so
    the stream is bit-identical to an unmigrated prefill."""
    prompt = _prompt(16, seed=5)  # 4 chunks of CT=4
    ref = _single(family)
    ref.add_request(prompt, 6, uid=0)
    want = np.asarray(ref.run()[0].tokens)

    cl = _cluster(family)
    cl.add_request(prompt, 6, uid=0)
    for _ in range(300):
        cl.step()
        owner = cl._owner.get(0)
        e = cl.replicas[owner].lc.entries.get(0) if owner is not None else None
        if (e is not None and e.state is ReqState.PREFILLING
                and 0 < e.prefill_pos < len(prompt)):
            break
    else:
        raise AssertionError("never caught the request mid-prefill")
    pos = e.prefill_pos
    assert pos % CT == 0  # migration runs between ticks: chunk-aligned
    cl.migrate(0, 1 - owner)
    dst = cl.replicas[1 - owner]
    assert dst.lc.entries[0].prefill_pos == pos
    done = cl.run()
    assert dst.lc.entries.get(0) is None  # finished (pruned) on the dest
    np.testing.assert_array_equal(want, done[0].tokens)


def test_abort_while_migrating_releases_both_sides():
    """Aborting a request that sits in MIGRATING on the destination (its
    ticket adopted, its splice not yet run) leaves BOTH pools pristine:
    the source released everything at migrate_out, and the destination's
    store pins nothing until swap-in."""
    cl = _cluster("dense")
    cl.add_request(_prompt(6, seed=1), 8, uid=0)
    e, owner = _step_until(cl, 0, ReqState.RUNNING, min_outputs=1)
    src, dst = cl.replicas[owner], cl.replicas[1 - owner]
    ticket = src.migrate_out(0)
    dst.migrate_in(ticket)
    cl._owner[0] = 1 - owner
    assert dst.lc.entries[0].state is ReqState.MIGRATING
    out = cl.abort(0)
    assert out is not None and out.finish_reason == "aborted"
    assert dst.lc.entries.get(0) is None
    _assert_pool_pristine(src)
    _assert_pool_pristine(dst)
    assert not src._work_remaining() and not dst._work_remaining()


# -- drained cluster restores every pool (property) ---------------------------


@settings(max_examples=15, deadline=None)
@given(
    spec=st.lists(
        st.tuples(
            st.integers(min_value=1, max_value=10),  # prompt length
            st.integers(min_value=1, max_value=6),  # max_new
            st.integers(min_value=0, max_value=4),  # arrival (cluster ticks)
        ),
        min_size=1, max_size=5,
    ),
    admission=st.sampled_from(["balanced", "round_robin"]),
)
def test_drained_cluster_restores_pools(spec, admission):
    """Whatever the workload and routing, a drained cluster returns every
    replica's pool to its initial free state — with hot-spot migration
    enabled and an aggressive threshold so moves actually happen."""
    cl = _cluster(
        "dense", admission=admission,
        migration=MigrationConfig(enabled=True, imbalance_tokens=8,
                                  min_remaining=2),
    )
    for i, (plen, new, arr) in enumerate(spec):
        cl.add_request(_prompt(plen, seed=i), new, uid=i, arrival=arr)
    done = cl.run()
    assert len(done) == len(spec)
    for i, (plen, new, arr) in enumerate(spec):
        assert done[i].tokens.shape[0] == new
    for eng in cl.replicas:
        _assert_pool_pristine(eng)
        assert not eng._work_remaining()
    assert cl._work_remaining() is False


# -- global-queue policy parity -----------------------------------------------


def test_n1_cluster_matches_single_engine_fifo():
    """An N=1 cluster is a pass-through: the global queue admits in
    exactly the single-engine FIFO order and every stream is identical —
    the dispatcher adds routing, never reordering."""
    spec = [(6, 5, 0), (4, 3, 0), (8, 4, 1), (5, 6, 3)]
    ref = _single("dense", max_slots=2)
    cl = _cluster("dense", n_replicas=1, max_slots=2)
    for i, (plen, new, arr) in enumerate(spec):
        p = _prompt(plen, seed=i)
        ref.add_request(p, new, uid=i, arrival=arr)
        cl.add_request(p, new, uid=i, arrival=arr)
    want = ref.run()
    done = cl.run()
    for i in range(len(spec)):
        np.testing.assert_array_equal(want[i].tokens, done[i].tokens)
    order = lambda outs: [u for u, _ in sorted(
        outs.items(), key=lambda kv: (kv[1].admitted_step, kv[0]))]
    assert order(want) == order(done)
    assert len(cl.admission_waits) == len(spec)


# -- peek_prefix is a pure probe ----------------------------------------------


def test_peek_prefix_probe_has_no_side_effects():
    """``BlockPool.peek_prefix`` returns what ``lookup`` would serve but
    mutates nothing: no hit/miss counts, no tokens-saved, no LRU bump —
    the dispatcher probes every replica per admission and N-1 of those
    probes route nowhere."""
    eng = _single("dense", prefix_cache=True, max_slots=2)
    shared = _prompt(12, seed=3)
    eng.add_request(shared, 4, uid=0)  # warm the chain
    eng.run()
    pool = eng.pool
    pc = pool.prefix_cache
    snap = (pc.hits, pc.misses, pc.tokens_saved, pc._tick, pc.inserts,
            pc.evictions, pc.retained)
    ticks = {k: e.tick for k, e in pc.entries.items()}

    probe = pool.peek_prefix(np.concatenate([shared, _prompt(3, seed=4)]), CT)
    assert probe == 12  # the full warmed chain is resumable
    assert pool.peek_prefix(_prompt(12, seed=9), CT) == 0  # miss probes too
    assert (pc.hits, pc.misses, pc.tokens_saved, pc._tick, pc.inserts,
            pc.evictions, pc.retained) == snap
    assert {k: e.tick for k, e in pc.entries.items()} == ticks

    # the probe PREDICTS the mutating lookup: same fork the admission gets
    fork, _ = pc.lookup(np.concatenate([shared, _prompt(3, seed=4)]), CT)
    assert fork == probe
    assert pc._tick > snap[3]  # and the real lookup does bump


# -- swap-store byte cap ------------------------------------------------------


def test_swap_store_cap_degrades_oldest():
    """Under a host swap-store byte cap, the OLDEST swapped request is
    degraded to recompute (releasing its store) instead of growing the
    store without bound — counted in telemetry, invisible in the streams."""
    model, params, kw = _engine_kw("dense")
    spec = [(8, 10, 0)] * 4  # 4 x (17 rows = 5 blocks) vs 6 usable blocks

    def serve(cap):
        eng = PagedEngine(
            model, params,
            preemption=PreemptionConfig(mode="swap", swap_store_cap_bytes=cap),
            **{**kw, "max_slots": 3, "num_blocks": 7},
        )
        for i, (plen, new, arr) in enumerate(spec):
            eng.add_request(_prompt(plen, seed=i), new, uid=i, arrival=arr)
        return eng, eng.run()

    free_eng, free_done = serve(None)  # uncapped: swaps accumulate freely
    assert free_eng.lc.preempted(kind="swap") >= 1, "workload must force swaps"
    assert free_eng.swap_cap_evictions == 0

    cap_eng, cap_done = serve(1)  # no store survives a 1-byte cap
    assert cap_eng.swap_cap_evictions >= 1
    assert cap_eng.lc.counts.get(
        ("preempted_swapped", "preempted_recompute"), 0) >= 1
    assert cap_eng.recompute_tokens > 0
    assert cap_eng.swap_store_bytes == 0  # nothing resident after the drain
    for i in range(len(spec)):  # degrade is a scheduling move, not a numerics one
        np.testing.assert_array_equal(free_done[i].tokens, cap_done[i].tokens)


# -- per-replica device placement (8 forced host devices) ---------------------


def test_replica_device_placement_and_streams():
    """With a data=2 mesh, the two replicas' KV arenas live on DISTINCT
    devices (dispatch-concurrent decode) and the streams still match a
    single engine bit-for-bit — placement is invisible in the tokens."""
    out = run_with_devices(
        """
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import GlassConfig
        from repro.models import ModelConfig, build_model
        from repro.launch.mesh import make_host_mesh
        from repro.serve.cluster import ClusterEngine, MigrationConfig
        from repro.serve.engine import PagedEngine

        cfg = ModelConfig(name="cl-dev", family="dense", n_layers=2, d_model=48,
                          n_heads=4, n_kv_heads=2, head_dim=12, d_ff=96,
                          vocab_size=101, dtype="float32", remat="none")
        model = build_model(cfg)
        params = model.init(jax.random.key(0))
        glass = GlassConfig(density=0.5, selection="neuron", block_size=128)
        prior = jnp.abs(jax.random.normal(jax.random.key(7),
                                          (cfg.n_layers, cfg.d_ff)))
        kw = dict(max_slots=2, max_len=32, block_size=4, chunk_tokens=4,
                  glass=glass, global_prior=prior)

        mesh = make_host_mesh(data=2, model=4)
        cl = ClusterEngine(model, params, n_replicas=2, mesh=mesh,
                           migration=MigrationConfig(enabled=False), **kw)
        devs = [
            {d for leaf in jax.tree.leaves(eng.pool.cache)
             for d in leaf.devices()}
            for eng in cl.replicas
        ]
        assert devs[0] and devs[1] and devs[0].isdisjoint(devs[1]), devs
        assert cl.replicas[0].programs.namespace == "replica0"
        assert all(
            name.startswith("replica1/")
            for name in cl.replicas[1].programs.sizes()
        )

        ref = PagedEngine(model, params, **kw)
        prompts = [np.random.RandomState(s).randint(3, 101, size=6).astype(np.int32)
                   for s in range(3)]
        for i, p in enumerate(prompts):
            ref.add_request(p, 6, uid=i)
            cl.add_request(p, 6, uid=i)
        want = ref.run()
        done = cl.run()
        for i in range(3):
            np.testing.assert_array_equal(want[i].tokens, done[i].tokens)
        # migration across DEVICES: host-roundtrip wire, still bit-exact
        cl2 = ClusterEngine(model, params, n_replicas=2, mesh=mesh,
                            migration=MigrationConfig(enabled=False), **kw)
        from repro.serve.lifecycle import ReqState
        cl2.add_request(prompts[0], 6, uid=0)
        for _ in range(200):
            cl2.step()
            owner = cl2._owner.get(0)
            e = cl2.replicas[owner].lc.entries.get(0) if owner is not None else None
            if e is not None and e.state is ReqState.RUNNING and len(e.outputs) >= 2:
                cl2.migrate(0, 1 - owner)
                break
        done2 = cl2.run()
        np.testing.assert_array_equal(want[0].tokens, done2[0].tokens)
        print("PLACEMENT-OK")
        """,
        n_devices=8,
    )
    assert "PLACEMENT-OK" in out
