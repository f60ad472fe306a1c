#!/usr/bin/env python3
"""Bring-up smoke run of the GLASS serving path on a TPU.

Drives ``PagedEngine``, the production serving path, through its public
entry points (``add_request`` / ``step``) at the published llama3-8b widths
in bf16 with seeded random weights.  Only depth is cut, so that weights, KV
blocks and GLASS arenas fit one 16 GB v5e chip.

    python chip_smoke.py               # one chip: phases A and B
    python chip_smoke.py --four-chips  # four chips: cluster path only

Phase A is the chip-native path: the block-sparse GLASS FFN kernel over the
union of the rows' block lists (per-row tile scales), the fused
paged-attention kernel and self-speculative decode.  Its decode logits, taken from the live engine
state, are checked against the XLA path (gather attention + masked FFN).
Phase B is the default constructor path (compact GLASS, gather attention)
and records the size of its per-slot compact weight copies.
``--four-chips`` runs a 4-replica ``ClusterEngine`` with a hot-spot burst
that forces migration, compares its streams with one ``PagedEngine``, and
checks that each replica's state lives on its own chip.

The compile cache is JAX's: ``JAX_COMPILATION_CACHE_DIR`` when set, else
``.jax_cache`` in this checkout.  The last line of standard output is one
JSON object; a failed check raises, exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SEED = 0
N_LAYERS = 8  # of 32: ~5.6 GB of bf16 weights with embedding and head
BLOCK = 128  # GLASS FFN block = lane width
# Relative L2 gate on kernel-path vs XLA-path decode logits.  Both paths
# compute in bf16 with f32 accumulation but round in different places: the
# FFN kernel keeps gate/up in f32 and sums tiles in its own order, the masked
# XLA FFN rounds gate/up/h to bf16; the attention kernel's online softmax
# sums in another order than the dense softmax.  Each is ~1 bf16 ulp
# (2**-8 = 0.4%) of a sublayer output; over 8 layers x 2 sublayers that
# random-walks to ~1-2% of the logits.  A wrong head, block or scale moves a
# sublayer by O(100%) of its output, far beyond 5%.
LOGIT_REL_L2_TOL = 0.05


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    log(f"check {what}: {'PASS' if ok else 'FAIL'}")
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def memory(dev) -> str:
    st = dev.memory_stats() or {}
    return (f"bytes_in_use={st.get('bytes_in_use')} "
            f"peak_bytes_in_use={st.get('peak_bytes_in_use')}")


def build(jax, jnp):
    """llama3-8b at published widths, depth cut, seeded random weights, and
    a GLASS activation prior computed from a seeded random-token corpus."""
    from repro.configs import get_config
    from repro.core.glass import compute_global_prior
    from repro.core.nps import NPSConfig
    from repro.models import build_model

    full = get_config("llama3-8b")
    cfg = full.replace(n_layers=N_LAYERS)
    log(f"config {cfg.name}: d_model={cfg.d_model} d_ff={cfg.d_ff} "
        f"heads={cfg.n_heads} kv_heads={cfg.n_kv_heads} head_dim={cfg.head_dim} "
        f"vocab={cfg.vocab_size} dtype={cfg.dtype}")
    log(f"cut: n_layers {full.n_layers} -> {cfg.n_layers} (depth only; "
        f"every width as published)")
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.key(SEED))
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    log(f"weights: {n_bytes} bytes of random {cfg.dtype} from seed {SEED}")
    # NPS sampling keeps a (batch, V, V) bigram table, 16 GB a row at
    # V=128256, so the prior is taken over a seeded random-token corpus
    # through the same compute_global_prior entry (its corpus= path)
    npc = NPSConfig(n_seqs=8, seq_len=64, batch=8)
    corpus = jax.random.randint(
        jax.random.key(SEED + 1), (npc.n_seqs, npc.seq_len), 3, cfg.vocab_size
    )
    prior = compute_global_prior(model, params, jax.random.key(SEED + 2), npc,
                                 variant="A", corpus=corpus)
    prior = jax.block_until_ready(prior)
    return model, params, prior


def prompts(np, V, lens, seed):
    rng = np.random.RandomState(seed)
    return [rng.randint(3, V, size=n).astype(np.int32) for n in lens]


def drain(eng, limit=2000):
    """Step until every request finished; returns {uid: final output}."""
    done = {}
    for _ in range(limit):
        for o in eng.step():
            if o.finished:
                done[o.uid] = o
        if not eng._work_remaining():
            return done
    raise RuntimeError(f"engine did not drain in {limit} steps")


def logits_check(jax, jnp, np, model, params, eng):
    """One decode step from the engine's live state, through the kernels
    (paged Pallas attention + block-sparse FFN over the union of the rows'
    block lists) and through XLA (gather attention + masked FFN).  Returns
    whether the union held fewer tiles than the rows' lists together."""
    from repro.serve.lifecycle import ReqState

    run = eng.lc.in_state(ReqState.RUNNING)
    decoding, lengths, toks, btab = eng._scan_inputs(run, 1)
    arena = eng.glass_slots.arena
    idx, scale = arena["idx"], arena["scale"]
    bs = eng.glass.block_size

    def kernel_path(p, cache, t, ln, bt, ix, sc):
        return model.decode_step(
            p, t[:, None], cache, ln, block_table=bt, attn_mode="paged_pallas",
            ffn_block_idx=ix, ffn_block_scale=sc, ffn_block_size=bs,
        )[0][:, 0].astype(jnp.float32)

    def xla_path(p, cache, t, ln, bt, ix, sc):
        # the block list and tile scales as the masked mode's unit mask
        nblk = model.cfg.d_ff // bs
        blk = jnp.sum(jax.nn.one_hot(ix, nblk, dtype=jnp.float32) * sc[..., None], axis=-2)
        mask = jnp.repeat(blk, bs, axis=-1)  # (L, B, d_ff)
        return model.decode_step(
            p, t[:, None], cache, ln, block_table=bt, attn_mode="gather",
            ffn_masks=mask,
        )[0][:, 0].astype(jnp.float32)

    args = (params, eng.pool.cache, jnp.asarray(toks), jnp.asarray(lengths),
            jnp.asarray(btab), idx, scale)
    compiled = jax.jit(kernel_path).lower(*args).compile()
    n_custom = compiled.as_text().count("tpu_custom_call")
    lk = np.asarray(compiled(*args))
    lx = np.asarray(jax.jit(xla_path)(*args))
    rows = np.flatnonzero(decoding)
    lk, lx = lk[rows], lx[rows]
    rel = float(np.linalg.norm(lk - lx) / np.linalg.norm(lx))
    union = int(np.logical_or.reduce([e.ffn_tiles for e in run]).sum())
    kept = int(sum(e.ffn_tiles.sum() for e in run))
    log(f"logits check over {len(rows)} decoding rows (union of {kept} kept "
        f"tiles: {union}): rel_l2={rel:.3e} "
        f"max_abs={float(np.max(np.abs(lk - lx))):.3e} "
        f"max_abs_ref={float(np.max(np.abs(lx))):.3e} "
        f"argmax_agree={int(np.sum(lk.argmax(-1) == lx.argmax(-1)))}/{len(rows)} "
        f"finite={bool(np.isfinite(lk).all())}")
    log(f"kernel decode program: tpu_custom_call x{n_custom}")
    check(bool(np.isfinite(lk).all()), "kernel-path logits finite")
    check(rel <= LOGIT_REL_L2_TOL,
          f"kernel vs XLA decode logits rel_l2 <= {LOGIT_REL_L2_TOL}")
    check(n_custom > 0, "compiled decode program contains tpu_custom_call")
    return union < kept


def phase_a(jax, jnp, np, model, params, prior, dev, meter):
    from repro.core import GlassConfig
    from repro.core.glass import GlassParams
    from repro.serve.engine import Engine, PagedEngine
    from repro.serve.lifecycle import ReqState

    V = model.cfg.vocab_size
    g = GlassConfig(density=0.5, variant="A", selection="block",
                    block_size=BLOCK, draft_ratio=0.5)
    t0, c0 = time.perf_counter(), meter.secs
    eng = PagedEngine(model, params, max_slots=8, max_len=128, block_size=16,
                      chunk_tokens=16, glass=g, global_prior=prior,
                      glass_mode="block_sparse", attn_mode="paged_pallas",
                      spec_k=3)
    a, b, c, d = prompts(np, V, (48, 16, 32, 64), SEED + 3)
    # a twice: identical prompts give identical block lists, so the union
    # of the rows' lists is smaller than the lists together.  uid 2 asks for a quarter density: its dropped tiles scale to 0.0
    reqs = [(a, None), (a, None), (b, GlassParams(density=0.25)), (c, None), (d, None)]
    for uid, (p, gp) in enumerate(reqs):
        eng.add_request(p, 32, uid=uid, glass=gp)
    log(f"phase A: PagedEngine(glass_mode=block_sparse, attn_mode=paged_pallas, "
        f"spec_k=3, max_slots=8), density 0.5 / draft 0.25, "
        f"{len(reqs)} requests, prompt lens {[len(p) for p, _ in reqs]}, max_new 32")
    done = {}
    checked = False
    for _ in range(2000):
        for o in eng.step():
            if o.finished:
                done[o.uid] = o
        running = eng.lc.in_state(ReqState.RUNNING)
        if not checked and len(running) == len(reqs):
            shared = logits_check(jax, jnp, np, model, params, eng)
            check(shared, "union FFN grid shares tiles across rows")
            checked = True
        if not eng._work_remaining():
            break
    serve_s = time.perf_counter() - t0
    check(checked, "logits check ran with every request decoding")
    check(len(done) == len(reqs), "phase A drained every request")
    n_tok = sum(len(o.tokens) for o in done.values())
    check(all(len(o.tokens) == 32 and o.tokens.min() >= 0 and o.tokens.max() < V
              for o in done.values()), "phase A streams complete and in vocab")
    tel = eng.spec_telemetry
    log(f"phase A: tokens_generated={n_tok} wall_s={serve_s:.1f} "
        f"compile_s_in_phase={meter.secs - c0:.1f} "
        f"programs_compiled={eng.programs.total()} "
        f"spec_rounds={eng.spec_ticks} draft_acceptance={tel['draft_acceptance_rate']:.3f}")
    log(f"phase A: duplicate prompts give identical streams: "
        f"{bool(np.array_equal(done[0].tokens, done[1].tokens))}")
    # information, not a gate: the static-batch Engine (non-paged XLA
    # attention, whole-prompt prefill, unscaled shared-list FFN kernel)
    ref = Engine(model, params, glass=g, global_prior=prior, glass_mode="block_sparse")
    for uid in (0, 3):
        p = reqs[uid][0]
        want = ref.generate(jnp.asarray(p)[None], 32).tokens[0]
        same = bool(np.array_equal(np.asarray(want), done[uid].tokens))
        log(f"phase A: uid {uid} greedy stream bit-identical to Engine.generate: {same}")
    log(f"phase A: {memory(dev)}")
    return n_tok


def phase_b(jax, np, model, params, prior, dev, meter):
    from repro.core import GlassConfig
    from repro.serve.engine import PagedEngine

    V = model.cfg.vocab_size
    t0, c0 = time.perf_counter(), meter.secs
    # the default constructor: glass_mode="compact", attn_mode="gather"; two
    # slots of per-slot compact FFN copies at density 0.5 fit beside the weights
    eng = PagedEngine(model, params, max_slots=2, max_len=128,
                      glass=GlassConfig(density=0.5, variant="A"), global_prior=prior)
    for uid, p in enumerate(prompts(np, V, (16, 32), SEED + 4)):
        eng.add_request(p, 8, uid=uid)
    done = drain(eng)
    check(len(done) == 2 and all(len(o.tokens) == 8 for o in done.values()),
          "phase B streams complete")
    arena = sum(x.nbytes for t in (eng.glass_slots.arena, eng.glass_slots.draft_arena)
                if t is not None for x in jax.tree.leaves(t))
    log(f"phase B: PagedEngine default (compact, gather), max_slots=2: "
        f"tokens_generated={sum(len(o.tokens) for o in done.values())} "
        f"wall_s={time.perf_counter() - t0:.1f} compile_s_in_phase={meter.secs - c0:.1f} "
        f"compact_arena_bytes={arena} ({arena // 2} per slot)")
    log(f"phase B: {memory(dev)}")


def four_chips(jax, np, model, params, prior, meter):
    from repro.core import GlassConfig
    from repro.launch.mesh import make_host_mesh
    from repro.serve.cluster import ClusterEngine, MigrationConfig
    from repro.serve.engine import PagedEngine

    devs = jax.devices()
    check(len(devs) == 4, f"four chips visible (found {len(devs)})")
    V = model.cfg.vocab_size
    g = GlassConfig(density=0.5, variant="A", selection="block", block_size=BLOCK)
    kw = dict(max_slots=4, max_len=128, block_size=16, chunk_tokens=32, glass=g,
              global_prior=prior, glass_mode="block_sparse",
              attn_mode="paged_pallas", decode_chunk=2)
    # hot spot: round-robin sends every long request to replica 0; once the
    # short ones drain, the load gap triggers migration off replica 0
    ps = prompts(np, V, (32,) * 8, SEED + 5)
    new = [40 if i % 4 == 0 else 4 for i in range(8)]
    t0, c0 = time.perf_counter(), meter.secs
    single = PagedEngine(model, params, **kw)
    for uid, (p, n) in enumerate(zip(ps, new)):
        single.add_request(p, n, uid=uid)
    want = drain(single)
    cl = ClusterEngine(model, params, n_replicas=4, mesh=make_host_mesh(data=4),
                       admission="round_robin",
                       migration=MigrationConfig(imbalance_tokens=16, min_remaining=24),
                       **kw)
    for uid, (p, n) in enumerate(zip(ps, new)):
        cl.add_request(p, n, uid=uid)
    got = cl.run()
    log(f"four chips: {len(ps)} requests, max_new {new}, wall_s="
        f"{time.perf_counter() - t0:.1f} compile_s_in_phase={meter.secs - c0:.1f} "
        f"migrations={cl.migrations} migration_bytes={cl.migration_bytes}")
    check(cl.migrations >= 1, "hot-spot burst migrated at least one request")
    check(set(got) == set(want) and all(
        np.array_equal(got[u].tokens, want[u].tokens) for u in want),
        "cluster streams bit-identical to one PagedEngine")
    homes = [s[0] for s in cl.devices]
    check(len(set(homes)) == 4, "replicas on four distinct chips")
    for r, eng in enumerate(cl.replicas):
        gs = eng.glass_slots
        state = {"params": eng.params, "glass params": gs.params, "prior": gs.prior,
                 "KV pool": eng.pool.cache, "GLASS arena": gs.arena,
                 "GLASS draft arena": gs.draft_arena}
        where = {name: {d for x in jax.tree.leaves(t) for d in x.devices()}
                 for name, t in state.items() if t is not None}
        log(f"replica {r} ({homes[r]}): " + ", ".join(
            f"{name} on {sorted(str(d) for d in ds)}" for name, ds in where.items()))
        check("GLASS arena" in where and all(ds == {homes[r]} for ds in where.values()),
              f"replica {r} state lives on its own chip")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-replica cluster path and its comparison")
    args = ap.parse_args()

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found platform {dev.platform!r}")

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax.numpy as jnp
    import numpy as np

    from bench.build import compile_meter

    meter = compile_meter(jax)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    log(f"device: {json.dumps(device)} jax={jax.__version__} "
        f"compilation_cache_dir={jax.config.jax_compilation_cache_dir}")
    t0 = time.perf_counter()
    model, params, prior = build(jax, jnp)
    log(f"set-up (weights + prior): {time.perf_counter() - t0:.1f}s, {meter.line()}, "
        f"{memory(dev)}")
    if args.four_chips:
        four_chips(jax, np, model, params, prior, meter)
    else:
        phase_a(jax, jnp, np, model, params, prior, dev, meter)
        gc.collect()
        phase_b(jax, np, model, params, prior, dev, meter)
    log(f"total: {time.perf_counter() - t0:.1f}s, {meter.line()}")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
