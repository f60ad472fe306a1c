"""PagedEngine's decode waste counters (``counters()``): the FFN tile
fetches against the union of the decoding rows' kept tiles, and the
attention blocks walked against those holding live K/V.  The counting
rules by hand, then every decode call of a served run against a brute
force count of what that call's programs are handed."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import GlassConfig, GlassParams
from repro.models import ModelConfig, build_model
from repro.serve.engine import PagedEngine, attn_live_blocks, ffn_tile_fetches
from repro.serve.lifecycle import ReqState

# d_ff 128 in blocks of 32: 4 tiles a layer, 2 kept at density 0.5; a
# 16-token window over blocks of 8 so that long rows are window-capped
CFG = ModelConfig(name="ctr-dense", family="dense", n_layers=2, d_model=48, n_heads=4,
                  n_kv_heads=2, head_dim=12, d_ff=128, vocab_size=101, dtype="float32",
                  remat="none", sliding_window=16)
GC = GlassConfig(density=0.5, selection="block", block_size=32)
BS = 8


def lists(*rows):
    """(B, L=1, nb) block lists, one per slot."""
    return np.asarray(rows, np.int32)[:, None, :]


@pytest.mark.parametrize("ids, groups, perm, T, want", [
    (lists([0, 2], [0, 2]), (2,), [0, 1], 1, 2),  # one group: its list once
    (lists([0, 2], [1, 2]), (), None, 1, 4),  # rowwise: both lists
    (lists([0, 2], [2, 3]), (), None, 1, 3),  # a step on the previous tile fetches nothing
    (lists([0, 2], [1, 3], [0, 0], [0, 0]), (), None, 1, 5),  # cleared rows: tile 0 once
    (lists([1, 3], [0, 2], [1, 3]), (2,), [0, 2, 1], 1, 4),  # a group, then the rest rowwise
    (lists([0, 2]), (), None, 2, 4),  # T queries walk the row's list T times
    (np.asarray([[[0, 2], [1, 3]], [[0, 2], [3, 1]]], np.int32), (), None, 1, 7),  # 2 layers
])
def test_ffn_tile_fetches_by_hand(ids, groups, perm, T, want):
    perm = None if perm is None else np.asarray(perm, np.int32)
    assert ffn_tile_fetches(ids, groups, perm, T) == want


def test_attn_live_blocks_by_hand():
    # blocks of 8, a global layer and an 8-token window; rows at 5 and 20,
    # two steps.  Global: 1 + 1 and 3 + 3 blocks; window: 1 + 1, 2 + 2
    windows = [(2**30, 1), (8, 1)]
    assert attn_live_blocks(np.asarray([5, 20]), 2, windows, 8) == 14
    assert attn_live_blocks(np.asarray([0]), 1, windows, 8) == 2
    assert attn_live_blocks(np.asarray([20]), 1, [(8, 3)], 8) == 6


def brute(args, windows, bs):
    """What one decode call's kernels are handed, counted grid step by grid
    step: (tile fetches, union of kept tiles, blocks walked, live blocks)."""
    lengths, btab, dmask = (np.asarray(a) for a in (args[2], args[4], args[5]))
    idx, scale = np.asarray(args[6]["idx"]), np.asarray(args[6]["scale"])  # (L, B, nb)
    H, perm, groups = args[7].shape[0], np.asarray(args[9]), args[18]
    L, B, _ = idx.shape
    nb = btab.shape[1]
    read = union = live = 0
    for layer in range(L):
        calls, off = [], 0
        order = perm if groups else np.arange(B)
        for g in groups:
            calls.append(list(idx[layer, order[off]]))
            off += g
        calls.append([t for b in order[off:] for t in idx[layer, b]])
        for tiles in calls:
            prev = None
            for t in tiles:
                read += t != prev
                prev = t
        union += len({t for b in np.flatnonzero(dmask)
                      for t, s in zip(idx[layer, b], scale[layer, b]) if s})
        for b in np.flatnonzero(dmask):
            for j in range(H):
                q = lengths[b] + j
                live += sum(k * bs <= q and q - ((k + 1) * bs - 1) < windows[layer]
                            for k in range(nb))
    return H * read, H * union, H * L * B * nb, live


def serve(prompts, max_slots, glass=None, **kw):
    """Serve ``prompts`` to the end; per decode call, the counters' change
    beside the brute force count."""
    model = build_model(CFG)
    params = model.init(jax.random.key(0))
    prior = jnp.abs(jax.random.normal(jax.random.key(7), (CFG.n_layers, CFG.d_ff)))
    eng = PagedEngine(model, params, max_slots=max_slots, max_len=48, block_size=BS,
                      chunk_tokens=16, glass=GC, global_prior=prior,
                      glass_mode="block_sparse", attn_mode="paged_pallas", **kw)
    windows = np.asarray([CFG.sliding_window] * CFG.n_layers)
    calls, keys = [], ("ffn_tiles_read", "ffn_tiles_union", "attn_blocks_walked",
                       "attn_blocks_live")
    last = dict.fromkeys(keys, 0)
    decode = eng._decode

    def spy(*args):
        now = eng.counters()
        calls.append((tuple(now[k] - last[k] for k in keys), brute(args, windows, BS),
                      args[18]))
        last.update({k: now[k] for k in keys})
        return decode(*args)

    eng._decode = spy
    for i, p in enumerate(prompts):
        eng.add_request(p, 12, glass=None if glass is None else glass[i])
    for _ in range(200):
        eng.step()
        if not eng._work_remaining():
            break
    return eng, calls


RNG = np.random.RandomState(3)
SAME = RNG.randint(3, 101, size=14).astype(np.int32)


@pytest.mark.parametrize("case", ["same_prompt", "distinct", "lower_density"])
def test_counters_match_what_each_decode_call_is_handed(case):
    prompts = {"same_prompt": [SAME, SAME.copy()],
               "distinct": [RNG.randint(3, 101, size=n).astype(np.int32) for n in (9, 14, 20)],
               "lower_density": [SAME]}[case]
    glass = [GlassParams(density=0.25)] if case == "lower_density" else None
    eng, calls = serve(prompts, max_slots={"distinct": 4}.get(case, 2), glass=glass)
    assert calls
    for got, want, _ in calls:
        assert got == want
    c = eng.counters()
    assert [c[k] for k in ("t", "slot_steps", "kv_row_ticks")] == \
        [eng.t, eng.slot_steps, eng.kv_row_ticks]
    assert c["attn_blocks_walked"] > c["attn_blocks_live"] > 0
    grouped = [(got, want) for got, want, groups in calls if groups]
    if case == "same_prompt":
        # identical lists and scales batch through one shared grid, which
        # streams each kept tile once: read == union in every such call
        assert grouped and all(got[0] == got[1] for got, _ in grouped)
    if case == "lower_density":
        # the row keeps its capacity list of 2 tiles a layer, and scales the
        # one that density 0.25 drops by 0.0: fetched, but not in the union
        assert c["ffn_tiles_union"] == CFG.n_layers * eng.slot_steps
        assert c["ffn_tiles_read"] >= 2 * c["ffn_tiles_union"]


def test_migrated_request_keeps_its_tiles():
    src, _ = serve([SAME], 2)
    dst, _ = serve([], 2)
    src.add_request(SAME.copy(), 12)
    for _ in range(20):
        src.step()
        run = src.lc.in_state(ReqState.RUNNING)
        if run:
            break
    tiles = run[0].ffn_tiles
    assert tiles.shape == (CFG.n_layers, CFG.d_ff // GC.block_size)
    assert tiles.sum(1).tolist() == [2] * CFG.n_layers  # density 0.5 of 4 tiles
    dst.migrate_in(src.migrate_out(run[0].uid))
    (e,) = dst.lc.entries.values()
    np.testing.assert_array_equal(e.ffn_tiles, tiles)
