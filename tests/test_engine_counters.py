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
from repro.kernels.ops import ffn_union
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


def maps(*rows, layers=1):
    """Per row, the (L, 4) bool map of the tiles it keeps: a row is a list
    of kept tile ids per layer (one flat list when ``layers`` is 1)."""
    out = []
    for r in rows:
        per_layer = [r] if layers == 1 else r
        m = np.zeros((layers, 4), bool)
        for layer, ids in enumerate(per_layer):
            m[layer, ids] = True
        out.append(m)
    return out


@pytest.mark.parametrize("tiles, want", [
    (maps([0, 2], [0, 2]), 2),  # identical lists: one list's tiles
    (maps([0, 2], [1, 2]), 3),  # overlapping lists: their union
    (maps([0, 2], [1, 3]), 4),  # disjoint lists: every tile once
    (maps([0, 2], [1, 3], [], []), 4),  # cleared rows keep nothing, add nothing
    (maps([1, 3], [0, 2], [1, 3]), 4),  # a repeated list adds nothing
    (maps([0, 1, 3]), 3),  # one row: its own list, as a one-row grid reads it
    (maps([[0, 2], [1, 3]], [[0, 2], [3, 1]], layers=2), 4),  # 2 layers: 2 + 2
    (maps([[0, 2], []], layers=2), 3),  # a layer that keeps nothing fetches its pad tile
])
def test_ffn_tile_fetches_by_hand(tiles, want):
    assert ffn_tile_fetches(tiles) == want


def test_attn_live_blocks_by_hand():
    # blocks of 8, a global layer and an 8-token window; rows at 5 and 20,
    # two steps.  Global: 1 + 1 and 3 + 3 blocks; window: 1 + 1, 2 + 2
    windows = [(2**30, 1), (8, 1)]
    assert attn_live_blocks(np.asarray([5, 20]), 2, windows, 8) == 14
    assert attn_live_blocks(np.asarray([0]), 1, windows, 8) == 2
    assert attn_live_blocks(np.asarray([20]), 1, [(8, 3)], 8) == 6


def brute(args, windows, bs):
    """What one decode call's kernels are handed, counted grid step by grid
    step: (tile fetches, union of kept tiles, blocks walked, live blocks).
    The FFN grid walks the union ``ffn_union`` builds from the call's lists
    and decoding mask; a step fetches when its tile id differs from the
    previous step's."""
    lengths, btab, dmask = (np.asarray(a) for a in (args[2], args[4], args[5]))
    idx, scale = np.asarray(args[6]["idx"]), np.asarray(args[6]["scale"])  # (L, B, nb)
    H = args[7].shape[0]
    L, B, _ = idx.shape
    nb = btab.shape[1]
    grid = np.asarray(ffn_union(args[6]["idx"], args[6]["scale"], args[5],
                                n_tiles=CFG.d_ff // GC.block_size)[0])
    read = union = live = 0
    for layer in range(L):
        prev = None
        for t in grid[layer]:
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
        calls.append((tuple(now[k] - last[k] for k in keys), brute(args, windows, BS)))
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
    for got, want in calls:
        assert got == want
    c = eng.counters()
    assert [c[k] for k in ("t", "slot_steps", "kv_row_ticks")] == \
        [eng.t, eng.slot_steps, eng.kv_row_ticks]
    assert c["attn_blocks_walked"] > c["attn_blocks_live"] > 0
    # the union grid streams each kept tile once a step: read == union in
    # every call, whether the lists coincide, differ, or drop tiles at 0.0
    assert all(got[0] == got[1] for got, _ in calls)
    if case == "lower_density":
        # the row keeps its capacity list of 2 tiles a layer, and scales the
        # one that density 0.25 drops by 0.0: neither fetched nor in the union
        assert c["ffn_tiles_union"] == CFG.n_layers * eng.slot_steps
        assert c["ffn_tiles_read"] == c["ffn_tiles_union"]


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
