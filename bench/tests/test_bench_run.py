"""A whole run at a tiny size on the CPU: the harness's look for a chip is
skipped, the rest of a run is driven.  A sound run is correct; a run whose
engine alters a token where it is produced is not, nor one whose prefill
leaves the KV pool unchanged; the control (the reference in fp8) reads a
gap far above the program's; gaps between tokens stay positive across the
window's opening; the window's host log orders its ticks longest first and
counts the garbage collector's passes; a traced run hands the per-layer
readers the program's counters and spans; and without a TPU the command
exits non-zero and prints no result.  Also the checks made before set-up:
the registry entry against the configuration file, and a layer kind with
no work module."""
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
TINY_HF = dict(hidden_size=128, intermediate_size=512, num_attention_heads=4,
               num_key_value_heads=2, num_hidden_layers=2, vocab_size=256,
               rms_norm_eps=1e-5, rope_theta=10000.0, sliding_window=48,
               tie_word_embeddings=False, torch_dtype="bfloat16")
FILES = {
    "config": dict(
        name="tiny", registry="mistral-7b", reference="dense_glass", hf_config=TINY_HF,
        registry_overrides=dict(n_layers=2, d_model=128, n_heads=4, n_kv_heads=2, head_dim=32,
                                d_ff=512, vocab_size=256, sliding_window=48),
        glass=dict(density=0.5, lam=0.5, variant="A", selection="block", block_size=128,
                   bos_id=1, prior_seqs=4, prior_len=16),
        engine=dict(max_slots=4, block_size=16, chunk_tokens=32, decode_chunk=8, spec_k=0,
                    glass_mode="block_sparse", attn_mode="paged_pallas")),
    "mix": dict(prompt=dict(median=24, sigma=0.4, min=16, max=32),
                output=dict(median=10, sigma=0.3, min=6, max=16)),
    "cell": dict(rate_per_s=3.0, resident=2, prerun_s=0, max_len=48, num_blocks=13,
                 check=dict(requests=3, limits=dict(logit_gap_max=0.5, logit_gap_mean=0.02))),
}
SEED = 2**31 + 77


def tiny_run(fault=None, control=False, trace=False):
    from bench import harness

    return harness.run("mistral7b.short_long", SEED, 2.0, trace, time.perf_counter(),
                       require_chip=False, files=FILES, fault=fault, control=control)


@pytest.fixture(scope="module")
def sound():
    return tiny_run(control=True)


def test_sound_run_is_correct(sound):
    assert sound["correct"] is True
    assert sound["failed"] == 0 and sound["attempted"] >= 1
    assert set(sound["metrics"]) == {"tokens_per_s", "itl_p50_ms", "itl_p95_ms", "setup_s"}
    assert list(sound)[-1] == "checks"  # the compared numbers come last


def test_control_fails_where_the_program_passes(sound):
    checks = sound["checks"]
    names = [k for k in checks if not k.startswith("control_")]
    assert all(checks[k]["value"] <= checks[k]["limit"] for k in names)
    assert any(checks[f"control_{k}"]["value"] > checks[k]["limit"] for k in names)


def test_altered_token_is_not_correct():
    def alter(loop):
        step = loop.eng.step

        def bad_step():
            outs = step()
            for o in outs:
                if len(o.new_tokens) and o.uid % 2 == 0:  # every other request
                    o.new_tokens = o.new_tokens.copy()
                    o.new_tokens[-1] = (o.new_tokens[-1] + 1) % TINY_HF["vocab_size"]
            return outs

        loop.eng.step = bad_step

    got = tiny_run(fault=alter)
    assert got["correct"] is False
    assert got["checks"]["logit_gap_max"]["value"] > got["checks"]["logit_gap_max"]["limit"]


def test_prefill_that_leaves_the_kv_pool_unchanged_is_not_correct():
    """A step that returns its state unchanged: every prefill chunk hands
    back the KV pool it was given, so decode attends to no prompt."""
    import jax
    import jax.numpy as jnp

    def stale(loop):
        chunk = loop.eng._chunk

        def bad_chunk(params, cache, *args):
            kept = jax.tree.map(jnp.copy, cache)  # the chunk program donates its pool
            last, _, stats = chunk(params, cache, *args)
            return last, kept, stats

        loop.eng._chunk = bad_chunk

    got = tiny_run(fault=stale)
    assert got["correct"] is False
    assert got["checks"]["logit_gap_max"]["value"] > got["checks"]["logit_gap_max"]["limit"]


def test_traced_run_reads_the_programs_counters_and_spans():
    got = tiny_run(trace=True)
    assert got["correct"] is True
    m = {k: v["value"] for k, v in got["metrics"].items()}
    # no TPU planes in a CPU trace: the device readers read nothing
    assert set(m) == {"decode_rows_mean", "kv_blocks_used_share", "compiles_in_window",
                      "step_host_ms", "ffn_tiles_read_per_union", "attn_blocks_walked_per_live"}
    assert m["step_host_ms"] > 0
    assert m["ffn_tiles_read_per_union"] >= 1.0 and m["attn_blocks_walked_per_live"] >= 1.0
    assert got["metrics"]["ffn_tiles_read_per_union"]["unit"] == "ratio"


NO_ROPE = {k: v for k, v in TINY_HF.items() if k != "rope_theta"}


@pytest.mark.parametrize("change, error", [
    (dict(hf_config=NO_ROPE), None),  # no position encoding key: nothing to compare
    (dict(hf_config=dict(TINY_HF, rope_theta=5e5)), "rope_theta"),
    (dict(hf_config=dict(TINY_HF, attention_head_dim=32)), None),
    (dict(hf_config=dict(TINY_HF, attention_head_dim=64)), "head_dim"),
    (dict(registry_fields={"ffn_act": "silu", "gated_ffn": True, "rope_type": "standard"}), None),
    (dict(registry_fields={"ffn_act": "relu2", "gated_ffn": True}), "ffn_act"),
    (dict(registry_fields={"gated": False}), "names no ModelConfig field"),
    (dict(layers=[["attention", "ffn"]] * 2), None),
    (dict(layers=[["attention", "ffn"]] * 3), "lists 3 layers"),
])
def test_program_model_checks_what_the_file_states(change, error):
    from bench import build

    config = dict(FILES["config"], **change)
    if error is None:
        assert build.program_model(config).cfg.n_layers == 2
    else:
        with pytest.raises(ValueError, match=error):
            build.program_model(config)


def test_a_kind_without_a_work_module_fails_before_setup():
    from bench import harness

    files = dict(FILES, config=dict(FILES["config"], layers=[["attention", "moe"]] * 2))
    with pytest.raises(ValueError, match="no work module"):
        harness.Run("mistral7b.short_long", SEED, require_chip=False, files=files)


class _Out:
    def __init__(self, uid, toks, finished=False):
        self.uid, self.new_tokens, self.finished = uid, np.asarray(toks), finished


class _FakeEngine:
    """Hands every live request one token a step."""

    def __init__(self):
        self.live = {}

    def add_request(self, prompt, max_new):
        uid = len(self.live) + 100
        self.live[uid] = max_new
        return uid

    def step(self):
        outs = []
        for uid, left in list(self.live.items()):
            self.live[uid] = left - 1
            outs.append(_Out(uid, [7], finished=left == 1))
            if left == 1:
                del self.live[uid]
        return outs


def test_gaps_are_positive_across_the_window_opening():
    """Residents decode before the window opens and on into it: their gaps
    straddle the move of the time origin and stay positive."""
    import jax

    from bench import harness, traffic

    loop = harness.Loop(_FakeEngine(), jax, time.perf_counter() - 100.0)
    for _ in range(3):
        loop.add(traffic.Req(0.0, np.arange(5, dtype=np.int32), 12), False)
    for _ in range(4):
        loop.step()
    loop.set_origin(time.perf_counter())
    loop.record_ticks = True
    for _ in range(4):
        time.sleep(0.002)
        loop.step()
    gaps = harness.itl_samples(loop.ticks)
    assert len(gaps) == 12
    assert all(0 < g < 1.0 for g in gaps)


def test_host_log_names_the_longest_ticks_and_the_collectors_passes():
    """The window's host log: ticks ordered longest first, each with the
    tokens it brought; a garbage-collector pass inside the watch is counted."""
    import gc

    from bench import harness

    ticks = [(0.1, [(1, 3, 8, 0.0)]), (0.5, [(1, 11, 8, 0.1), (2, 4, 8, 0.1)]),
             (0.6, [(1, 19, 8, 0.5)])]
    assert harness.longest_ticks(ticks, k=2) == [(0.5, 0.4, 16), (0.1, 0.1, 8)]
    watch = harness.HostWatch()
    gc.collect()
    host = watch.close()
    assert host["gc_passes"] >= 1 and host["gc_s"] >= 0
    assert watch._gc not in gc.callbacks


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "mistral7b.short_long",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr
