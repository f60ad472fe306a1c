"""The reduction from a trace to busy time, program and kernel time, idle
gaps, and the per-layer readers over it, on a small trace made here."""
import importlib.util
import json
from pathlib import Path

import pytest

from bench import flops, harness
from bench import trace as tr

BENCH = Path(__file__).resolve().parents[1]
E = tr.Event
# the kernels' operations as a TPU trace names them: their HLO, with no
# kernel name (both Pallas bodies are called ``_kernel``)
FFN = ('%branch_0_fun.3 = f32[16,1,4096]{2,1,0} custom-call(s32[896]{0} %a, f32[896]{0} %b, '
       'bf16[16,1,4096]{2,1,0} %x, bf16[4096,14336]{1,0} %g, bf16[4096,14336]{1,0} %u, '
       'bf16[14336,4096]{1,0} %d), custom_call_target="tpu_custom_call"')
ATTN = ('%custom-call.4 = bf16[16,1,32,128]{3,2,1,0} custom-call(s32[16,64]{1,0} %t, '
        's32[16]{0} %c, s32[1]{0} %w, bf16[16,1,32,128]{3,2,1,0} %q, '
        'bf16[1537,16,8,128]{3,2,1,0} %k, bf16[1537,16,8,128]{3,2,1,0} %v, '
        's32[32,128]{1,0} %s, s32[1,128]{1,0} %r), custom_call_target="tpu_custom_call"')


def small_trace():
    # two decode programs, one prefill chunk; kernels inside and outside them
    modules = [E("jit_dec(1)", 0.0, 4.0), E("jit_chunk(2)", 5.0, 2.0), E("jit_dec(1)", 8.0, 1.0)]
    ops = [
        E("fusion.1", 0.0, 1.0),
        E(FFN, 1.0, 2.0),
        E(ATTN, 3.0, 1.0),
        E(ATTN, 5.5, 1.0),  # in the chunk program
        E("fusion.2", 6.0, 1.0),  # overlaps the previous op by 0.5
        E(FFN, 8.0, 1.0),
    ]
    host = [E("bench.window", 0.0, 10.0), E("bench.step", 0.0, 4.5), E("bench.step", 7.0, 3.0)]
    return tr.Trace([tr.Device(ops, modules)], host)


def test_busy_is_the_union_of_operations():
    t = small_trace()
    # [0, 4], [5.5, 7], [8, 9]
    assert tr.busy_seconds(t.devices[0].ops, 0.0, 10.0) == pytest.approx(6.5)
    assert tr.busy_seconds(t.devices[0].ops, 2.0, 6.0) == pytest.approx(2.5)


def test_programs_and_kernels():
    d = small_trace().devices[0]
    assert tr.module_seconds(d, r"^jit_dec\b") == pytest.approx(5.0)
    assert tr.module_seconds(d, r"^jit_chunk\b") == pytest.approx(2.0)
    shape = flops.shape(json.loads((BENCH / "configs" / "mistral7b.json").read_text()))
    ffn = kernel_pattern("glass_ffn_roofline", shape)
    attn = kernel_pattern("paged_attention_roofline", shape)
    assert tr.kernel_seconds(d, ffn) == pytest.approx(3.0)
    assert tr.kernel_seconds(d, attn) == pytest.approx(2.0)
    assert tr.kernel_seconds(d, attn, r"^jit_dec\b") == pytest.approx(1.0)


def kernel_pattern(reader: str, shape: dict) -> str:
    """The pattern by which a roofline reader finds its kernel's operations."""
    spec = importlib.util.spec_from_file_location(reader, BENCH / "metrics" / f"{reader}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.kernel(shape)


def test_top_ops_and_idle_gaps():
    t = small_trace()
    d = t.devices[0]
    assert tr.top_ops(d, 2) == [[FFN, 3.0], [ATTN, 2.0]]
    gaps = tr.idle_gaps(d, t.host, 0.0, 10.0)
    # 4-5.5 (half of it inside a step), 7-8 and 9-10 (inside one)
    assert gaps == [["bench.step", pytest.approx(1.5)], ["bench.step", pytest.approx(1.0)],
                    ["bench.step", pytest.approx(1.0)]]
    t.host[1] = E("bench.step", 0.0, 4.0)
    assert tr.idle_gaps(d, t.host, 0.0, 10.0)[0][0] == "outside any host span"


def test_union_merges_overlaps():
    assert tr.union([(3, 4), (0, 2), (1, 3)]) == [(0, 4)]


def ctx(t, lists):
    config = json.loads((BENCH / "configs" / "mistral7b.json").read_text())
    s = flops.shape(config)
    served = {1: harness.Served(prompt=[0] * 100, due=0.0, counted=True, tokens=[0] * 9,
                                first_at=0.1),
              2: harness.Served(prompt=[0] * 50, due=0.0, counted=False, tokens=[0] * 5)}
    # tick 1: uid 1 answered (first token + 1 decoded); tick 2: both decode 4
    ticks = [(0.1, [(1, 0, 1, None)]), (0.2, [(1, 2, 4, 0.1), (2, 1, 4, 0.05)])]
    return harness.Ctx(s, flops.peaks("TPU v5 lite"), 10.0,
                       {"t": 5, "slot_steps": 9, "kv_row_ticks": 5 * 160}, 1600, 0, ticks,
                       served, 100, t, lambda: lists)


def test_readers_on_a_small_trace():
    lists = {u: [set(range(56)) for _ in range(16)] for u in (1, 2)}
    c = ctx(small_trace(), lists)
    read = lambda name: harness.metric_reader(name)(c)
    assert read("decode_rows_mean") == pytest.approx(9 / 5)
    assert read("kv_blocks_used_share") == pytest.approx(10.0)
    assert read("compiles_in_window") == 0
    assert read("device_idle_share") == pytest.approx(35.0)
    # 5 decode steps (1 + 4) over 5 s of decode programs
    assert read("decode_step_ms") == pytest.approx(1000.0)
    assert read("prefill_ms_per_ktok") == pytest.approx(2.0 / 0.1 * 1e3)
    s, peak = c.shape, c.peak
    # identical lists: a step of two rows reads the tiles once
    one = flops.least_seconds(*flops.ffn_step_work(s, [lists[1]]), peak)
    two = flops.least_seconds(*flops.ffn_step_work(s, [lists[1], lists[2]]), peak)
    assert read("glass_ffn_roofline") == pytest.approx(100 * (one + 4 * two) / 3.0)
    assert 0 < read("paged_attention_roofline") < 100
    assert 0 < read("step_mfu") < 100


def test_readers_find_nothing_without_a_trace():
    c = ctx(None, {})
    for name in ("device_idle_share", "decode_step_ms", "glass_ffn_roofline",
                 "paged_attention_roofline", "prefill_ms_per_ktok"):
        assert harness.metric_reader(name)(c) is None


def test_decode_steps_rebuilt_from_ticks():
    from bench import steps

    got = steps.decode_steps(ctx(None, {}))
    # uid 1: prompt 100, its first decoded token (output 1) feeds output 0 at
    # position 100; uid 2: prompt 50 with 1 token before decodes outputs 1..4
    assert got[0] == (1, [(1, 100)])
    assert got[1] == (1, [(1, 101), (2, 50)])
    assert len(got) == 5 and got[-1] == (1, [(1, 104), (2, 53)])
