"""The reduction from a trace to busy time, program and kernel time, idle
gaps, and the per-layer readers over it, on a small trace made here."""
import dataclasses
import importlib.util
import json
from pathlib import Path

import pytest

from bench import flops, harness, steps
from bench import trace as tr
from bench.tests.test_bench_flops import fake_kind, hybrid_config  # noqa: F401

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


def ctx(t, lists, s=None):
    s = s or flops.shape(json.loads((BENCH / "configs" / "mistral7b.json").read_text()))
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


def test_roofline_and_mfu_readers_on_another_composition(fake_kind):  # noqa: F811
    """A cut of 5 state-space mixers, 5 non-gated FFNs and 1 attention
    layer: with each kernel's time equal to its least time, and a window as
    long as the served work takes at the peak, each reader reads 100%."""
    s = flops.shape(hybrid_config())
    lists = {u: [set(range(u, u + 120)) for _ in range(5)] for u in (1, 2)}
    c = ctx(None, lists, s)
    decode = steps.decode_steps(c)
    ffn_s = sum(flops.least_seconds(*flops.ffn_step_work(s, [lists[u] for u, _ in rows]), c.peak)
                for _, rows in decode)
    attn_s = sum(flops.least_seconds(*flops.attn_step_work(s, [x for _, x in rows]), c.peak)
                 for _, rows in decode)
    work = (sum(flops.decode_token_flops(s, x) for _, rows in decode for _, x in rows)
            + flops.prefill_flops(s, 100))  # uid 1's prompt, answered in the window
    ffn_op = ('%glass_ffn_shared.7 = bf16[16,1,8192]{2,1,0} custom-call(s32[240]{0} %a, '
              'bf16[16,1,8192]{2,1,0} %x, bf16[8192,30720]{1,0} %u, bf16[30720,8192]{1,0} %d), '
              'custom_call_target="tpu_custom_call"')
    attn_op = ('%paged_attention.3 = bf16[16,1,64,128]{3,2,1,0} custom-call(s32[16,64]{1,0} %t, '
               'bf16[16,1,64,128]{3,2,1,0} %q, bf16[1537,16,8,128]{3,2,1,0} %k, '
               'bf16[1537,16,8,128]{3,2,1,0} %v), custom_call_target="tpu_custom_call"')
    trace = tr.Trace([tr.Device([E(ffn_op, 0.0, ffn_s), E(attn_op, ffn_s, attn_s)],
                                [E("jit_dec(1)", 0.0, ffn_s + attn_s)])],
                     [E("bench.window", 0.0, 1.0)])
    served = {**c.served, 1: dataclasses.replace(c.served[1], first_at=0.0)}  # in the window
    c = dataclasses.replace(c, trace=trace, served=served,
                            window_s=work / c.peak["bf16_flops_per_s"])
    for name in ("glass_ffn_roofline", "paged_attention_roofline", "step_mfu"):
        assert harness.metric_reader(name)(c) == pytest.approx(100.0), name


@pytest.mark.parametrize("name, kind", [("glass_ffn_roofline", "ffn"),
                                        ("paged_attention_roofline", "attention")])
def test_a_roofline_reads_nothing_where_no_layer_holds_its_kind(name, kind):
    lists = {u: [set(range(56)) for _ in range(16)] for u in (1, 2)}
    c = ctx(small_trace(), lists)
    kinds = {k: v for k, v in c.shape["kinds"].items() if k != kind}
    assert harness.metric_reader(name)(dataclasses.replace(c, shape=dict(c.shape, kinds=kinds))) is None
