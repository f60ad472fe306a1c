"""The readings of the program's own spans and counters (``bench/probe.py``
and the readers on them) and the named-kernel reader, on a small trace made
here and on one traced run of a tiny cell on the CPU."""
import dataclasses
import re
import time

import pytest

from bench import harness, probe
from bench import trace as tr
from bench.tests.test_bench_run import FILES, SEED
from bench.tests.test_bench_trace import FFN, ctx, small_trace

E = tr.Event
# the program's spans over ``small_trace``'s two harness steps: a decode
# tick and a tick that runs a prefill chunk, then decodes
SPANS = [
    E("engine.step", 0.1, 4.3), E("engine.admit", 0.1, 0.05),
    E("engine.decode.prepare", 0.2, 0.3), E("engine.decode.dispatch", 0.5, 0.1),
    E("engine.decode.wait", 0.6, 3.3), E("engine.decode.commit", 3.9, 0.4),
    E("engine.outputs", 4.3, 0.1),
    E("engine.step", 7.05, 2.9), E("engine.prefill", 7.05, 0.85),
    E("engine.prefill.finalize", 7.6, 0.3), E("engine.decode.wait", 8.0, 1.0),
    E("engine.outputs", 9.9, 0.05),
]


def test_step_host_time_leaves_out_the_wait():
    # 4.3 - 3.3 and 2.9 - 1.0 seconds of host time
    assert probe.step_host_ms(SPANS, 0.0, 10.0) == pytest.approx(1450.0)
    assert probe.step_host_ms(SPANS, 5.0, 10.0) == pytest.approx(1900.0)
    assert probe.step_host_ms([], 0.0, 10.0) is None


def test_gaps_named_by_the_innermost_program_span():
    t = small_trace()
    got = tr.idle_gaps(t.devices[0], t.host + SPANS, 0.0, 10.0)
    # 4-5.5: no span covers half of it, the harness's step most; 7-8: the
    # prefill chunk covers 0.85 of it, inside its engine.step and
    # bench.step; 9-10: engine.step but none of its phases
    assert got == [["bench.step", pytest.approx(1.5)], ["engine.prefill", pytest.approx(1.0)],
                   ["engine.step", pytest.approx(1.0)]]


@pytest.mark.parametrize("host, first", [
    (small_trace().host, "bench.step"),
    ([E("bench.window", 0.0, 10.0), E("bench.step", 0.0, 4.0), E("bench.step", 7.0, 3.0)],
     "outside any host span"),
])
def test_gaps_without_program_spans_are_named_as_the_harness_names_them(host, first):
    d = small_trace().devices[0]
    assert tr.idle_gaps(d, host, 0.0, 10.0) == [
        [first, pytest.approx(1.5)], ["bench.step", pytest.approx(1.0)],
        ["bench.step", pytest.approx(1.0)]]


def test_span_table():
    table = probe.span_table(SPANS, 0.0, 10.0)
    assert table["engine.step"] == [2, pytest.approx(7.2)]
    assert table["engine.decode.wait"] == [2, pytest.approx(4.3)]
    assert probe.span_table(SPANS, 5.0, 10.0)["engine.outputs"] == [1, pytest.approx(0.05)]
    assert probe.prefills(SPANS, 0.0, 10.0) == [[pytest.approx(850.0), pytest.approx(300.0), ""]]


def test_counter_ratios():
    c = {"ffn_tiles_read": 90, "ffn_tiles_union": 20, "attn_blocks_walked": 64,
         "attn_blocks_live": 0}
    assert probe.ratio(c, "ffn_tiles_read", "ffn_tiles_union") == pytest.approx(4.5)
    assert probe.ratio(c, "attn_blocks_walked", "attn_blocks_live") is None


def named(trace, name):
    """``trace`` with its FFN operations named as a TPU trace names a named
    Pallas call: the custom call's HLO instruction takes the kernel's name."""
    for d in trace.devices:
        d.ops = [E(o.name.replace("%branch_0_fun", f"%{name}"), o.start, o.dur, o.meta)
                 for o in d.ops]
    return trace


@pytest.mark.parametrize("kernel", ["glass_ffn_rowwise", "glass_ffn_shared"])
def test_glass_ffn_decode_share_reads_the_named_kernels(kernel):
    read = harness.metric_reader("glass_ffn_decode_share")
    # FFN 2 s + 1 s inside 5 s of decode programs
    assert read(ctx(named(small_trace(), kernel), {})) == pytest.approx(60.0)


def test_glass_ffn_decode_share_reads_nothing_without_named_kernels():
    read = harness.metric_reader("glass_ffn_decode_share")
    assert read(ctx(small_trace(), {})) is None  # the kernels carry no name
    assert read(ctx(named(small_trace(), "glass_ffn"), {})) is None
    assert read(ctx(None, {})) is None


PROGRAM_COUNTERS = {"t": 5, "slot_steps": 9, "kv_row_ticks": 800, "ffn_tiles_read": 90,
                    "ffn_tiles_union": 90, "attn_blocks_walked": 64, "attn_blocks_live": 16}


@pytest.mark.parametrize("name, value", [("step_host_ms", 1450.0),
                                         ("ffn_tiles_read_per_union", 1.0),
                                         ("attn_blocks_walked_per_live", 4.0)])
def test_program_readers_on_a_small_trace(name, value):
    t = small_trace()
    t.spans = SPANS
    c = dataclasses.replace(ctx(t, {}), counters=PROGRAM_COUNTERS)
    assert harness.metric_reader(name)(c) == pytest.approx(value)


@pytest.mark.parametrize("name", ["step_host_ms", "ffn_tiles_read_per_union",
                                  "attn_blocks_walked_per_live"])
def test_program_readers_find_nothing_to_read(name):
    # no trace, a trace with no program spans, counters that counted nothing
    c = dataclasses.replace(ctx(small_trace(), {}),
                            counters=dict.fromkeys(PROGRAM_COUNTERS, 0))
    assert harness.metric_reader(name)(c) is None
    assert harness.metric_reader(name)(dataclasses.replace(c, trace=None)) is None


@pytest.fixture(scope="module")
def traced():
    return probe.probe("mistral7b.short_long", SEED, 2.0, time.perf_counter(),
                       require_chip=False, files=FILES)


# the phases of one tick, in order, as they nest inside ``engine.step``
TICK = re.compile(r"(engine\.admit )(engine\.prefill )?(engine\.admit )"
                  r"(engine\.decode\.prepare engine\.decode\.dispatch engine\.decode\.wait "
                  r"engine\.decode\.commit )?engine\.outputs $")


def children(parent, spans):
    return [s for s in spans if s is not parent and parent.start <= s.start
            and s.end <= parent.end]


def test_traced_run_spans_nest_in_the_harness_steps(traced):
    trace = traced["_trace"]
    spans = trace.spans
    steps = [s for s in spans if s.name == "engine.step"]
    assert steps
    harness_steps = [h for h in trace.host if h.name == "bench.step"]
    seen_decode = seen_prefill = False
    for st in steps:
        assert any(h.start <= st.start and st.end <= h.end for h in harness_steps)
        inner = children(st, spans)
        top = [s for s in inner if not any(p is not s and p in inner and p.start <= s.start
                                           and s.end <= p.end and p.dur > s.dur for p in inner)]
        assert TICK.fullmatch(" ".join(s.name for s in top) + " "), [s.name for s in top]
        seen_decode |= any(s.name == "engine.decode.wait" for s in top)
        for p in (s for s in top if s.name == "engine.prefill"):
            seen_prefill = True
            assert {s.name for s in children(p, spans)} <= {"engine.prefill.finalize"}
    assert seen_decode and seen_prefill
    assert any(s.name == "engine.prefill.finalize" for s in spans)


def test_traced_run_reads_the_program(traced):
    assert traced["step_host_ms"] is not None and traced["step_host_ms"] > 0
    assert traced["ffn_tiles_read_per_union"] >= 1.0
    assert traced["attn_blocks_walked_per_live"] >= 1.0
    c = traced["counters"]  # every counter of the program, over the window
    assert set(c) == {"t", "slot_steps", "kv_row_ticks", "ffn_tiles_read", "ffn_tiles_union",
                      "attn_blocks_walked", "attn_blocks_live"}
    assert c["t"] > 0 and c["ffn_tiles_union"] > 0
    assert traced["spans"]["engine.step"][0] > 0


def test_program_spans_leave_the_harness_readers_alone(traced):
    # the trace loader keeps the program's spans apart from the harness's,
    # so every reader and the window's bounds read what they read before
    assert {h.name for h in traced["_trace"].host} == {"bench.window", "bench.step"}
    assert all(s.name.startswith("engine.") for s in traced["_trace"].spans)
