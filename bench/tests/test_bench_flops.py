"""Work counts: the union of active tiles, live window-capped K/V, useful
operations only, each counted per layer kind; and the table of peaks."""
import json
import sys
from pathlib import Path

import pytest

import bench.work
from bench import flops
from bench.work import attention, ffn

BENCH = Path(__file__).resolve().parents[1]


def config(name="mistral7b"):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def shape(name="mistral7b"):
    return flops.shape(config(name))


def lists_for(rows, L, start_of):
    return [[set(range(start_of(r), start_of(r) + 56)) for _ in range(L)] for r in range(rows)]


def fixed_lists(s, rows=3):
    k = s["kinds"]["ffn"]
    tiles = k["f"] // k["bs"]
    return [[set((7 * r + 3 * l + j) % tiles for j in range(k["n_keep"])) for l in range(s["L"])]
            for r in range(rows)]


# The counts of the formulas that held every layer as attention and a gated
# FFN, before the counts went per layer kind; the default composition keeps
# them to the bit.
GOLDEN = {
    "mistral7b": {
        "decode_0": 4423155712, "decode_4095": 5496635392, "prefill_100": 699518156800,
        "prefill_5000": 38067094159360, "ffn_step": (8455716864, 3524001792),
        "attn_step": (2173960192, 543490048),
    },
    "yi9b": {
        "decode_0": 3896770560, "decode_4095": 4970250240, "prefill_100": 555496243200,
        "prefill_5000": 30960386048000, "ffn_step": (6492782592, 2869690368),
        "attn_step": (3721920512, 465240064),
    },
}
COUNTS = {
    "decode_0": lambda s: flops.decode_token_flops(s, 0),
    "decode_4095": lambda s: flops.decode_token_flops(s, 4095),
    "prefill_100": lambda s: flops.prefill_flops(s, 100),
    "prefill_5000": lambda s: flops.prefill_flops(s, 5000),
    "ffn_step": lambda s: flops.ffn_step_work(s, fixed_lists(s)),
    "attn_step": lambda s: flops.attn_step_work(s, [0, 99, 4095, 10000]),
}


@pytest.mark.parametrize("name", sorted(GOLDEN))
@pytest.mark.parametrize("count", sorted(COUNTS))
def test_default_composition_keeps_the_golden_counts(name, count):
    assert COUNTS[count](shape(name)) == GOLDEN[name][count]


def test_union_of_identical_lists_is_read_once():
    s = shape()
    one_f, one_b = flops.ffn_step_work(s, lists_for(1, s["L"], lambda r: 0))
    f16, b16 = flops.ffn_step_work(s, lists_for(16, s["L"], lambda r: 0))
    tile = ffn.tile_params(s["kinds"]["ffn"]) * s["bytes"]
    act = s["L"] * 2 * s["d"] * s["bytes"]
    assert b16 - 16 * act == one_b - act == s["L"] * 56 * tile  # 1x the weights
    assert f16 == 16 * one_f  # operations scale with rows


def test_union_of_disjoint_lists_is_read_per_row():
    s = shape()
    _, b = flops.ffn_step_work(s, lists_for(16, s["L"], lambda r: 56 * r))
    tile = ffn.tile_params(s["kinds"]["ffn"]) * s["bytes"]
    assert b - 16 * s["L"] * 2 * s["d"] * s["bytes"] == 16 * s["L"] * 56 * tile  # 16x


@pytest.mark.parametrize("name, n_keep", [("mistral7b", 56),  # 0.5 x 14336 / 128
                                          ("yi9b", 43)])  # 0.5 x 11008 / 128
def test_kept_tiles_follow_density(name, n_keep):
    assert shape(name)["kinds"]["ffn"]["n_keep"] == n_keep


def test_attention_work_is_capped_at_the_window():
    s = shape()  # sliding window 4096
    k = s["kinds"]["attention"]
    f_long, b_long = flops.attn_step_work(s, [10000])
    f_win, b_win = flops.attn_step_work(s, [4095])
    assert (f_long, b_long) == (f_win, b_win)
    f_short, b_short = flops.attn_step_work(s, [99])
    assert b_short == s["L"] * 100 * 2 * k["K"] * k["hd"] * s["bytes"]
    assert f_short == s["L"] * 4 * k["H"] * k["hd"] * 100
    yi = shape("yi9b")  # no window
    assert flops.attn_step_work(yi, [10000])[1] > flops.attn_step_work(yi, [4095])[1]


def test_decode_and_prefill_flops():
    s = shape()
    a, f = s["kinds"]["attention"], s["kinds"]["ffn"]
    lin = 2 * (s["L"] * (attention.weights(a) + 56 * ffn.tile_params(f)) + s["d"] * s["V"])
    assert flops.decode_token_flops(s, 0) == lin + s["L"] * attention.score_flops(a, 1)
    # the prompt runs the dense FFN: per token more than a decoded token
    assert flops.prefill_flops(s, 100) > 100 * flops.decode_token_flops(s, 0) - 100 * 2 * s["d"] * s["V"]


@pytest.mark.parametrize("prompt", [0, 1, 100, 4096, 4097, 10000])
def test_prefill_scores_in_closed_form(prompt):
    k = shape()["kinds"]["attention"]  # window 4096
    loop = sum(attention.score_flops(k, t + 1) for t in range(prompt))
    assert attention.prefill_flops(k, prompt) == 2 * prompt * attention.weights(k) + loop


# ---------------------------------------------------------------------------
# another composition: a cut of Nemotron-H's pattern (layers 40-50,
# ``M-M-M-M-M*-``): 5 state-space mixers, 5 non-gated FFNs, 1 attention
# layer; the mixers counted by a kind module that only this test adds

FAKE_KIND = '''"""A state-space mixer, for the tests: its projections and a state."""


def sizes(config, given):
    return {"d": config["hf_config"]["hidden_size"], "bytes": 2, **given}


def weights(k):
    return 3 * k["d"] * k["d"]


def token_flops(k, context):
    return 2 * weights(k) + 6 * k["state"]


def row_bytes(k, context):
    return 4 * k["state"]


def prefill_flops(k, prompt):
    return prompt * token_flops(k, 0)
'''
PATTERN = "M-M-M-M-M*-"
NEMO_HF = {  # Nemotron-H-47B-Base-8K's config.json widths; a quarter of the vocabulary
    "hidden_size": 8192, "intermediate_size": 30720, "num_attention_heads": 64,
    "num_key_value_heads": 8, "attention_head_dim": 128, "num_hidden_layers": 11,
    "vocab_size": 32768, "sliding_window": None, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": False, "torch_dtype": "bfloat16",
}


@pytest.fixture
def fake_kind(tmp_path, monkeypatch):
    """A kind module ``ssm_fake`` in a directory of its own, searched as a
    part of ``bench/work``: a new kind is a new file only."""
    (tmp_path / "ssm_fake.py").write_text(FAKE_KIND)
    monkeypatch.setattr(bench.work, "__path__", [*bench.work.__path__, str(tmp_path)])
    monkeypatch.delitem(sys.modules, "bench.work.ssm_fake", raising=False)
    yield "ssm_fake"
    sys.modules.pop("bench.work.ssm_fake", None)


def hybrid_config(kind="ssm_fake"):
    kinds = {"M": [kind], "-": ["ffn"], "*": ["attention"]}
    return dict(config(), hf_config=NEMO_HF, layers=[kinds[c] for c in PATTERN],
                kinds={"ffn": {"gated": False}, kind: {"state": 256 * 64 * 256}})


def fake_token(k):
    return 2 * 3 * k["d"] * k["d"] + 6 * k["state"]


def test_hybrid_composition_counts_each_kind_in_its_layers(fake_kind):
    s = flops.shape(hybrid_config())
    assert s["L"] == 11
    assert [len(flops.layers_of(s, k)) for k in ("ssm_fake", "ffn", "attention")] == [5, 5, 1]
    a, f, m = (s["kinds"][k] for k in ("attention", "ffn", "ssm_fake"))
    assert (f["gated"], f["n_keep"], a["hd"], a["window"]) == (False, 120, 128, None)
    d, bs = 8192, 128
    lists = [[set(range(r, r + 120)) for _ in range(5)] for r in range(3)]  # union 122
    fl, b = flops.ffn_step_work(s, lists)
    assert b == 5 * 122 * 2 * d * bs * 2 + 3 * 5 * 2 * d * 2  # 2 matrices a tile, 5 layers
    assert fl == 2 * 3 * 5 * 120 * 2 * d * bs
    fa, ba = flops.attn_step_work(s, [99, 9999])  # one layer, no window
    assert (fa, ba) == (4 * 64 * 128 * (100 + 10000), (100 + 10000) * 2 * 8 * 128 * 2)
    head = 2 * d * 32768
    for c in (0, 4095):
        assert flops.decode_token_flops(s, c) == (
            5 * fake_token(m) + 5 * 2 * 120 * 2 * d * bs + attention.token_flops(a, c) + head)
    assert flops.prefill_flops(s, 100) == (
        5 * 100 * fake_token(m) + 5 * 2 * 100 * 2 * d * 30720
        + attention.prefill_flops(a, 100) + head)


def test_a_window_per_attention_layer():
    c = dict(config(), layers=[["attention", "ffn"]] * 16,
             kinds={"attention": {"per_layer": {"window": [16, None] * 8}}})
    s = flops.shape(c)
    assert [k["window"] for k in flops.layers_of(s, "attention")][:3] == [16, None, 16]
    k = s["kinds"]["attention"]
    f, b = flops.attn_step_work(s, [100])
    assert f == 8 * 4 * k["H"] * k["hd"] * (16 + 101)
    assert b == 8 * (16 + 101) * 2 * k["K"] * k["hd"] * 2


@pytest.mark.parametrize("change, message", [
    (dict(layers=[["attention", "moe"]] * 16), "no work module"),
    (dict(layers=[["attention", "ffn.x"]] * 16), "not a module name"),
    (dict(kinds={"mamba2": {}}), "which no layer holds"),
    (dict(kinds={"attention": {"per_layer": {"window": [16]}}}), "1 values for 16 layers"),
])
def test_a_composition_the_counts_cannot_take_fails_in_shape(change, message):
    with pytest.raises(ValueError, match=message):
        flops.shape(dict(config(), **change))


@pytest.mark.parametrize("layers", [15, 17])
def test_kept_lists_that_miss_an_ffn_layer_raise(layers):
    s = shape()
    with pytest.raises(ValueError, match=f"hold {layers} layers; the configuration has 16"):
        flops.ffn_step_work(s, fixed_lists(s) + [[set(range(56))] * layers])


@pytest.mark.parametrize("flop, nbytes, seconds", [(197e12, 0, 1.0), (0, 819e9, 1.0),
                                                   (197e12, 2 * 819e9, 2.0)])
def test_roofline_takes_the_larger_bound(flop, nbytes, seconds):
    peak = flops.peaks("TPU v5 lite")
    assert flops.least_seconds(flop, nbytes, peak) == pytest.approx(seconds)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        flops.peaks("TPU v9 imaginary")
