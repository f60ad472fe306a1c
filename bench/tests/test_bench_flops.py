"""Work counts: the union of active tiles, live window-capped K/V, useful
operations only; and the table of peaks."""
import json
from pathlib import Path

import pytest

from bench import flops

BENCH = Path(__file__).resolve().parents[1]


def shape(name="mistral7b"):
    return flops.shape(json.loads((BENCH / "configs" / f"{name}.json").read_text()))


def lists_for(rows, L, start_of):
    return [[set(range(start_of(r), start_of(r) + 56)) for _ in range(L)] for r in range(rows)]


def test_union_of_identical_lists_is_read_once():
    s = shape()
    one_f, one_b = flops.ffn_step_work(s, lists_for(1, s["L"], lambda r: 0))
    f16, b16 = flops.ffn_step_work(s, lists_for(16, s["L"], lambda r: 0))
    tile = flops.tile_params(s) * s["bytes"]
    act = s["L"] * 2 * s["d"] * s["bytes"]
    assert b16 - 16 * act == one_b - act == s["L"] * 56 * tile  # 1x the weights
    assert f16 == 16 * one_f  # operations scale with rows


def test_union_of_disjoint_lists_is_read_per_row():
    s = dict(shape(), f=16 * 56 * 128)  # room for 16 disjoint lists
    _, b = flops.ffn_step_work(s, lists_for(16, s["L"], lambda r: 56 * r))
    tile = flops.tile_params(s) * s["bytes"]
    assert b - 16 * s["L"] * 2 * s["d"] * s["bytes"] == 16 * s["L"] * 56 * tile  # 16x


def test_kept_tiles_follow_density():
    assert shape("mistral7b")["n_keep"] == 56  # 0.5 x 14336 / 128
    assert shape("yi9b")["n_keep"] == 43  # 0.5 x 11008 / 128


def test_attention_work_is_capped_at_the_window():
    s = shape()  # sliding window 4096
    f_long, b_long = flops.attn_step_work(s, [10000])
    f_win, b_win = flops.attn_step_work(s, [4095])
    assert (f_long, b_long) == (f_win, b_win)
    f_short, b_short = flops.attn_step_work(s, [99])
    assert b_short == s["L"] * 100 * 2 * s["K"] * s["hd"] * s["bytes"]
    assert f_short == s["L"] * 4 * s["H"] * s["hd"] * 100
    yi = shape("yi9b")  # no window
    assert flops.attn_step_work(yi, [10000])[1] > flops.attn_step_work(yi, [4095])[1]


def test_decode_and_prefill_flops():
    s = shape()
    lin = 2 * (s["L"] * (flops.attn_params(s) + 56 * flops.tile_params(s)) + s["d"] * s["V"])
    assert flops.decode_token_flops(s, 0) == lin + s["L"] * flops.score_flops(s, 1)
    # the prompt runs the dense FFN: per token more than a decoded token
    assert flops.prefill_flops(s, 100) > 100 * flops.decode_token_flops(s, 0) - 100 * 2 * s["d"] * s["V"]


def test_roofline_takes_the_larger_bound():
    peak = flops.peaks("TPU v5 lite")
    assert flops.least_seconds(197e12, 0, peak) == pytest.approx(1.0)
    assert flops.least_seconds(0, 819e9, peak) == pytest.approx(1.0)
    assert flops.least_seconds(197e12, 2 * 819e9, peak) == pytest.approx(2.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        flops.peaks("TPU v9 imaginary")
