"""The traffic generator: deterministic per seed, lengths in range, the same
sizes at the same times for every seed, residual outputs, and a warm-up plan that reaches
every program shape."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import traffic

BENCH = Path(__file__).resolve().parents[1]
MIXES = sorted(p.stem for p in (BENCH / "traffic").glob("*.json"))
CELL = {"rate_per_s": 0.5, "resident": 6, "prerun_s": 8, "max_len": 4224}
BIG = 2**31 + 12345


def mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def flat(s):
    return [(r.due_s, r.prompt.tolist(), r.max_new) for k in ("resident", "prerun", "window")
            for r in s[k]]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule(name):
    a = traffic.schedule(mix(name), CELL, 32000, BIG, 30)
    b = traffic.schedule(mix(name), CELL, 32000, BIG, 30)
    assert flat(a) == flat(b)
    assert flat(a) != flat(traffic.schedule(mix(name), CELL, 32000, BIG + 1, 30))


@pytest.mark.parametrize("name", MIXES)
def test_lengths_in_range_and_quantised(name):
    m = mix(name)
    s = traffic.schedule(m, CELL, 32000, 7, 60)
    for r in s["window"] + s["prerun"] + s["resident"]:
        assert m["prompt"]["min"] <= len(r.prompt) <= m["prompt"]["max"]
        assert r.prompt.min() >= 3 and r.prompt.max() < 32000
    for r in s["window"] + s["prerun"]:
        assert m["output"]["min"] <= r.max_new <= m["output"]["max"]
    # whole tokens, any of them: no length is rounded to a step
    prompts = [len(r.prompt) for r in s["window"] + s["prerun"]]
    assert any(p % 2 for p in prompts) and any(p % 16 for p in prompts)
    assert all(0 < r.due_s < 60 for r in s["window"])
    assert all(-CELL["prerun_s"] < r.due_s < 0 for r in s["prerun"])
    assert [r.due_s for r in s["window"]] == sorted(r.due_s for r in s["window"])


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_offers_the_same_work(name):
    m = mix(name)
    a = traffic.schedule(m, CELL, 32000, 1, 40)
    b = traffic.schedule(m, CELL, 32000, BIG, 40)
    # the same sizes at the same times, in every part of the schedule
    sizes = lambda s: [(r.due_s, len(r.prompt), r.max_new) for k in ("resident", "prerun", "window")
                       for r in s[k]]
    assert sizes(a) == sizes(b)
    assert len(a["window"]) == round(CELL["rate_per_s"] * 40)
    gaps = np.diff([0.0] + [r.due_s for r in a["window"]])
    assert len(set(np.round(gaps, 9))) > 1  # Poisson-like, not evenly spaced


def test_residents_have_residual_outputs():
    m = mix("short_long")
    s = traffic.schedule(m, dict(CELL, resident=16), 32000, 3, 30)
    full = traffic.lengths(m["output"], 16)
    res = sorted(r.max_new for r in s["resident"])
    assert len(res) == 16 and all(1 <= x <= m["output"]["max"] for x in res)
    # a uniform share of each output is left: on the whole about half of it
    assert 0.3 * sum(full) < sum(res) < 0.7 * sum(full)


def test_lengths_are_stratified_quantiles():
    spec = {"median": 100, "sigma": 0.5, "min": 10, "max": 1000}
    xs = traffic.lengths(spec, 101)
    assert list(xs) == sorted(xs) and xs[50] == 100


@pytest.mark.parametrize("name", MIXES)
def test_warm_plan_reaches_every_shape(name):
    m = mix(name)
    eng = {"block_size": 16, "chunk_tokens": 512}
    max_len = m["prompt"]["max"] + m["output"]["max"]
    scheds = [traffic.schedule(m, CELL, 32000, seed, 30) for seed in (5, BIG)]
    prompts, longest = traffic.served_sizes(scheds)
    # every seed serves the same lengths, so one plan warms every seed's run
    assert traffic.served_sizes(scheds[:1]) == (prompts, longest)
    plan = traffic.warm_plan(prompts, longest, max_len, eng)
    bs, nb_max = 16, -(-max_len // 16)

    def chunks(p):
        return {(min(512, p - s), traffic.pow2_bucket(-(-(s + min(512, p - s)) // bs), nb_max))
                for s in range(0, p, 512)}

    want = set().union(*(chunks(p) for p in prompts))
    got = set().union(*(chunks(p) for p, _ in plan))
    assert want <= got
    # every decode width a served context reaches, at horizons 1, 8, 4, 2
    widths = {traffic.pow2_bucket(-(-n // bs), nb_max) for n in range(min(prompts) + 1, longest + 1)}
    decoded = {traffic.pow2_bucket(-(-(p + 1) // bs), nb_max) for p, n in plan if n == 16}
    assert widths == decoded
