"""One general generator for every traffic mix.

A mix file (``bench/traffic/<mix>.json``) gives the shape of the traffic:
clipped lognormal prompt and output lengths, any whole number of tokens.
A cell file (``bench/cells/<cell>.json``) gives what belongs
to the cell: the fixed arrival rate, the requests resident when the window
opens, and the seconds of arrivals run before it.

Every seed gets the same work: lengths are the distribution's quantiles at
(i + 1/2) / n and gaps the exponential's, shuffled once by a fixed
``LAYOUT`` seed, so Poisson-like bursts fall at the same times in every run.
The run's seed draws the token ids only.  A window is shorter than a
request's life, so an order drawn from the run's seed would decide which
work falls inside it, and two seeds would time different work.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np

_NORMAL = NormalDist()
LAYOUT = 0  # the seed of every schedule's order of sizes and gaps


@dataclass(frozen=True)
class Req:
    due_s: float  # seconds from the window's opening (negative before it)
    prompt: np.ndarray  # int32 token ids
    max_new: int


def lengths(spec: dict, n: int) -> np.ndarray:
    """The ``n`` stratified quantiles of a clipped lognormal, ascending,
    rounded up to whole tokens."""
    qs = [(i + 0.5) / n for i in range(n)]
    raw = [spec["median"] * math.exp(spec["sigma"] * _NORMAL.inv_cdf(u)) for u in qs]
    out = [min(spec["max"], max(spec["min"], math.ceil(x))) for x in raw]
    return np.asarray(out, np.int64)


def gaps(rate: float, n: int, seconds: float) -> np.ndarray:
    """``n`` exponential quantiles at rate ``rate``, scaled to fill
    ``seconds`` less half a mean gap (so the last arrival falls inside)."""
    g = np.asarray([-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)])
    return g * (seconds - 0.5 / rate) / g.sum()


def _requests(order, tokens, mix: dict, vocab: int, n: int, dues, residual: bool):
    """``n`` requests due at ``dues``: sizes in the order that ``order`` (a
    generator) draws, token ids from ``tokens``."""
    p = order.permutation(lengths(mix["prompt"], n))
    o = order.permutation(lengths(mix["output"], n))
    if residual:
        # a request caught in flight has a uniform share of its output left
        frac = order.permutation([(i + 0.5) / n for i in range(n)])
        o = np.maximum(1, np.ceil(o * frac)).astype(np.int64)
    return [Req(float(d), tokens.integers(3, vocab, size=int(pl), dtype=np.int32), int(m))
            for d, pl, m in zip(dues, p, o)]


def schedule(mix: dict, cell: dict, vocab: int, seed: int, seconds: float) -> dict:
    """The run's requests: ``resident`` (admitted and prefilled before the
    window, residual outputs), ``prerun`` (arrivals in the ``prerun_s``
    seconds before the window) and ``window`` (arrivals inside it)."""
    orders = [np.random.default_rng(s) for s in np.random.SeedSequence(LAYOUT).spawn(3)]
    tokens = [np.random.default_rng(s) for s in np.random.SeedSequence(seed).spawn(3)]
    out = {}
    rate = cell["rate_per_s"]
    k = cell.get("resident", 0)
    out["resident"] = _requests(orders[0], tokens[0], mix, vocab, k, [0.0] * k,
                                residual=True) if k else []
    pre = cell.get("prerun_s", 0)
    n_pre = round(rate * pre)
    if n_pre:
        dues = np.cumsum(orders[1].permutation(gaps(rate, n_pre, pre))) - pre
        out["prerun"] = _requests(orders[1], tokens[1], mix, vocab, n_pre, dues, residual=False)
    else:
        out["prerun"] = []
    n = max(1, round(rate * seconds))
    dues = np.cumsum(orders[2].permutation(gaps(rate, n, seconds)))
    out["window"] = _requests(orders[2], tokens[2], mix, vocab, n, dues, residual=False)
    return out


def pow2_bucket(n: int, cap: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return min(p, cap)


def served_sizes(scheds: list) -> tuple:
    """(distinct prompt lengths, longest prompt plus longest output) of the
    requests of one or more schedules: the same for every seed, whatever
    prompt the seed pairs with whatever output."""
    reqs = [r for s in scheds for k in ("resident", "prerun", "window") for r in s[k]]
    return (sorted({len(r.prompt) for r in reqs}),
            max(len(r.prompt) for r in reqs) + max(r.max_new for r in reqs))


def warm_plan(prompts: list, longest: int, max_len: int, engine: dict) -> list:
    """Prompt lengths and ``max_new`` of the requests that, served before the
    window, compile every program shape that requests of these ``prompts``
    lengths, whose prompt and output reach at most ``longest`` tokens, can
    reach: each prefill chunk (tokens, block-table width) of every prompt
    length, and each decode step (horizon 1, 2, 4, 8; block-table width) of
    every context length.  It models the engine's bucketing: chunks of
    ``chunk_tokens``, block-table widths in powers of two up to
    ``max_len``'s, decode horizons in powers of two up to ``decode_chunk``.
    A request of ``max_new`` 16 decodes at horizons 1, 8, 4 and 2."""
    bs, chunk = engine["block_size"], engine["chunk_tokens"]
    nb_max = -(-max_len // bs)

    def chunks(p):
        return {(min(chunk, p - s), pow2_bucket(-(-(s + min(chunk, p - s)) // bs), nb_max))
                for s in range(0, p, chunk)}

    def bucket(n_rows):
        return pow2_bucket(-(-n_rows // bs), nb_max)

    need = set().union(*(chunks(p) for p in prompts))
    plan = []
    for b in sorted({bucket(n) for n in range(min(prompts) + 1, longest + 1)}):
        # a prompt whose 16 decoded tokens stay inside bucket b: one the run
        # serves if any, else one whose chunks it compiles anyway
        fits = [p for p in range(1, max_len - 15) if bucket(p + 1) == b and bucket(p + 15) == b]
        if not fits:
            continue
        p = next((x for x in fits if x in prompts), None) or next(
            (x for x in fits if chunks(x) <= need), fits[0])
        plan.append((p, 16))
        need -= chunks(p)
    while need:
        p = max(prompts, key=lambda x: len(need & chunks(x)))
        plan.append((p, 1))
        need -= chunks(p)
    return plan
