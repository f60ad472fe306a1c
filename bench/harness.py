"""One run of one cell: set up, warm, measure a window, check, report.

The window drives the engine only through ``PagedEngine.add_request`` and
``PagedEngine.step``, open loop: each request is added when it is due,
whatever the engine is doing, and every time is taken on the host's clock
when ``step()`` hands the tokens back.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import resource
import shutil
import tempfile
import sys
import time
from contextlib import nullcontext
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import build, flops, traffic
from bench import trace as tr

BENCH = build.BENCH
ROOT = build.ROOT
DRAIN_S = 60.0  # how long past the window's close a due request is waited for


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


@dataclass
class Served:
    """What the host saw of one request."""

    prompt: np.ndarray
    due: float  # seconds from the window's opening
    counted: bool  # due inside the window: a TTFT sample
    tokens: List[int] = field(default_factory=list)
    first_at: Optional[float] = None
    last_at: Optional[float] = None
    finished: bool = False


@dataclass
class Ctx:
    """What a per-layer metric's reader may read after a traced run."""

    shape: dict
    peak: dict
    window_s: float
    counters: Dict[str, int]  # every ``PagedEngine.counters()`` entry's change over the window
    pool_rows: int
    compiles: int
    ticks: list  # per step() in the window: (time, [(uid, tokens before, decoded, prev), ...])
    served: Dict[int, Served]
    prefill_tokens: int  # prompt tokens of requests first answered in the window
    trace: Optional[tr.Trace]  # with the program's ``engine.*`` spans in ``trace.spans``
    lists: Callable[[], Dict[int, list]]  # uid -> per-layer kept block-id sets


def percentile(xs, p: float) -> float:
    """Linear-interpolated percentile, ``p`` in [0, 100]."""
    return float(np.percentile(np.asarray(xs, np.float64), p))


def metric_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Loop:
    """The open loop around one engine: adds requests when due, steps the
    engine while it has work, and records what every step returns."""

    def __init__(self, eng, jax, t_origin: float):
        self.eng = eng
        self.jax = jax
        self.t_origin = t_origin  # host clock at the window's opening
        self.served: Dict[int, Served] = {}
        self.live: set = set()
        self.ticks: list = []
        self.record_ticks = False

    def now(self) -> float:
        return time.perf_counter() - self.t_origin

    def set_origin(self, t_origin: float) -> None:
        """Move the origin of every time to ``t_origin`` on the host clock;
        the times already taken move with it, so gaps stay gaps."""
        shift = self.t_origin - t_origin
        for s in self.served.values():
            s.due += shift
            if s.first_at is not None:
                s.first_at += shift
            if s.last_at is not None:
                s.last_at += shift
        self.t_origin = t_origin

    def add(self, r: traffic.Req, counted: bool) -> int:
        uid = self.eng.add_request(r.prompt, r.max_new)
        self.served[uid] = Served(r.prompt, r.due_s, counted)
        self.live.add(uid)
        return uid

    def step(self) -> None:
        with self.jax.profiler.TraceAnnotation("bench.step"):
            outs = self.eng.step()
        t = self.now()
        tick = []
        for o in outs:
            s = self.served[o.uid]
            new = [int(x) for x in o.new_tokens]
            if new:
                first = not s.tokens
                tick.append((o.uid, len(s.tokens), len(new) - (1 if first else 0), s.last_at))
                if first:
                    s.first_at = t
                s.tokens += new
                s.last_at = t
            if o.finished:
                s.finished = True
                self.live.discard(o.uid)
        if self.record_ticks:
            self.ticks.append((t, tick))

    def until(self, done: Callable[[], bool], limit_s: float) -> None:
        t_end = self.now() + limit_s
        while not done():
            if self.now() > t_end:
                raise RuntimeError(f"engine did not finish its work in {limit_s} s")
            self.step()


class HostWatch:
    """What the host did over the window, for the log: the garbage
    collector's passes and their seconds, the process's CPU seconds, its
    voluntary and involuntary context switches, and the load average."""

    def __init__(self):
        self.gc_s, self.gc_n, self._t = 0.0, 0, None
        self.ru0 = resource.getrusage(resource.RUSAGE_SELF)
        gc.callbacks.append(self._gc)

    def _gc(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.gc_s += time.perf_counter() - self._t
            self.gc_n += 1

    def close(self) -> dict:
        gc.callbacks.remove(self._gc)
        ru = resource.getrusage(resource.RUSAGE_SELF)
        return {"gc_passes": self.gc_n, "gc_s": round(self.gc_s, 4),
                "cpu_user_s": round(ru.ru_utime - self.ru0.ru_utime, 3),
                "cpu_sys_s": round(ru.ru_stime - self.ru0.ru_stime, 3),
                "ctx_switches": ru.ru_nvcsw - self.ru0.ru_nvcsw,
                "ctx_switches_involuntary": ru.ru_nivcsw - self.ru0.ru_nivcsw,
                "loadavg_1m": os.getloadavg()[0]}


def longest_ticks(ticks: list, t_open: float = 0.0, k: int = 5) -> list:
    """The ``k`` longest intervals between consecutive ticks of the window:
    (seconds into the window at its end, its seconds, tokens it brought)."""
    out, prev = [], t_open
    for t, tick in ticks:
        out.append((round(t, 3), round(t - prev, 4), sum(d for _, _, d, _ in tick)))
        prev = t
    return sorted(out, key=lambda x: -x[1])[:k]


def itl_samples(ticks: list) -> list:
    """Gaps between output tokens: a delta of n decoded tokens that arrives
    dt after the same request's previous delta gives n samples of dt / n.
    Tokens that arrive with a request's first token give none."""
    out = []
    for t, tick in ticks:
        for _, before, decoded, prev in tick:
            if before and decoded:
                out += [(t - prev) / decoded] * decoded
    return out


class Run:
    """One cell on this machine's chips: its files, the engine under test
    and the open loop around it.  ``require_chip``, ``files`` (``config``,
    ``mix`` or ``cell`` dicts in place of the cell's files) serve the CPU
    tests."""

    def __init__(self, workload: str, seed: int, require_chip: bool = True,
                 files: Optional[dict] = None):
        self.workload, self.seed = workload, seed
        cell, cfg_entry = build.find_cell(workload)
        files = files or {}
        self.config = files.get("config") or build.load_json(cfg_entry["file"])
        self.mix = files.get("mix") or build.load_json(f"bench/traffic/{cell['traffic']}.json")
        self.cell = files.get("cell") or build.load_json(f"bench/cells/{workload}.json")
        os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp
        import jax

        self.jax = jax
        if require_chip:
            if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
                jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
            # every program, however quick to compile, is kept: a later run loads all
            jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
            jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
            devs = build.open_device(jax, cell["chips"])
        else:
            devs = jax.devices()[: cell["chips"]]
        self.dev = devs[0]
        self.device = {"platform": self.dev.platform, "kind": self.dev.device_kind,
                       "count": len(devs)}
        log(f"device {json.dumps(self.device)} jax {jax.__version__} "
            f"cache {jax.config.jax_compilation_cache_dir}")
        self.meter = build.compile_meter(jax)
        self.shape = flops.shape(self.config)
        self.peak = flops.peaks(self.dev.device_kind) if require_chip else None
        self.vocab = self.config["hf_config"]["vocab_size"]

    def setup(self, t_start: float, scheds: list, fault: Optional[Callable] = None) -> None:
        """Weights, prior, engine, and every program shape that the requests
        of ``scheds`` reach.  ``fault`` is called with the open loop before
        any request."""
        jax, config = self.jax, self.config
        model = build.program_model(config)
        self.params = build.weights(jax, config, self.seed, model)
        self.toks = build.corpus(jax, config, self.seed)
        prior = build.program_prior(jax, model, self.params, config, self.toks)
        self.eng = build.engine(model, self.params, prior, config, self.cell)
        log(f"weights and prior {time.perf_counter() - t_start:.1f}s, {self.meter.line()}")
        self.loop = Loop(self.eng, jax, time.perf_counter())
        if fault is not None:
            fault(self.loop)
        rng = np.random.default_rng(build.seed_words(self.seed)[2])
        prompts, longest = traffic.served_sizes(scheds)
        plan = traffic.warm_plan(prompts, longest, self.cell["max_len"], config["engine"])
        for p, m in plan:
            self.loop.add(traffic.Req(0.0, rng.integers(3, self.vocab, size=p, dtype=np.int32), m),
                          False)
            self.loop.until(lambda: not self.loop.live, 600)
        log(f"warm-up: {len(plan)} requests {plan}, {time.perf_counter() - t_start:.1f}s, "
            f"{self.meter.line()}, programs {self.eng.programs.sizes()}")
        self.loop.served.clear()  # only the window's requests are measured and checked

    def window(self, sched: dict, seconds: float, trace: bool, t_start: float) -> dict:
        """Admit the residents, run the arrivals open loop and measure
        ``seconds`` from the window's opening; then wait for every request
        due in it to be answered (its TTFT counts the wait)."""
        jax, eng, loop = self.jax, self.eng, self.loop
        residents = [loop.add(r, False) for r in sched["resident"]]
        loop.until(lambda: all(loop.served[u].tokens for u in residents), 600)
        arrivals = sched["prerun"] + sched["window"]
        loop.set_origin(time.perf_counter() + self.cell.get("prerun_s", 0))
        loop.ticks = []
        w = {"setup_s": None, "logdir": None}
        span = nullcontext()
        i = 0
        while True:
            t = loop.now()
            if w["setup_s"] is None and t >= 0:
                w["setup_s"] = time.perf_counter() - t_start
                if trace:
                    w["logdir"] = tempfile.mkdtemp(prefix="bench_trace_")
                    jax.profiler.start_trace(w["logdir"])
                    span = jax.profiler.TraceAnnotation("bench.window")
                    span.__enter__()
                compiles0 = self.meter.compiles
                counters0 = eng.counters()
                watch = HostWatch()
                t_open = time.perf_counter()
                loop.record_ticks = True
            if t >= seconds:
                # an arrival due while the last step ran is still sent and waited for
                while i < len(arrivals) and arrivals[i].due_s < seconds:
                    loop.add(arrivals[i], arrivals[i].due_s >= 0)
                    i += 1
                break
            while i < len(arrivals) and arrivals[i].due_s <= t:
                loop.add(arrivals[i], arrivals[i].due_s >= 0)
                i += 1
            if loop.live:
                loop.step()
            else:
                nxt = arrivals[i].due_s if i < len(arrivals) else seconds
                if w["setup_s"] is None:
                    nxt = min(nxt, 0.0)
                time.sleep(max(0.0, min(nxt, seconds) - t))
        w["window_s"] = time.perf_counter() - t_open
        w["host"] = watch.close()
        loop.record_ticks = False
        w["compiles"] = self.meter.compiles - compiles0
        w["counters"] = {k: int(v - counters0[k]) for k, v in eng.counters().items()}
        w["in_flight"] = len(loop.live)
        if trace:
            span.__exit__(None, None, None)
            jax.profiler.stop_trace()
        w["due"] = [u for u, s in loop.served.items() if s.counted]
        try:
            loop.until(lambda: all(loop.served[u].tokens for u in w["due"]), DRAIN_S)
        except RuntimeError as e:
            log(str(e))
        w["ticks"] = loop.ticks
        return w

    def ctx(self, w: dict, served: Dict[int, Served], t_all: Optional[tr.Trace],
            lists: Callable[[], Dict[int, list]]) -> Ctx:
        """What the per-layer readers read of the window ``w``."""
        return Ctx(self.shape, self.peak, w["window_s"], w["counters"],
                   (self.cell["num_blocks"] - 1) * self.config["engine"]["block_size"],
                   w["compiles"], w["ticks"], served,
                   sum(len(s.prompt) for s in served.values()
                       if s.first_at is not None and 0 <= s.first_at < w["window_s"]),
                   t_all, lists)

    def e2e(self, w: dict) -> dict:
        served = self.loop.served
        itl = itl_samples(w["ticks"])
        ttft = [(served[u].first_at - served[u].due) * 1e3 for u in w["due"]
                if served[u].first_at is not None]
        w["decoded"] = sum(d for _, tick in w["ticks"] for _, _, d, _ in tick)
        log(f"window {w['window_s']:.3f}s: {len(w['due'])} arrivals, {len(ttft)} first tokens, "
            f"{len(itl)} gap samples, {w['decoded']} tokens decoded "
            f"({w['decoded'] / w['window_s']:.2f}/s), {w['compiles']} compiles, "
            f"{w['in_flight']} in flight at the close, counters {w['counters']}")
        log(f"host over the window {json.dumps(w['host'])}; longest ticks (end s, s, tokens) "
            f"{longest_ticks(w['ticks'])}")
        if ttft:
            log(f"ttft_ms p50 {percentile(ttft, 50)} p95 {percentile(ttft, 95)} "
                f"max {max(ttft)} (n={len(ttft)})")
        if itl:
            log(f"itl_ms p50 {percentile(itl, 50) * 1e3} p95 {percentile(itl, 95) * 1e3} "
                f"p99 {percentile(itl, 99) * 1e3} (n={len(itl)})")
        return {
            "itl_p50_ms": percentile(itl, 50) * 1e3 if itl else None,
            "itl_p95_ms": percentile(itl, 95) * 1e3 if itl else None,
            "ttft_p95_ms": percentile(ttft, 95) if ttft else None,
            "tokens_per_s": w["decoded"] / w["window_s"],
            "setup_s": w["setup_s"],
        }


def run(workload: str, seed: int, seconds: float, trace: bool, t_start: float,
        require_chip: bool = True, files: Optional[dict] = None,
        fault: Optional[Callable] = None, control: bool = False) -> dict:
    """One run of ``workload``: the result line's object.  ``control`` adds
    the control's reading to the checks (for calibration; it then decides
    nothing)."""
    r = Run(workload, seed, require_chip, files)
    sched = traffic.schedule(r.mix, r.cell, r.vocab, seed, seconds)
    r.setup(t_start, [sched], fault)
    w = r.window(sched, seconds, trace, t_start)
    e2e = r.e2e(w)
    served = r.loop.served
    failed = sum(1 for u in w["due"] if not served[u].tokens)
    mem = build.memory(r.dev)
    log(f"device memory after the window: {mem}")
    peak_bytes = mem["peak_bytes_in_use"]
    jax, config = r.jax, r.config

    # -- free the program's state, then the comparison -----------------------
    del r.eng, r.loop
    gc.collect()
    ref = build.reference_module(config)
    checks = reference_check(jax, ref, config, r.cell, r.params, r.toks, served, seed, control)
    diag = checks.pop("diag", None)
    correct = all(c["value"] <= c["limit"] for k, c in checks.items() if not k.startswith("control"))

    result = {"correct": correct, "attempted": len(w["due"]), "failed": failed}
    spec = build.load_json("BENCHMARK.json")
    names = [m for m in spec["end_to_end"] if workload in m.get("workloads", [workload])]
    pl_names = [m for m in spec["per_layer"] if workload in m.get("workloads", [workload])]
    device = dict(r.device)
    if not trace:
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                             for m in names if e2e.get(m["name"]) is not None}
    else:
        t_all = tr.load(w["logdir"])
        shutil.rmtree(w["logdir"], ignore_errors=True)
        ctx = r.ctx(w, served, t_all,
                    lambda: block_lists(jax, ref, config, r.params, r.toks, served, w["ticks"]))
        got = {}
        for m in pl_names:
            v = metric_reader(m["name"])(ctx)
            if v is not None:
                got[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = got
        bounds = window_bounds(t_all)
        busy = [tr.busy_seconds(d.ops, *bounds) for d in t_all.devices]
        device["busy_s"] = float(np.mean(busy)) if busy else 0.0
        device["window_s"] = w["window_s"]
        if t_all.devices:
            d0 = t_all.devices[0]
            result["breakdown"] = {"device_ops": tr.top_ops(d0),
                                   "idle_gaps": tr.idle_gaps(d0, t_all.host + t_all.spans,
                                                             *bounds)}
    device["memory_peak_bytes"] = peak_bytes
    result["device"] = device
    if diag is not None:
        result["diag"] = diag
    result["checks"] = checks
    for k, c in checks.items():
        log(f"check {k} = {c['value']!r} limit {c['limit']!r}")
    return result


def window_bounds(t_all: tr.Trace) -> tuple:
    w = [h for h in t_all.host if h.name == "bench.window"]
    if w:
        return w[0].start, w[0].end
    ops = [o for d in t_all.devices for o in d.ops]
    return min(o.start for o in ops), max(o.end for o in ops)


def pad_to(n: int) -> int:
    return n if n <= 512 else 512 * -(-n // 512)


def sample(served: Dict[int, Served], k: int, seed: int) -> list:
    """``k`` finished requests drawn from the seed, the longest among them."""
    done = sorted(u for u, s in served.items() if s.finished and len(s.tokens) > 1)
    if not done:
        return []
    longest = max(done, key=lambda u: len(served[u].prompt) + len(served[u].tokens))
    rest = [u for u in done if u != longest]
    rng = np.random.default_rng(build.seed_words(seed)[3])
    pick = list(rng.choice(rest, size=min(k - 1, len(rest)), replace=False)) if rest else []
    return [longest] + [int(u) for u in pick]


def served_rows(jax, ref, config, params, ref_prior, s: Served, pad: int, precision: str):
    """The reference's logits, at ``precision``, at each position that
    scored a served token: the prompt, then the served tokens fed back."""
    toks = np.zeros((pad,), np.int32)
    seq = np.concatenate([s.prompt, np.asarray(s.tokens[:-1], np.int32)])
    toks[: len(seq)] = seq
    P = len(s.prompt)
    lg = ref.served_logits(config["hf_config"], params, ref_prior, jax.numpy.asarray(toks),
                           P, config["glass"], precision)
    return np.asarray(lg[P - 1: P - 1 + len(s.tokens)], np.float64)


def gap_stats(gaps) -> dict:
    """The numbers a cell's check may compare, over per-token logit gaps:
    the widest, the 99th percentile, the mean, and the share of tokens that
    were not the reference's best."""
    g = np.asarray(gaps, np.float64)
    return {"logit_gap_max": float(g.max()), "logit_gap_p99": percentile(g, 99),
            "logit_gap_mean": float(g.mean()), "not_best_share": float((g > 0).mean())}


def reference_check(jax, ref, config, cell_file, params, toks, served, seed,
                    control: bool = False) -> dict:
    """Gaps by which a served token's reference logit lies below the
    reference's best, over a seeded sample of finished requests; each number
    of ``gap_stats`` that the cell's ``check.limits`` names is compared with
    its limit.  With ``control``, also the same numbers for the token that
    the reference in the next precision down (fp8) puts first, at the same
    positions, and under ``diag`` the gap that a token altered where it is
    produced would read (the next token id in its place) and how many kept
    blocks the reference's own selection changes between float32 and bf16
    products."""
    chk = cell_file["check"]
    uids = sample(served, chk["requests"], seed)
    if not uids:
        return {"requests_checked": {"value": 0, "limit": -1}}
    t0 = time.perf_counter()
    ref_prior = ref.prior(config["hf_config"], params, toks, config["glass"]["bos_id"])
    pad = pad_to(cell_file["max_len"])
    if control:
        low_prior = ref.prior(config["hf_config"], params, toks, config["glass"]["bos_id"],
                              precision="fp8")
    gaps, ctrl, altered = [], [], []
    vocab = config["hf_config"]["vocab_size"]
    for u in uids:
        s = served[u]
        rows = served_rows(jax, ref, config, params, ref_prior, s, pad, "f32")
        tok = np.asarray(s.tokens)
        at = np.arange(len(tok))
        best = rows.max(-1)
        gaps.append(best - rows[at, tok])
        if control:
            low = served_rows(jax, ref, config, params, low_prior, s, pad, "fp8").argmax(-1)
            ctrl.append(best - rows[at, low])
            altered.append(best - rows[at, (tok + 1) % vocab])
    prog = gap_stats(np.concatenate(gaps))
    log(f"reference check: {len(uids)} requests, {sum(map(len, gaps))} served tokens, "
        f"{time.perf_counter() - t0:.1f}s; program {prog}")
    out = {k: {"value": prog[k], "limit": lim} for k, lim in chk["limits"].items()}
    if control:
        low = gap_stats(np.concatenate(ctrl))
        out.update({f"control_{k}": {"value": low[k], "limit": lim}
                    for k, lim in chk["limits"].items()})
        altered = np.concatenate(altered)
        kept = kept_by_precision(jax, ref, config, params, toks, served, uids, ref_prior)
        out["diag"] = {"tokens": sum(map(len, gaps)), "program": prog, "control": low,
                       "altered_token_gap": {"min": float(altered.min()),
                                             "median": percentile(altered, 50)},
                       "requests": {u: {"tokens": len(g), "gap_mean": float(g.mean()),
                                        "gap_max": float(g.max()),
                                        "kept_f32_vs_bf16": blocks_apart(kept["f32"][u],
                                                                         kept["bf16"][u])}
                                    for u, g in zip(uids, gaps)},
                       "kept_f32": kept["f32"]}
    return out


def blocks_apart(a, b) -> int:
    """Kept blocks, summed over layers, that one (L, n_keep) list has and
    the other has not."""
    return int(sum(len(set(x) - set(y)) for x, y in zip(np.asarray(a).tolist(),
                                                        np.asarray(b).tolist())))


def kept_by_precision(jax, ref, config, params, toks, served, uids, ref_prior) -> dict:
    """Per checked request, the (L, n_keep) block ids that the reference's
    selection keeps with float32 and with bf16 products."""
    bf_prior = ref.prior(config["hf_config"], params, toks, config["glass"]["bos_id"],
                         precision="bf16")
    S = pad_to(max(len(served[u].prompt) for u in uids))
    t = np.zeros((len(uids), S), np.int32)
    n = np.asarray([len(served[u].prompt) for u in uids], np.int32)
    for j, u in enumerate(uids):
        t[j, : n[j]] = served[u].prompt
    out = {}
    for prec, pr in (("f32", ref_prior), ("bf16", bf_prior)):
        ids = np.asarray(ref.kept_blocks(config["hf_config"], params, pr, t, n,
                                         config["glass"], precision=prec))
        out[prec] = {u: ids[j] for j, u in enumerate(uids)}
    return out


def block_lists(jax, ref, config, params, toks, served, ticks) -> Dict[int, list]:
    """Per request decoded in the window, the per-layer block-id sets its
    prompt keeps, by the reference's selection."""
    uids = sorted({u for _, tick in ticks for u, _, d, _ in tick if d})
    if not uids:
        return {}
    ref_prior = ref.prior(config["hf_config"], params, toks, config["glass"]["bos_id"],
                          precision="bf16")
    S = pad_to(max(len(served[u].prompt) for u in uids))
    out = {}
    for i in range(0, len(uids), 8):
        part = uids[i: i + 8]
        t = np.zeros((8, S), np.int32)  # one shape for every batch: one compile
        n = np.ones((8,), np.int32)
        for j, u in enumerate(part):
            t[j, : len(served[u].prompt)] = served[u].prompt
            n[j] = len(served[u].prompt)
        ids = np.asarray(ref.kept_blocks(config["hf_config"], params, ref_prior, t, n,
                                         config["glass"], precision="bf16"))
        for j, u in enumerate(part):
            out[u] = [set(int(x) for x in ids[j, l]) for l in range(ids.shape[1])]
    return out
