#!/usr/bin/env python3
"""Readings of the program's own spans and counters in one cell: a traced
window as ``bench/run.py --trace 1`` makes it, read for what the harness
does not pass to the per-layer readers: the engine's ``engine.*`` host
spans and its decode waste counters (``PagedEngine.counters``).

    python3 bench/probe.py --workload mistral7b.short_long --seed 7 --seconds 30

Prints one JSON object on standard output: ``step_host_ms`` (mean over the
window's ``engine.step`` spans of the time not covered by their
``engine.decode.wait`` children), ``ffn_tiles_read_per_union``,
``attn_blocks_walked_per_live``, ``glass_ffn_decode_share``, each span's
count and seconds, each prefill chunk's time, the counters' change over the
window, the window's tokens/s, and the longest idle gaps of the device,
each named by the innermost span that covers most of it.  It runs no
reference check.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import trace as tr  # noqa: E402

WAIT = "engine.decode.wait"


def load_spans(logdir: str) -> List[tr.Event]:
    """The host events named ``engine.*`` of the trace under ``logdir``."""
    from jax.profiler import ProfileData

    files = sorted(Path(logdir).rglob("*.xplane.pb"))
    pd = ProfileData.from_file(str(files[-1]))
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [e for e in tr._events(line, plane) if e.name.startswith("engine.")]
    return sorted(out, key=lambda e: e.start)


def inside(spans: List[tr.Event], t0: float, t1: float) -> List[tr.Event]:
    return [s for s in spans if t0 <= s.start < t1]


def step_host_ms(spans: List[tr.Event], t0: float, t1: float) -> Optional[float]:
    """Mean over the ``engine.step`` spans that start in [t0, t1) of the
    milliseconds not covered by the ``engine.decode.wait`` spans inside
    them: the host's own time per tick."""
    steps = [s for s in inside(spans, t0, t1) if s.name == "engine.step"]
    if not steps:
        return None
    waits = [s for s in spans if s.name == WAIT]
    host = 0.0
    for st in steps:
        covered = tr.union((max(w.start, st.start), min(w.end, st.end))
                           for w in waits if w.end > st.start and w.start < st.end)
        host += st.dur - sum(e - s for s, e in covered)
    return 1e3 * host / len(steps)


def span_table(spans: List[tr.Event], t0: float, t1: float) -> Dict[str, list]:
    """[count, seconds] of each span name in [t0, t1)."""
    out: Dict[str, list] = {}
    for s in inside(spans, t0, t1):
        c = out.setdefault(s.name, [0, 0.0])
        c[0] += 1
        c[1] += s.dur
    return dict(sorted(out.items()))


def name_gap(s: float, e: float, host: List[tr.Event]) -> str:
    """The innermost host span (the window's own aside) that covers more
    than half of [s, e); where none does, the name whose spans cover most
    of it, as ``trace.idle_gaps`` names a gap."""
    cover: dict = {}
    inner = None
    for h in host:
        ov = min(h.end, e) - max(h.start, s)
        if h.name == "bench.window" or ov <= 0:
            continue
        cover[h.name] = cover.get(h.name, 0.0) + ov
        if 2 * ov > e - s and (inner is None or h.dur < inner.dur):
            inner = h
    if inner is not None:
        return inner.name
    return max(cover, key=cover.get) if cover else "outside any host span"


def idle_gaps(dev: tr.Device, host: List[tr.Event], t0: float, t1: float,
              n: int = 10) -> List[list]:
    """The ``n`` longest gaps in [t0, t1) with no device operation, each
    named by ``name_gap`` over ``host`` (the harness's and the program's
    spans), [name, seconds]."""
    busy = tr.union((max(o.start, t0), min(o.end, t1)) for o in dev.ops
                    if o.end > t0 and o.start < t1)
    edges = [t0] + [x for iv in busy for x in iv] + [t1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    return [[name_gap(s, e, host), e - s]
            for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]]


def prefills(spans: List[tr.Event], t0: float, t1: float) -> List[list]:
    """Per ``engine.prefill`` span in [t0, t1): its milliseconds, those of
    its ``engine.prefill.finalize``, and its stats (uid, tokens)."""
    fin = [s for s in spans if s.name == "engine.prefill.finalize"]
    return [[1e3 * p.dur, 1e3 * sum(f.dur for f in fin if p.start <= f.start < p.end), p.meta]
            for p in inside(spans, t0, t1) if p.name == "engine.prefill"]


def ratio(counters: dict, num: str, den: str) -> Optional[float]:
    return counters[num] / counters[den] if counters.get(den) else None


def watch_counters(loop) -> dict:
    """``eng.counters()`` before the window's first step and after its
    last, the two points between which the harness diffs its own."""
    got: dict = {}
    step = loop.step

    def watched():
        if loop.record_ticks and "open" not in got:
            got["open"] = loop.eng.counters()
        step()
        if loop.record_ticks:
            got["close"] = loop.eng.counters()

    loop.step = watched
    return got


def probe(workload: str, seed: int, seconds: float, t_start: float,
          require_chip: bool = True, files: Optional[dict] = None) -> dict:
    """One traced window of ``workload``, set up as ``harness.run`` sets it
    up, and the readings above; ``require_chip`` and ``files`` serve the
    CPU tests as they do ``harness.Run``."""
    from bench import harness, traffic

    r = harness.Run(workload, seed, require_chip, files)
    sched = traffic.schedule(r.mix, r.cell, r.vocab, seed, seconds)
    r.setup(t_start, [sched])
    got = watch_counters(r.loop)
    w = r.window(sched, seconds, True, t_start)
    e2e = r.e2e(w)
    t_all = tr.load(w["logdir"])
    spans = load_spans(w["logdir"])
    shutil.rmtree(w["logdir"], ignore_errors=True)
    t0, t1 = harness.window_bounds(t_all)
    counters = {k: got["close"][k] - got["open"][k] for k in got["close"]}
    ctx = harness.Ctx(r.shape, r.peak, w["window_s"], w["counters"], 0, w["compiles"],
                      w["ticks"], r.loop.served, 0, t_all, lambda: {})
    out = {
        "workload": workload, "seed": seed, "device": r.device,
        "tokens_per_s": e2e["tokens_per_s"], "itl_p50_ms": e2e["itl_p50_ms"],
        "step_host_ms": step_host_ms(spans, t0, t1),
        "ffn_tiles_read_per_union": ratio(counters, "ffn_tiles_read", "ffn_tiles_union"),
        "attn_blocks_walked_per_live": ratio(counters, "attn_blocks_walked", "attn_blocks_live"),
        "glass_ffn_decode_share": harness.metric_reader("glass_ffn_decode_share")(ctx),
        "counters": counters, "harness_counters": w["counters"],
        "spans": span_table(spans, t0, t1), "prefills": prefills(spans, t0, t1),
    }
    if t_all.devices:
        out["idle_gaps"] = idle_gaps(t_all.devices[0], t_all.host + spans, t0, t1)
    out["_trace"], out["_spans"] = t_all, spans
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    from bench import build

    try:
        out = probe(args.workload, args.seed, args.seconds, T_START)
    except build.NoChip as e:
        sys.exit(f"probe: {e}")
    print(json.dumps({k: v for k, v in out.items() if not k.startswith("_")}), flush=True)


if __name__ == "__main__":
    main()
