#!/usr/bin/env python3
"""Readings of the program's own spans and counters in one cell: a traced
window as ``bench/run.py --trace 1`` makes it, read for more than the
per-layer metrics give.

    python3 bench/probe.py --workload mistral7b.short_long --seed 7 --seconds 30

Prints one JSON object on standard output: the readers ``step_host_ms``
(mean over the window's ``engine.step`` spans of the time not covered by
their ``engine.decode.wait`` children), ``ffn_tiles_read_per_union``,
``attn_blocks_walked_per_live`` and ``glass_ffn_decode_share``; each span's
count and seconds, each prefill chunk's time, the counters' change over the
window (``PagedEngine.counters``), the window's tokens/s, and the longest
idle gaps of the device, each named by the innermost span that covers most
of it.  It runs no reference check.
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
READERS = ("step_host_ms", "ffn_tiles_read_per_union", "attn_blocks_walked_per_live",
           "glass_ffn_decode_share")


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


def prefills(spans: List[tr.Event], t0: float, t1: float) -> List[list]:
    """Per ``engine.prefill`` span in [t0, t1): its milliseconds, those of
    its ``engine.prefill.finalize``, and its stats (uid, tokens)."""
    fin = [s for s in spans if s.name == "engine.prefill.finalize"]
    return [[1e3 * p.dur, 1e3 * sum(f.dur for f in fin if p.start <= f.start < p.end), p.meta]
            for p in inside(spans, t0, t1) if p.name == "engine.prefill"]


def ratio(counters: dict, num: str, den: str) -> Optional[float]:
    return counters[num] / counters[den] if counters.get(den) else None


def probe(workload: str, seed: int, seconds: float, t_start: float,
          require_chip: bool = True, files: Optional[dict] = None) -> dict:
    """One traced window of ``workload``, set up as ``harness.run`` sets it
    up, and the readings above; ``require_chip`` and ``files`` serve the
    CPU tests as they do ``harness.Run``."""
    from bench import harness, traffic

    r = harness.Run(workload, seed, require_chip, files)
    sched = traffic.schedule(r.mix, r.cell, r.vocab, seed, seconds)
    r.setup(t_start, [sched])
    w = r.window(sched, seconds, True, t_start)
    e2e = r.e2e(w)
    t_all = tr.load(w["logdir"])
    shutil.rmtree(w["logdir"], ignore_errors=True)
    t0, t1 = harness.window_bounds(t_all)
    ctx = r.ctx(w, r.loop.served, t_all, lambda: {})
    out = {
        "workload": workload, "seed": seed, "device": r.device,
        "tokens_per_s": e2e["tokens_per_s"], "itl_p50_ms": e2e["itl_p50_ms"],
        **{name: harness.metric_reader(name)(ctx) for name in READERS},
        "counters": w["counters"],
        "spans": span_table(t_all.spans, t0, t1), "prefills": prefills(t_all.spans, t0, t1),
    }
    if t_all.devices:
        out["idle_gaps"] = tr.idle_gaps(t_all.devices[0], t_all.host + t_all.spans, t0, t1)
    out["_trace"] = t_all
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
