#!/usr/bin/env python3
"""Find the highest arrival rate a cell sustains: one process, one set-up
that warms every rate's shapes, then one window per rate, each from an
empty engine with the cell's residents.  A rate is sustained where no
request waits for a slot at the close and the first tokens keep up
(``ttft_p95_ms`` stays near one prefill); above it the queue grows
through the window.

    python3 bench/sweep.py --workload mistral7b.short_long --seed 5 --seconds 60 --rates 0.2,0.3,0.4

Prints one JSON line per rate on standard output.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True, help="comma-separated requests per second")
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness, traffic

    r = harness.Run(args.workload, args.seed)
    rates = [float(x) for x in args.rates.split(",")]
    cells = [dict(r.cell, rate_per_s=rate) for rate in rates]
    scheds = [traffic.schedule(r.mix, c, r.vocab, args.seed, args.seconds) for c in cells]
    r.setup(T_START, scheds)
    slots = r.config["engine"]["max_slots"]
    for rate, cell, sched in zip(rates, cells, scheds):
        r.cell = cell
        w = r.window(sched, args.seconds, False, T_START)
        e = r.e2e(w)
        c = w["counters"]
        print(json.dumps({"rate_per_s": rate, "in_flight_at_open": len(sched["resident"]),
                          "in_flight_at_close": w["in_flight"],
                          "waiting_at_close": max(0, w["in_flight"] - slots),
                          "arrivals": len(w["due"]),
                          "rows_mean": c["slot_steps"] / max(1, c["t"]),
                          "compiles": w["compiles"], **e}), flush=True)
        for uid in list(r.loop.live):
            r.eng.abort(uid)
        r.loop.live.clear()
        r.loop.served.clear()


if __name__ == "__main__":
    main()
