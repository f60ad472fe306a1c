#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload mistral7b.short_long --seed 7 --seconds 10 --trace 0

Prints progress and each compared number beside its limit on standard
error, and one JSON object as the last line of standard output.  Exits
non-zero, printing no result, where JAX finds no TPU or fewer chips than
the cell asks for.
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
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import build, harness

    try:
        result = harness.run(args.workload, args.seed, args.seconds, bool(args.trace), T_START)
    except build.NoChip as e:
        sys.exit(f"bench: {e}")
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
