#!/usr/bin/env python3
"""Readings that the limit of ``correct`` is set from: for each seed, one
run of the cell as the benchmark makes it, with the control beside the
program: the reference in the next precision down (fp8 for the bf16 the
configurations state), read at the same positions of the same requests.
The benchmark's own runs never run the control.

    python3 bench/calibrate.py --workload mistral7b.short_long --seconds 30 --seeds 1,2,3

Prints one JSON line per seed: the program's widest logit gap and the
control's, and ``correct`` as the run's own comparison decides it for each:
the control put in the program's place has to come out not correct.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import build, harness

    _, cfg = build.find_cell(args.workload)
    layers = build.load_json(cfg["file"])["hf_config"]["num_hidden_layers"]
    for seed in [int(x) for x in args.seeds.split(",")]:
        t0 = time.perf_counter()
        kept = {}
        r = harness.run(args.workload, seed, args.seconds, False, t0, control=True,
                        fault=lambda loop: watch_selection(loop, kept, layers))
        checks = r["checks"]
        names = [k for k in checks if not k.startswith("control_")]
        diag = r["diag"]
        ref_kept = diag.pop("kept_f32")
        for u, q in diag["requests"].items():
            # the program's own selection, for the look only: it decides nothing
            q["kept_program_vs_f32"] = harness.blocks_apart(kept[u], ref_kept[u]) \
                if u in kept else None
        diag["requests"] = {str(u): q for u, q in diag["requests"].items()}
        print(json.dumps({"seed": seed, "correct": r["correct"],
                          "control_correct": all(checks[f"control_{k}"]["value"] <= checks[k]["limit"]
                                                 for k in names),
                          "checks": checks, "metrics": r["metrics"], "diag": diag}), flush=True)
        del r
        gc.collect()


def watch_selection(loop, kept: dict, layers: int) -> None:
    """Record each request's active FFN block ids as the engine keeps them
    (the first L x n_keep int32 of its decode grouping key)."""
    eng = loop.eng
    step = eng.step

    def watched():
        outs = step()
        for uid, e in eng.lc.entries.items():
            if uid not in kept and e.glass_key is not None:
                ids = np.frombuffer(e.glass_key, np.int32)
                kept[uid] = ids[: len(ids) // 2].reshape(layers, -1)  # then float32 scales
        return outs

    eng.step = watched


if __name__ == "__main__":
    main()
