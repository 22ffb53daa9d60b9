#!/usr/bin/env python3
"""Readings that set a cell's correctness limit, on the chip, in one
process: for each seed, a short run of the cell at its own load, the
widest gap of the served tokens (the lower reading) and, over the same
prompts and tokens, the widest gap of the token the reference computed
in a lower precision puts first (the control, the upper reading), and
``correct`` as the benchmark decides it with the control's tokens in
the served tokens' place: a sound limit makes every control incorrect.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 \\
        --seconds 15 [--controls int8,fp8]

One JSON line per seed, then the largest sound reading and the
smallest reading of each control.  The benchmark's own runs never run
the control.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main() -> None:
    t0 = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--controls", default="fp8")
    args = ap.parse_args()
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    from chipbench import correctness, run
    run.use_compile_cache()
    cell, cdoc, mix, limits = run.load_cell(args.workload)
    device = run.require_chips(int(cell["chips"]))[0]
    controls = tuple(c for c in args.controls.split(",") if c)
    sound, low = [], {c: [] for c in controls}
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run.run_cell(args.workload, cell, cdoc, mix, limits, seed,
                           args.seconds, False, device, time.perf_counter(),
                           controls=controls)
        r = out["_diag"]["readings"]
        sound.append(r["served_gap_max"])
        decided = {}
        for c in controls:
            low[c].append(r[f"control_{c}_gap_max"])
            decided[f"control_{c}_correct"] = correctness.decide(
                r[f"control_{c}_gap_max"], r["served_tokens"], limits)[0]
        print(json.dumps({"seed": seed, **r, **decided,
                          "correct": out["correct"],
                          "attempted": out["attempted"],
                          "failed": out["failed"]}), flush=True)
        gc.collect()
    print(json.dumps({"workload": args.workload, "sound_max": max(sound),
                      **{f"{c}_min": min(v) for c, v in low.items()},
                      "wall_s": time.perf_counter() - t0}))


if __name__ == "__main__":
    main()
