#!/usr/bin/env python3
"""Finds a cell's knee on the chip: the highest fixed rate the system
sustains, where every request due in the window is served before the
drain ends and at least half of them meet the traffic's TTFT limit (a
backlog that grows through the window pushes the median past it).  The
gap limit is not part of it: the step time sets the gap whatever the
rate, and each line reports the attainment of both limits beside it.

    python3 chipbench/sweep.py --workload <cell> --rates 1,2,3 \\
        --seconds 20 --seed 5 [--controls fp8]

One process runs the cell's traffic at each rate in turn, in the order
given (set-up paid once; the engine is rebuilt for each rate, its
programs stay loaded), the i-th rate with seed ``seed + i``, prints one
JSON line per rate and stops after the first rate that misses.  Each
control named is read at every rate, with ``correct`` decided as the
benchmark decides it, the control's tokens in the served tokens'
place.  The cell's traffic file then records the rate chosen, about
0.8 of the knee, and these readings.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
SUSTAINED_TTFT_ATTAINMENT = 50.0


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--controls", default="")
    args = ap.parse_args()
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    from chipbench import correctness, run
    run.use_compile_cache()
    cell, cdoc, mix, limits = run.load_cell(args.workload)
    device = run.require_chips(int(cell["chips"]))[0]
    controls = tuple(c for c in args.controls.split(",") if c)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        out = run.run_cell(args.workload, cell, cdoc, dict(mix, rate_rps=rate),
                           limits, args.seed + i, args.seconds, False, device,
                           time.perf_counter(), controls=controls)
        s, r = out["_diag"]["summary"], out["_diag"]["readings"]
        meets = (s["failed"] == 0 and s.get("ttft_attainment", 0.0)
                 >= SUSTAINED_TTFT_ATTAINMENT)
        line = {"rate_rps": rate, "seed": args.seed + i, **s,
                "setup_s": out["_diag"]["setup_s"], "correct": out["correct"],
                "gap_max": r["served_gap_max"], "meets": meets}
        for c in controls:
            line[f"control_{c}_gap_max"] = r[f"control_{c}_gap_max"]
            line[f"control_{c}_correct"] = correctness.decide(
                r[f"control_{c}_gap_max"], r["served_tokens"], limits)[0]
        print(json.dumps(line), flush=True)
        if not meets:
            break
        gc.collect()


if __name__ == "__main__":
    main()
