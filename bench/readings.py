#!/usr/bin/env python3
"""Readings that the limits of `correct` are set from: one cell, several
seeds, in one process, each with the program's numbers and the controls'.

    python3 bench/readings.py --workload <cell> --seconds <s> --seeds 1 2 3

Each seed is a whole run of the cell (set-up, window, reference) as
bench/run.py makes it; the result line of each seed also carries, under
"controls", what the cell's controls read on the same sample (its cell
class names them in CONTROLS).  The benchmark's own runs never run the
controls.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import run

def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    spec = run.load_spec()
    wl, _, traffic = run.cell_files(spec, args.workload)
    run.enable_cache()
    device = run.device_info(int(wl["chips"]))
    t_start = run.T_START
    for seed in args.seeds:
        res = run.run_cell(args.workload, seed, args.seconds, False,
                           spec=spec, device=device,
                           controls=run.cell_class(traffic["kind"]).CONTROLS,
                           t_start=t_start)
        print(json.dumps({"seed": seed, **res}), flush=True)
        gc.collect()
        t_start = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
