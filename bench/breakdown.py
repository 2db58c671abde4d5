#!/usr/bin/env python3
"""One traced run of a cell, broken down by the program's own spans.

    python3 bench/breakdown.py --workload <cell> --seed <n> --seconds <s>

Runs the cell as `bench/run.py --trace 1` does and prints its result line
with one more key, "program": the window's longest device-idle gaps, each
named by the program span whose self time covers most of it
(bench/spans.py), the program spans with the most self time, and the
share of the front door's flush time that its child spans cover.  The
run deletes its trace when it ends; this reads the window its per-layer
readers parsed (`spans.last()`).
"""

from __future__ import annotations

import argparse
import json
import sys

import run
import spans


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    spec = run.load_spec()
    wl, _, _ = run.cell_files(spec, args.workload)
    run.enable_cache()
    try:
        device = run.device_info(int(wl["chips"]))
    except run.NoChip as e:
        print(f"bench: no chip: {e}", file=sys.stderr)
        return run.EXIT_NO_CHIP
    res = run.run_cell(args.workload, args.seed, args.seconds, True,
                       spec=spec, device=device)
    w = spans.last()
    if w is not None:
        res["program"] = {
            "idle_gaps": w.idle_gaps(),
            "self_s": w.self_seconds(),
            "flush_child_cover": w.child_cover("frontdoor.flush")}
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
