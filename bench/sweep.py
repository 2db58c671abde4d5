#!/usr/bin/env python3
"""The rate a serving cell's system sustains, found once by a sweep.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds 10 --rates 30 40 50 60

One set-up of the cell (as bench/run.py makes it), then the cell's load at
each offered rate in turn, on the window's queries, each step drained
before the next: a cell whose class OFFERS_RATE.  Prints one JSON line per rate: the rate answered
(as `radius_qps` and `topk_qps` count it), the latency percentiles from due
time to answer, the mean rows of a flush, refusals, and how late the
generator submitted.  No answer is checked.  Serving cells offer a fixed
rate set from this: about four fifths of the sustained rate where the tail
is the metric, above it where the rate answered is.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time

import numpy as np

import run


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()
    spec = run.load_spec()
    wl, cfg, traffic = run.cell_files(spec, args.workload)
    cls = run.cell_class(traffic["kind"])
    if not cls.OFFERS_RATE:
        ap.error(f"{args.workload} offers no request rate")
    run.enable_cache()
    device = run.device_info(int(wl["chips"]))
    from clock import CompileClock
    clock = CompileClock()
    cell = cls(wl, cfg, traffic, args.seed, clock,
               lambda name: contextlib.nullcontext())
    cell.setup()
    print(json.dumps({"setup_s": time.perf_counter() - run.T_START,
                      "device": device, **cell.phases}), flush=True)
    n = 0
    for rate in args.rates:
        cell.load.rate = rate
        obs = run.ObsDelta(cell.obs())
        t0, recs = cell.load.run(args.seconds, first_n=n)
        obs.close()
        n += len(recs)
        ctx = run.Ctx(args.workload, cfg, traffic, device)
        ctx.window_t0 = t0
        ctx.requests[cell.op] = [(r[1], r[2], r[3]) for r in recs]
        flushes = obs.histogram("frontdoor_flush_rows")
        late = np.array(cell.load.late_s) * 1e3
        t1 = max(r[2] for r in recs)
        print(json.dumps({
            "offered_per_s": rate, "requests": len(recs),
            "answered_per_s": ctx.rate(cell.op),
            "p50_ms": ctx.latency_ms(cell.op, 50),
            "p95_ms": ctx.latency_ms(cell.op, 95),
            "flush_rows_mean": (flushes[1] / flushes[0] if flushes
                                and flushes[0] else None),
            "refused": sum(1 for r in recs if not r[6]),
            "failed": sum(1 for r in recs if r[6] and not r[3]),
            "late_p95_ms": float(np.percentile(late, 95)),
            "compiles": clock.count(t0, t1)}), flush=True)
    cell.release()
    return 0


if __name__ == "__main__":
    sys.exit(main())
