#!/usr/bin/env python3
"""The benchmark: one cell, one run, one result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (an entry of BENCHMARK.json's `workloads`) names a configuration,
bench/configs/<config>.json, and a traffic mix, bench/traffic/<mix>.json.
The mix's `kind` names the class that runs the cell: `Cell` of the module
bench/cell_<kind>.py, found by that name alone.  Every metric of
BENCHMARK.json is read by bench/metrics/<metric>.py, whose `read(ctx)`
returns a number or None when the cell has nothing for it to read.  So a
new deployment enters as new files: a configuration, a traffic mix, a
kind's module where no kind fits, and readers.

The cell protocol (bench/readings.py, bench/sweep.py and bench/tests use
it too):

  Cell(workload, cfg, traffic, seed, clock, span)  the workload's entry
      of BENCHMARK.json (its `chips` too), the configuration, the mix, the
      run's seed, a CompileClock and the span factory (bench.* spans)
  setup()            everything before the window: data, state, warm-up
  obs()              the program's obs registry (`snapshot()`)
  window(ctx, seconds) -> (t0, t1, attempted, failed)  the measured
      window; fills what its readers read in `ctx` (Ctx.requests,
      Ctx.ingest)
  release()          frees the program's state before the reference runs
  check(controls) -> {"program": {name: (value, limit)}, control: {...}}
  CONTROLS           names of the controls `check` can read
  FAULTS             {name: planter(monkeypatch)}: the faults the cell can
                     have, planted under the harness by its self-tests
  tiny(cfg, traffic) -> (cfg, traffic)  (classmethod) the cell shrunk for
                     the CPU tests
  OFFERS_RATE        whether `cell.load.rate` sets an offered rate that
                     bench/sweep.py may sweep
  phases, n_checked  (optional) set-up and check figures for the log

A run: set-up (corpus drawn on the device from --seed, sketched and stored
through the program, every shape warmed from the persistent compile cache
at bench/.jax_cache), the measured window of --seconds, then the check
against the plain reference (bench/reference.py) once the program's state
is freed.  With --trace 0 the result carries the cell's end-to-end
metrics; with --trace 1 the window runs under the profiler and the result
carries its per-layer metrics, device busy time and a breakdown.

The run refuses to start unless JAX's first device is a TPU and there are
as many as the cell asks for; it then exits 3 and prints no result.  The
numbers compared for `correct` are printed, each with its limit, as the
last lines of standard error and under `checks`, the last key of the
result line.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = BENCH / ".jax_cache"
TRACE_DIR = BENCH / ".trace"
METRICS = BENCH / "metrics"
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

EXIT_NO_CHIP = 3


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# the benchmark's data
# ---------------------------------------------------------------------------


def load_spec(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def workload_entry(spec: dict, name: str) -> dict:
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    return cells[name]


def cell_files(spec: dict, name: str) -> tuple[dict, dict, dict]:
    """(workload entry, configuration, traffic mix) of cell `name`."""
    wl = workload_entry(spec, name)
    conf = {c["name"]: c for c in spec["configs"]}[wl["config"]]
    cfg = json.loads((ROOT / conf["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{wl['traffic']}.json")
                         .read_text())
    return wl, cfg, traffic


def cell_metrics(spec: dict, name: str, trace: bool) -> list[dict]:
    """The metrics cell `name` reports: its end-to-end metrics, or with
    `trace` its per-layer metrics (a metric without `workloads` belongs to
    every cell that reports the end-to-end metric it moves)."""
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if name in m["workloads"] or ("workloads" not in m
                                          and m["moves"] in moved)]


def load_reader(metric: str):
    path = METRICS / f"{metric}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# what readers read
# ---------------------------------------------------------------------------


class ObsDelta:
    """Counters and histogram (count, sum) of the program's registry
    (`snapshot()`), as they moved between the window's start and end."""

    def __init__(self, registry):
        self.registry = registry
        self.before = registry.snapshot()
        self.after = None

    def close(self) -> None:
        self.after = self.registry.snapshot()

    @staticmethod
    def _get(snap: dict, name: str, labels: dict):
        v = snap.get(name)
        if labels and v is not None:
            v = v.get(",".join(f"{k}={x}" for k, x in sorted(labels.items())))
        return v

    def counter(self, name: str, **labels):
        b = self._get(self.after, name, labels)
        if b is None:
            return None
        return b - (self._get(self.before, name, labels) or 0)

    def histogram(self, name: str, **labels):
        """(count, sum) over the window, or None."""
        b = self._get(self.after, name, labels)
        if b is None:
            return None
        a = self._get(self.before, name, labels) or {"count": 0, "sum": 0.0}
        return b["count"] - a["count"], b["sum"] - a["sum"]


class Ctx:
    """Everything a metric reader may read about one run."""

    def __init__(self, workload: str, cfg: dict, traffic: dict, device: dict):
        self.workload = workload
        self.cfg = cfg
        self.traffic = traffic
        self.device = device
        self.setup_s = None
        self.requests: dict = {}  # op -> [(t_due, t_answer, ok)]
        self.window_t0 = None
        self.ingest = None  # (rows acknowledged, seconds)
        self.obs: ObsDelta | None = None
        self.trace = None  # tracereduce.Reduced

    def rate(self, op: str):
        """Requests of `op` due in the window and answered, over the time
        from the window's start to the last of their answers."""
        recs = [r for r in self.requests.get(op, ()) if r[2]]
        if not recs:
            return None
        return len(recs) / (max(r[1] for r in recs) - self.window_t0)

    def latency_ms(self, op: str, pct: float):
        """Percentile of the latency, due time to answer, of the requests of
        `op` due in the window and answered."""
        recs = [r for r in self.requests.get(op, ()) if r[2]]
        if not recs:
            return None
        lat = np.array([r[1] - r[0] for r in recs]) * 1e3
        return float(np.percentile(lat, pct))


# ---------------------------------------------------------------------------
# a run
# ---------------------------------------------------------------------------


def enable_cache() -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info(chips: int) -> dict:
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise NoChip(f"JAX's first device is {dev.platform} "
                     f"({dev.device_kind}), not a TPU")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX sees "
                     f"{len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def memory_peak(chips: int) -> int:
    import jax
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in jax.devices()[:chips]]
    return max(peaks)


KIND = re.compile(r"[a-z][a-z0-9_]*")


def cell_class(kind: str):
    """`Cell` of the module cell_<kind>, found on the import path (bench/
    first)."""
    if not KIND.fullmatch(kind):
        raise ValueError(f"traffic kind {kind!r} is not a module name")
    return importlib.import_module(f"cell_{kind}").Cell


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             spec: dict, device: dict, cfg: dict | None = None,
             traffic: dict | None = None, controls: tuple = (),
             t_start: float = T_START, log=None) -> dict:
    """One run of a cell; returns the result object.  `cfg`/`traffic`
    replace the cell's files (small sizes for tests); `controls` names
    controls (cell.check) whose numbers the result also carries, under
    "controls", each with the `correct` it would give."""
    import jax
    from clock import CompileClock

    log = log or (lambda *a: print(*a, file=sys.stderr, flush=True))
    wl = workload_entry(spec, workload)
    if cfg is None or traffic is None:
        _, cfg0, traffic0 = cell_files(spec, workload)
        cfg, traffic = cfg or cfg0, traffic or traffic0
    chips = int(wl["chips"])
    metrics = cell_metrics(spec, workload, trace)
    ctx = Ctx(workload, cfg, traffic, device)
    clock = CompileClock()
    span = (jax.profiler.TraceAnnotation if trace
            else (lambda name: contextlib.nullcontext()))

    cell = cell_class(traffic["kind"])(wl, cfg, traffic, seed, clock, span)
    cell.setup()
    ctx.obs = ObsDelta(cell.obs())
    ctx.setup_s = time.perf_counter() - t_start
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    with span("bench.window"):
        t0, window_end, attempted, failed = cell.window(ctx, seconds)
    ctx.window_t0 = t0
    if trace:
        jax.profiler.stop_trace()
    ctx.obs.close()
    window_compiles = clock.count(t0, window_end)
    window_compile_s = clock.seconds(t0, window_end)
    peak = memory_peak(chips)
    if trace:
        import tracereduce
        ctx.trace = tracereduce.reduce_dir(str(TRACE_DIR), n_devices=chips)

    values = {}
    for m in metrics:
        v = load_reader(m["name"])(ctx)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}

    t_ref = time.perf_counter()
    cell.release()
    readings = cell.check(controls=tuple(controls))
    checks = readings["program"]
    ref_s = time.perf_counter() - t_ref
    correct = all(v <= lim for v, lim in checks.values())

    log(f"run: workload={workload} seed={seed} setup_s={ctx.setup_s:.3f} "
        f"window_compiles={window_compiles} "
        f"window_compile_s={window_compile_s:.3f} reference_s={ref_s:.3f} "
        f"checked={getattr(cell, 'n_checked', 0)} attempted={attempted} "
        f"failed={failed} memory_peak_bytes={peak} "
        + " ".join(f"{k}={v:.3f}" if isinstance(v, float) else f"{k}={v}"
                   for k, v in getattr(cell, "phases", {}).items()))
    for name, (v, lim) in checks.items():
        log(f"check {name}: {v!r} limit {lim!r}")

    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(failed), "metrics": values,
              "device": dict(device, memory_peak_bytes=peak)}
    if trace:
        t = ctx.trace
        result["device"].update(busy_s=t.busy_s, window_s=t.window_s)
        result["breakdown"] = {"device_ops": [[k, v] for k, v in t.top_ops()],
                               "idle_gaps": t.idle_gaps}
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    if controls:
        result["controls"] = {
            name: {"correct": all(v <= lim for v, lim in nums.values()),
                   **{k: {"value": v, "limit": lim}
                      for k, (v, lim) in nums.items()}}
            for name, nums in readings.items() if name != "program"}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be >= 0")
    spec = load_spec()
    wl, _, _ = cell_files(spec, args.workload)
    try:
        import repro  # noqa: F401  (the system under test must be here)
    except ImportError as e:
        print(f"bench: the program is missing: {e}", file=sys.stderr)
        return 2
    enable_cache()
    try:
        device = device_info(int(wl["chips"]))
    except NoChip as e:
        print(f"bench: no chip: {e}", file=sys.stderr)
        return EXIT_NO_CHIP
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), spec=spec, device=device)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
