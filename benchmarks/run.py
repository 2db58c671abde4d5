"""Benchmark driver: one function per paper table/figure + kernel/system
benches.  Prints ``name,us_per_call,derived`` CSV; writes a JSON summary to
experiments/bench_summary.json and appends the kernel/dedup/index suites to
the perf trajectory in BENCH_kernels.json (repo root, committed — one
timestamped entry per run, so regressions across PRs stay visible in the
file itself, not just in its git history).

``--suites a,b,c`` filters by substring (e.g. ``--suites kernel,dedup``
re-records just those suites).  ``--smoke`` runs the trajectory suites at
tiny sizes as a wiring check — failures still abort loudly, but nothing is
written to BENCH_kernels.json (smoke numbers are not perf claims).
``--device-count N`` runs the benchmarks on N virtual CPU devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=N``; it needs
``JAX_PLATFORMS=cpu``), so many-device benches (index_sharded) are
reproducible from one flag on any single-host box.  JAX keeps its compiled programs in the persistent compile cache
(`repro.runtime.compile_cache`)."""

from __future__ import annotations

import json
import os
import sys
import time
import traceback

# suites whose results feed the BENCH_kernels.json perf trajectory
_TRAJECTORY_SUITES = ("kernel_packed", "kernel_cham", "kernel_sketch",
                      "kernel_sparse_sketch", "dedup", "dedup_streaming",
                      "index", "index_mixed", "index_migrate",
                      "index_sharded", "index_bulk", "cluster", "serve")

# tiny-size overrides for --smoke: exercise every trajectory suite's wiring
# (sketch -> kernels -> engine -> index) in seconds on a bare CPU runner
_SMOKE_KWARGS = {
    "kernel_packed": dict(n_rows=64, d=256),
    "kernel_cham": dict(scale=0.004, n_rows=48, d=256),
    "kernel_sketch": dict(scale=0.01, n_rows=64, d=256),
    "kernel_sparse_sketch": dict(n_rows=64, n_dims=1 << 16, nnz=50, d=256),
    "dedup": dict(n_docs=64),
    "dedup_streaming": dict(n_docs=256),
    "index": dict(n_small=256, n_large=2048, n_queries=8, chunk=256,
                  ratio_bar=None),
    "index_mixed": dict(n_small=256, n_large=1024, q_batch=4, rounds=3,
                        churn=16, speedup_bar=None),
    "index_migrate": dict(n=512, d_new=256, batch_rows=128, q_batch=4),
    "index_sharded": dict(n=1024, n_queries=8, n_shards=4),
    "index_bulk": dict(n_docs=256, n_shards=4, window=32, mean_len=48),
    "cluster": dict(n_small=256, n_large=1024, k=4, n_iter=2,
                    oracle_iters=1, batch_rows=256, speedup_bar=None),
    "serve": dict(n=2048, duration_s=0.4, levels=(1, 4), max_requests=400,
                  bars=False),
}


def _git_rev() -> str | None:
    """Short commit hash of the tree the numbers were measured on, so a
    trajectory regression points at a PR, not a date range.  None outside
    a git checkout (e.g. a source tarball) — absence is honest there."""
    import subprocess

    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__)))
        return out.stdout.strip() or None if out.returncode == 0 else None
    except (OSError, subprocess.SubprocessError):
        return None


def _record_trajectory(trajectory: dict) -> None:
    """Merge this run's suites into the committed record and append a
    timestamped entry to its `trajectory` list (older single-snapshot files
    are upgraded in place; their snapshot seeds the history).  Each entry
    carries the measurement context — backend, device count, git rev —
    so a number can be attributed before it is compared."""
    import jax

    backend = jax.default_backend()
    record = {"backend": backend, "suites": {}, "trajectory": []}
    if os.path.exists("BENCH_kernels.json"):
        try:
            with open("BENCH_kernels.json") as f:
                old = json.load(f)
            record["suites"] = old.get("suites", {})
            record["trajectory"] = old.get("trajectory", [])
            if not record["trajectory"] and record["suites"]:
                # upgrade a legacy single-snapshot file: its numbers become
                # the first trajectory entry instead of being overwritten
                record["trajectory"].append({
                    "ts": None,
                    "backend": old.get("backend", backend),
                    "suites": dict(record["suites"]),  # pre-update copy
                })
        except (json.JSONDecodeError, OSError):
            pass
    record["suites"].update(trajectory)
    record["trajectory"].append({
        "ts": time.strftime("%Y-%m-%dT%H:%M:%S", time.gmtime()),
        "backend": backend,
        "device_count": jax.device_count(),
        "git_rev": _git_rev(),
        "suites": trajectory,
    })
    with open("BENCH_kernels.json", "w") as f:
        json.dump(record, f, indent=1, default=str)


def _ensure_device_count(argv: list[str]) -> None:
    """`--device-count N`: give this process N virtual CPU devices by
    setting XLA_FLAGS before JAX starts a backend.  Virtual host devices
    exist only on the CPU platform, so it refuses to run unless
    JAX_PLATFORMS=cpu is set: on a chip host the numbers it records are
    then CPU numbers by the caller's own choice, never by a silent switch.
    It never touches JAX itself."""
    n = None
    for i, arg in enumerate(argv):
        if arg == "--device-count":
            if i + 1 >= len(argv):
                raise SystemExit("usage: run.py --device-count N")
            n = int(argv[i + 1])
        elif arg.startswith("--device-count="):
            n = int(arg.split("=", 1)[1])
    if n is None:
        return
    if n < 1:
        raise SystemExit(f"--device-count must be >= 1, got {n}")
    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms != "cpu":
        raise SystemExit(
            f"--device-count makes virtual CPU devices; it needs "
            f"JAX_PLATFORMS=cpu, got {platforms!r}")
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    flags.append(f"--xla_force_host_platform_device_count={n}")
    os.environ["XLA_FLAGS"] = " ".join(flags)


def main() -> None:
    _ensure_device_count(sys.argv[1:])
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import bench_cluster, bench_dedup, bench_index, \
        bench_kernels, bench_paper, bench_serve

    suites = [
        ("fig2_table3", bench_paper.fig2_table3_reduction_speed),
        ("fig3_rmse", bench_paper.fig3_rmse),
        ("fig4_binem_variance", bench_paper.fig4_binem_variance),
        ("fig5_step2_variance", bench_paper.fig5_step2_variance),
        ("fig6to10_clustering", bench_paper.fig6to10_clustering),
        ("table4_heatmap", bench_paper.table4_heatmap),
        ("theorem2", bench_paper.theorem2_check),
        ("kernel_packed", bench_kernels.kernel_packed_vs_unpacked),
        ("kernel_cham", bench_kernels.kernel_cham_vs_exact_fulldim),
        ("kernel_sketch", bench_kernels.kernel_sketch_throughput),
        ("kernel_sparse_sketch", bench_kernels.bench_sparse_sketch),
        ("dedup", bench_dedup.dedup_sketch_vs_exact),
        ("dedup_streaming", bench_dedup.dedup_streaming_vs_blocked),
        ("index", bench_index.bench_index),
        ("index_mixed", bench_index.bench_mixed_traffic),
        ("index_migrate", bench_index.bench_migration),
        ("index_sharded", bench_index.bench_sharded),
        ("index_bulk", bench_index.bench_bulk_ingest),
        ("cluster", bench_cluster.bench_cluster),
        ("serve", bench_serve.bench_serve),
    ]
    only = None
    smoke = "--smoke" in sys.argv[1:]
    for i, arg in enumerate(sys.argv[1:]):
        if arg == "--suites":
            if 2 + i >= len(sys.argv):
                raise SystemExit(
                    "usage: run.py [--smoke] [--suites substr[,substr...]]")
            only = sys.argv[2 + i].split(",")
    if smoke:
        suites = [(n, f) for n, f in suites if n in _SMOKE_KWARGS]
    if only:
        suites = [(n, f) for n, f in suites
                  if any(sel in n for sel in only)]
        if not suites:
            raise SystemExit(f"--suites {','.join(only)} matched no suite")
    print("name,us_per_call,derived")
    summary = {}
    failures = []
    for name, fn in suites:
        t0 = time.perf_counter()
        try:
            summary[name] = fn(**_SMOKE_KWARGS[name]) if smoke else fn()
        except Exception as e:  # keep the suite running; report at the end
            failures.append((name, repr(e)))
            traceback.print_exc()
        print(f"# suite {name} done in {time.perf_counter() - t0:.1f}s",
              flush=True)

    # roofline summary from dry-run records, if present
    dr_dir = os.path.join("experiments", "dryrun")
    if not smoke and os.path.isdir(dr_dir):
        from repro.launch.roofline import load_records

        recs = [r for r in load_records(dr_dir) if r.get("status") == "ok"]
        for r in recs:
            roof = r.get("roofline", {})
            print(f"roofline.{r['arch']}.{r['shape']}.{r['mesh']},0.0,"
                  f"dom={roof.get('dominant')};"
                  f"c={roof.get('compute_s', 0):.3g}s;"
                  f"m={roof.get('memory_s', 0):.3g}s;"
                  f"n={roof.get('collective_s', 0):.3g}s")
        summary["dryrun_cells_ok"] = len(recs)

    # trajectory entries hold ONLY suites measured by THIS run — extracted
    # before the summary merge below, so a filtered or partially-failed run
    # can never stamp another run's numbers with a fresh timestamp
    trajectory = {k: v for k, v in summary.items() if k in _TRAJECTORY_SUITES}
    os.makedirs("experiments", exist_ok=True)
    out_name = "bench_summary_smoke.json" if smoke else "bench_summary.json"
    out_path = os.path.join("experiments", out_name)
    if not smoke and os.path.exists(out_path):
        # merge: a --suites-filtered run refreshes its suites without
        # discarding the others' results (same discipline as the
        # BENCH_kernels.json trajectory record)
        try:
            with open(out_path) as f:
                merged = json.load(f)
            merged.update(summary)
            summary = merged
        except (json.JSONDecodeError, OSError):
            pass
    with open(out_path, "w") as f:
        json.dump(summary, f, indent=1, default=str)
    if trajectory and not smoke:
        _record_trajectory(trajectory)
    if failures:
        print("FAILURES:", failures)
        raise SystemExit(1)
    print("# all benchmark suites passed"
          + (" (smoke sizes)" if smoke else ""))


if __name__ == "__main__":
    main()
