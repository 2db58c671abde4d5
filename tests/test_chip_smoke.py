"""chip_smoke.py's phases at a tiny size on the CPU, and the rules a chip
run relies on: no TPU, no run; a fixed compile-cache directory; importing
the serving stack claims no device.

The script refuses to run off a TPU, so these tests drive its phase
functions directly: the corpus generator, the numpy Cabin reference, the
front-door serving checks and the mutation history.  The checks that need
the chip's compiled kernels (`kernel_paths`) are replaced by a stub here;
everything else runs as on the chip, with the jnp sketch and tile paths
that JAX takes on the CPU.
"""

import importlib.util
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # dataclasses resolve annotations there
    spec.loader.exec_module(mod)
    return mod


def test_corpus_twin_matches_the_synthetic_generator(smoke):
    """The vectorised twin has the shape and Zipf popularity of
    repro.data.synthetic.sample_sparse on the NYTimes spec."""
    from repro.data.synthetic import TABLE1, sample_sparse

    corpus = smoke.Corpus(rows=512)
    idx, val = smoke.CorpusStream(corpus, seed=3).batch(512)
    ref_idx, ref_val, _ = sample_sparse(TABLE1["nytimes"], 200, seed=0)
    assert idx.shape == val.shape == (512, corpus.width) == (
        512, ref_idx.shape[1])
    nnz = np.count_nonzero(val, axis=1)
    assert abs(nnz.mean() - corpus.density) < 0.03 * corpus.density
    assert nnz.max() <= corpus.width and nnz.min() >= 1
    assert val.max() <= corpus.n_categories and idx.max() < corpus.n_dims
    for i in range(0, 512, 64):  # ids distinct within a row
        live = idx[i][val[i] > 0]
        assert len(np.unique(live)) == len(live)
    for lo, hi in ((0, 100), (100, 10_000), (50_000, corpus.n_dims)):
        share = np.mean((idx[val > 0] >= lo) & (idx[val > 0] < hi))
        want = np.mean((ref_idx[ref_val > 0] >= lo)
                       & (ref_idx[ref_val > 0] < hi))
        assert abs(share - want) < 0.01, (lo, hi, share, want)


@pytest.mark.parametrize("d", [128, 512, 4096])
def test_numpy_sketch_matches_the_library(smoke, d):
    from repro.core.cabin import CabinParams, sketch_sparse_jnp

    corpus = smoke.Corpus(rows=64)
    idx, val = smoke.CorpusStream(corpus, seed=5).batch(64)
    params = CabinParams.create(corpus.n_dims, d, seed=11)
    want = np.asarray(sketch_sparse_jnp(params, idx, val))
    np.testing.assert_array_equal(smoke.np_sketch(params, idx, val), want)


def test_reference_checks_catch_a_wrong_answer(smoke):
    rng = np.random.default_rng(0)
    base = rng.integers(-2**31, 2**31, (50, 16)).astype(np.int32)
    ids = np.arange(100, 150)
    for metric in smoke.METRICS:
        ref, tol = smoke.ref_distances(base[:3], base, metric, 512)
        order = np.stack([np.lexsort((ids, r))[:5] for r in ref])
        good = ids[order]
        dists = np.take_along_axis(ref, order, axis=1).astype(np.float32)
        assert smoke.check_topk(good, dists, ref, tol, ids, 5) == 0
        wrong = good.copy()
        wrong[1, 4] = ids[np.argsort(ref[1])[-1]]
        assert smoke.check_topk(wrong, dists, ref, tol, ids, 5) == 1
        r = float(np.sort(ref[0])[4])
        hits = [ids[row < r] for row in ref]
        assert smoke.check_radius(hits, ref, tol, r, ids) == 0
        hits[2] = hits[2][1:]
        assert smoke.check_radius(hits, ref, tol, r, ids) == 1


def test_single_chip_phases_on_cpu(smoke, monkeypatch):
    monkeypatch.setattr(smoke, "kernel_paths", lambda *a, **k: {})
    monkeypatch.setattr(smoke, "N_ADDED", 256)
    smoke.run_single(seed=1, corpus=smoke.Corpus(rows=1024), d=512,
                     batch_rows=256)


def test_compile_clock_counts_nested_spans_once(smoke):
    """A jit traced inside another reports a span inside its parent's; a
    phase's compile seconds are their union, so never more than its wall."""
    import jax
    import jax.numpy as jnp

    clock = smoke.CompileClock()
    with smoke.Phase(clock) as ph:
        inner = jax.jit(lambda x: jnp.sin(x) * 3.0)
        jax.jit(lambda x: inner(x) + inner(x + 1.0))(
            jnp.arange(7.0)).block_until_ready()
    assert 0.0 < ph.compile <= ph.wall
    clock.spans = [(0.0, 10.0), (2.0, 5.0), (8.0, 12.0), (20.0, 21.0)]
    assert clock.seconds() == 13.0
    assert clock.seconds(4.0, 9.0) == 5.0
    assert clock.seconds(11.0, 20.5) == 1.5


@pytest.mark.parametrize("fault", ["sketch", "membership"])
def test_mutation_checks_catch_a_corrupt_store(smoke, monkeypatch, fault):
    """After the mutations the smoke checks the store itself, not only the
    answers read from it: a wrong stored sketch or a lost remove fails."""
    monkeypatch.setattr(smoke, "N_ADDED", 256)
    clock = smoke.CompileClock()
    engines, stream, samples, _, source_ids = smoke.build_engines(
        smoke.Corpus(rows=512), 4, clock, d=256, batch_rows=256)
    if fault == "membership":
        monkeypatch.setattr(engines[1], "remove", lambda ids: None)
        with pytest.raises(smoke.SmokeFailure, match="membership"):
            smoke.mutate(engines, stream, source_ids, samples, 4, clock)
        return
    ids, idx, val = smoke.mutate(engines, stream, source_ids, samples, 4,
                                 clock)
    smoke.check_sketches(engines[1], (ids, idx, val), "mutated")
    val = val.copy()
    val[-1] = 0  # the last added row's reference: as if never written
    with pytest.raises(smoke.SmokeFailure, match="differ"):
        smoke.check_sketches(engines[1], (ids, idx, val), "mutated")


def test_sharded_phase_on_four_cpu_devices():
    """`--chips 4`'s phase on four virtual CPU devices, in a child process
    (the device count is fixed when JAX starts)."""
    code = textwrap.dedent("""
        import importlib.util, sys
        spec = importlib.util.spec_from_file_location("chip_smoke",
                                                      "chip_smoke.py")
        mod = importlib.util.module_from_spec(spec)
        sys.modules[spec.name] = mod
        spec.loader.exec_module(mod)
        mod.run_sharded(seed=2, n_chips=4, corpus=mod.Corpus(rows=768),
                        d=512, batch_rows=256)
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    compared = [json.loads(line.split(" ", 1)[1])
                for line in proc.stdout.splitlines()
                if line.startswith("smoke ") and "shard_compare" in line]
    assert [c["metric"] for c in compared] == ["hamming", "cham"]
    assert all(c["shards"] == 4 and c["identical_id_rows"] == c["queries"]
               for c in compared)


def test_refuses_to_run_off_a_tpu():
    """No phase runs on the CPU: the script names the missing TPU and exits
    non-zero, printing no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout
    assert '"ingest"' not in proc.stdout


@pytest.mark.parametrize("from_env", [False, True])
def test_compile_cache_directory_is_fixed(tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR when set (JAX reads it itself, so nothing
    is set in code), else the fixed in-repo directory."""
    code = textwrap.dedent("""
        import jax
        from repro.runtime.compile_cache import enable_compile_cache
        print(enable_compile_cache())
        print(jax.config.jax_compilation_cache_dir)
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = str(ROOT / ".jax_cache")
    if from_env:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    proc = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.split() == [want, want]


def test_importing_the_index_claims_no_device():
    """A chip belongs to the first process that starts a JAX backend, so
    importing the serving stack must not start one."""
    code = textwrap.dedent("""
        import repro.index, repro.serve
        from jax._src import xla_bridge
        print(len(xla_bridge._backends))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.split() == ["0"]
