"""repro.obs: the flight recorder's accuracy, exporters, and off switch.

Four contracts under test:

  * instrument accuracy — pow2-bucket histogram quantiles are within one
    bucket of the true order statistic, merge is lossless at the bucket
    level, counters stay exact (they mirror the engine's own accounting);
  * exporters — `render_prom()` is valid Prometheus text exposition
    (cumulative monotone buckets, `_count`/`_sum` agreement), and a
    profiler capture holds spans that cover the serving ops, nest by
    layer, share the device ops' clock, and instants that mark
    faultinject crash points and JAX's compiles;
  * the off switch — REPRO_OBS=0 (env, subprocess-tested) and
    `obs.configure(False)` (runtime) hand every call site shared null
    instruments and put no span in a capture: results stay bit-identical
    and ZERO additional jit graphs compile relative to the instrumented
    run;
  * gauge truth at recovery — `engine_migration_progress` is exact at
    every faultinject crash/resume point of the migration matrix.
"""

import math
import os
import subprocess
import sys

import numpy as np
import pytest

from repro import obs
from repro.core.cabin import CabinParams
from repro.index import QueryEngine
from repro.index.engine import compile_cache_entries
from repro.obs.registry import Histogram, MetricsRegistry
from repro.runtime import faultinject

from _capture import capture

N_DIMS = 300
P = CabinParams(n_dims=N_DIMS, sketch_dim=64, psi_seed=21, pi_seed=22)
P_NEW = CabinParams(n_dims=N_DIMS, sketch_dim=128, psi_seed=21, pi_seed=22)

requires_obs = pytest.mark.skipif(
    not obs.enabled(), reason="suite running with REPRO_OBS=0")

# the modules whose spans are the program's (span names are
# `<module>.<what>`; crash points are `crash_point` instants)
PROGRAM_SPANS = ("engine.", "allpairs.", "partition.", "store.",
                 "frontdoor.", "migrate.", "merge_tree.", "ingest.",
                 "cluster.", "obs.", "crash_point")


def _program_spans(cap) -> set:
    return {n for n in cap.names() if n.startswith(PROGRAM_SPANS)}


def _rows(n, seed):
    rng = np.random.default_rng(seed)
    x = np.zeros((n, N_DIMS), np.int32)
    for r in range(n):
        cols = rng.choice(N_DIMS, size=rng.integers(8, 25), replace=False)
        x[r, cols] = rng.integers(1, 6, size=len(cols))
    return x


X = _rows(64, seed=0)
QUERIES = X[:4]


@pytest.fixture
def obs_restore():
    """Restore the module switch (and the faultinject observer binding)
    after a test that flips `obs.configure`."""
    was = obs.enabled()
    yield
    obs.configure(was)


def _same_or_adjacent_bucket(a: float, b: float) -> bool:
    """True when a and b fall in the same or neighbouring pow2 buckets —
    the histogram's advertised quantile accuracy."""
    ea = math.frexp(a)[1]
    eb = math.frexp(b)[1]
    return abs(ea - eb) <= 1


# ---------------------------------------------------------------------------
# instrument accuracy
# ---------------------------------------------------------------------------


def test_histogram_quantiles_within_one_bucket():
    rng = np.random.default_rng(7)
    samples = rng.lognormal(mean=1.0, sigma=1.5, size=2000)
    h = Histogram()
    for v in samples:
        h.observe(float(v))
    assert h.count == len(samples)
    assert h.min == samples.min() and h.max == samples.max()
    np.testing.assert_allclose(h.sum, samples.sum(), rtol=1e-9)
    for p in (1, 25, 50, 75, 95, 99):
        want = float(np.percentile(samples, p))
        got = h.quantile(p)
        assert h.min <= got <= h.max
        assert _same_or_adjacent_bucket(got, want), (p, got, want)
    # degenerate cases: empty -> NaN; single observation -> that value
    assert math.isnan(Histogram().quantile(50))
    h1 = Histogram()
    h1.observe(3.7)
    assert h1.quantile(50) == 3.7 == h1.quantile(99)


def test_histogram_merge_equals_union():
    rng = np.random.default_rng(8)
    a_s = rng.lognormal(1.0, 1.0, size=500)
    b_s = rng.lognormal(2.0, 0.5, size=700)
    ha, hb, hu = Histogram(), Histogram(), Histogram()
    for v in a_s:
        ha.observe(float(v))
        hu.observe(float(v))
    for v in b_s:
        hb.observe(float(v))
        hu.observe(float(v))
    ha.merge_from(hb)
    assert ha.count == hu.count and ha.buckets == hu.buckets
    assert ha.min == hu.min and ha.max == hu.max
    np.testing.assert_allclose(ha.sum, hu.sum, rtol=1e-9)
    for p in (10, 50, 90):
        assert ha.quantile(p) == hu.quantile(p)


def test_registry_merge_and_kind_collisions():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("reqs_total").inc(3)
    b.counter("reqs_total").inc(4)
    b.counter("other_total", shard="1").inc(2)
    a.histogram("lat_ms").observe(1.0)
    b.histogram("lat_ms").observe(9.0)
    a.merge(b)
    snap = a.snapshot()
    assert snap["reqs_total"] == 7
    assert snap["other_total"]["shard=1"] == 2
    assert snap["lat_ms"]["count"] == 2
    # one name keeps one kind — a mixed fleet could not merge or render
    with pytest.raises(ValueError, match="already a Counter"):
        a.gauge("reqs_total")
    # merging a null registry is a no-op, not an error
    a.merge(obs.NULL_REGISTRY)
    assert a.snapshot()["reqs_total"] == 7


def test_render_prom_is_valid_exposition():
    r = MetricsRegistry()
    r.counter("engine_cache_hits_total").inc(5)
    r.gauge_fn("rows_alive", lambda: 42.0)
    h = r.histogram("lat_ms", op="topk")
    for v in (0.3, 0.9, 2.0, 2.1, 7.5):
        h.observe(v)
    text = r.render_prom()
    lines = [ln for ln in text.strip().splitlines()]
    assert "# TYPE engine_cache_hits_total counter" in lines
    assert "engine_cache_hits_total 5" in lines
    assert "rows_alive 42.0" in lines
    # histogram: cumulative bucket counts are monotone and end at _count
    buckets = [ln for ln in lines if ln.startswith("lat_ms_bucket")]
    counts = [int(ln.rsplit(" ", 1)[1]) for ln in buckets]
    assert counts == sorted(counts)
    assert buckets[-1].startswith('lat_ms_bucket{op="topk",le="+Inf"}')
    assert counts[-1] == 5
    assert 'lat_ms_count{op="topk"} 5' in lines
    # every sample line is NAME{LABELS} VALUE with a parseable value
    for ln in lines:
        if ln.startswith("#"):
            continue
        float(ln.rsplit(" ", 1)[1])


# ---------------------------------------------------------------------------
# the off switch
# ---------------------------------------------------------------------------


def test_disabled_path_bit_identical_and_zero_new_graphs(obs_restore):
    """The REPRO_OBS=0 contract: an engine built under the disabled switch
    answers bit-identically AND compiles zero jit graphs beyond what the
    instrumented run already compiled — instrumentation never reaches the
    compiled graphs, it only wraps them on host."""
    obs.configure(True)
    eng_on = QueryEngine(P, cache_entries=4)
    assert not eng_on.obs.is_null

    def journey(eng):
        eng.add_dense(X[:48])
        a = eng.topk(QUERIES, 5)
        r = eng.radius(QUERIES, 60.0)
        eng.remove(np.arange(5))
        b = eng.topk(QUERIES, 5)
        b2 = eng.topk(QUERIES, 5)  # LRU hit path
        return a, r, b, b2

    on = journey(eng_on)
    assert eng_on.obs.snapshot()["engine_cache_hits_total"] == 1
    n_graphs = compile_cache_entries()

    obs.configure(False)
    eng_off = QueryEngine(P, cache_entries=4)
    assert eng_off.obs.is_null
    off = journey(eng_off)
    assert compile_cache_entries() == n_graphs, \
        "REPRO_OBS=0 run compiled additional graphs"
    for got, want in zip(off, on):
        if isinstance(got, list):
            for g, w in zip(got, want):
                np.testing.assert_array_equal(g, w)
        else:
            np.testing.assert_array_equal(got[0], want[0])
            np.testing.assert_array_equal(got[1], want[1])
    # the python-side accounting still works; the obs mirror is inert
    assert (eng_off.cache_hits, eng_off.cache_misses) == \
        (eng_on.cache_hits, eng_on.cache_misses)
    assert eng_off.obs.snapshot() == {}
    assert eng_off.render_prom() == ""
    assert "latency_ms" not in eng_off.stats()
    assert "latency_ms" in eng_on.stats()


def test_repro_obs_env_kills_the_layer_in_subprocess():
    """The deployment switch: REPRO_OBS=0 read at import time."""
    here = os.path.dirname(os.path.abspath(__file__))
    src = os.path.join(os.path.dirname(here), "src")
    child = (
        "import numpy as np\n"
        "from repro import obs\n"
        "from repro.core.cabin import CabinParams\n"
        "from repro.index import QueryEngine\n"
        "from _capture import capture\n"
        "assert not obs.enabled()\n"
        "assert obs.new_registry() is obs.NULL_REGISTRY\n"
        "p = CabinParams(n_dims=64, sketch_dim=32, psi_seed=1, pi_seed=2)\n"
        "eng = QueryEngine(p)\n"
        "assert eng.obs.is_null\n"
        "x = np.zeros((4, 64), np.int32)\n"
        "x[:, :5] = 1 + np.arange(5)\n"
        "with capture() as cap:\n"
        "    eng.add_dense(x)\n"
        "    eng.topk(x, 2)\n"
        "assert eng.obs.snapshot() == {}\n"
        "assert 'latency_ms' not in eng.stats()\n"
        f"prefixes = {PROGRAM_SPANS!r}\n"
        "assert cap.names(), 'the capture recorded nothing'\n"
        "assert not [n for n in cap.names() if n.startswith(prefixes)]\n"
        "assert obs.get_registry() is obs.NULL_REGISTRY\n"
        "print('NULLED')\n")
    env = dict(os.environ, PYTHONPATH=os.pathsep.join([src, here]),
               REPRO_OBS="0")
    proc = subprocess.run([sys.executable, "-c", child], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr
    assert "NULLED" in proc.stdout


# ---------------------------------------------------------------------------
# the acceptance run: live engine -> trace + prom + truthful quantiles
# ---------------------------------------------------------------------------


@requires_obs
def test_flight_recorder_acceptance():
    """One mixed serving journey (adds, removes, queries, a full spec
    migration) under a profiler capture: its spans cover every op and its
    instants mark the crash points crossed, plus a Prometheus snapshot
    whose latency quantiles agree with independently measured wall times
    to within one pow2 bucket."""
    import time

    with capture() as cap:
        eng = QueryEngine(P, cache_entries=0, keep_raw=True)
        eng.add_dense(X[:40])
        eng.remove(np.arange(3))
        eng.add_dense(X[40:])

        outer_ms = []
        for _ in range(8):
            t0 = time.perf_counter()
            eng.topk(QUERIES, 5)
            outer_ms.append((time.perf_counter() - t0) * 1e3)
        eng.radius(QUERIES, 60.0)
        eng.pairwise(QUERIES[:2], ids=eng.ids()[:10])

        eng.migrate(new_params=P_NEW, batch_rows=16, drive="manual")
        while eng.migration_step():
            pass
    assert not eng.migrating

    # -- counters/histograms tell the same story as the engine ------------
    snap = eng.obs_snapshot()
    lat = snap["engine_query_latency_ms"]
    assert lat["op=topk"]["count"] == 8
    assert lat["op=radius"]["count"] == 1
    assert lat["op=pairwise"]["count"] == 1
    h50 = lat["op=topk"]["p50"]
    # the recorder's p50 vs the test's own stopwatch: within one bucket
    # (outer timing adds only host dispatch around the timed region)
    assert _same_or_adjacent_bucket(h50, float(np.percentile(outer_ms, 50)))
    assert lat["op=topk"]["min"] <= h50 <= lat["op=topk"]["p99"] \
        <= lat["op=topk"]["max"] <= sum(outer_ms)
    assert snap["engine_migration_progress"] == 1.0
    assert snap["engine_rows_alive"] == float(len(eng))
    assert snap["migration_rows_resketched_total"] == 61  # 64 - 3 removed
    assert snap["migration_phase_ms"]["phase=resketch"]["count"] >= 4
    assert snap["migration_phase_ms"]["phase=fold"]["count"] == 1
    assert eng.stats()["latency_ms"]["topk"]["p50"] == h50

    # -- prom text covers the same instruments ----------------------------
    text = eng.render_prom()
    assert 'engine_query_latency_ms_bucket{op="topk",le="+Inf"} 8' in text
    assert "engine_rows_alive" in text and "store_rows_added_total" in text

    # -- the capture holds the journey's spans and crash points -----------
    names = cap.names()
    assert {"engine.topk", "engine.radius", "engine.pairwise",
            "engine.sketch", "migrate.batch", "migrate.fold", "store.add",
            "store.remove", "crash_point"} <= names
    assert len(cap.named("engine.topk")) == 8
    for e in cap.events:
        assert e.end_ns >= e.start_ns >= 0
    crossed = {e.args["point"] for e in cap.named("crash_point")}
    assert {"migrate.start", "migrate.batch.resketched",
            "migrate.batch.committed", "migrate.fold",
            "migrate.published"} <= crossed
    # every crash point falls inside the migration span that crossed it
    batches = cap.named("migrate.batch")
    for e in cap.named("crash_point"):
        if e.args["point"].startswith("migrate.batch."):
            assert any(e.within(b) for b in batches), e


@requires_obs
def test_walk_spans_nest_inside_engine_topk_and_layer_spans_exist():
    """A top-k flush's band walk and its rounds nest inside `engine.topk`
    (through `partition.topk`); radius and ingest record their own layer
    spans."""
    eng = QueryEngine(P, band_rows=8, cache_entries=0)
    eng.add_dense(X)
    eng.topk(QUERIES, 5)  # compile outside the capture
    coo = (np.tile(np.arange(10), (4, 1)), np.ones((4, 10), np.int32))
    with capture() as cap:
        eng.topk(QUERIES, 5)
        eng.radius(QUERIES, 60.0)
        eng.add_sparse(*coo)
        eng.remove(np.arange(3))
    [top] = cap.named("engine.topk")
    [walk] = cap.named("allpairs.walk")
    [part] = cap.named("partition.topk")
    assert walk.within(part) and part.within(top)
    assert walk.args["k"] == 5
    rounds = cap.named("allpairs.walk.plan")
    assert rounds
    for kind in ("plan", "score", "merge"):
        spans = cap.named(f"allpairs.walk.{kind}")
        assert spans and all(e.within(walk) for e in spans), kind
    assert not cap.named("allpairs.walk.gather")  # rounds score in place
    [sync] = [e for e in cap.named("engine.query_sync") if e.within(top)]
    assert sync.end_ns <= walk.start_ns
    [rad] = cap.named("engine.radius")
    for name in ("engine.sketch", "engine.query_sync",
                 "partition.radius_tiers", "allpairs.threshold_pairs",
                 "partition.radius_group"):
        assert any(e.within(rad) for e in cap.named(name)), name
    [add] = cap.named("engine.add_sparse")
    for name in ("engine.sketch", "store.add", "engine.track_drift"):
        assert any(e.within(add) for e in cap.named(name)), name
    [rm] = cap.named("engine.remove")
    [srm] = cap.named("store.remove")
    assert srm.within(rm) and srm.args["rows"] == 3


@requires_obs
def test_xla_events_of_a_query_fall_inside_its_engine_span():
    """One clock: JAX's own host events (dispatch) and the XLA ops the CPU
    backend runs for a query lie inside the query's `engine.topk` span."""
    eng = QueryEngine(P, band_rows=8, cache_entries=0)
    eng.add_dense(X)
    eng.topk(QUERIES, 5)
    with capture() as cap:
        eng.topk(QUERIES, 5)
    [top] = cap.named("engine.topk")
    dispatch = [e for e in cap.events if e.name.startswith("PjitFunction")]
    xla_ops = [e for e in cap.events if "hlo_op" in e.args]
    assert dispatch and xla_ops
    for e in dispatch + xla_ops:
        assert e.within(top), (e.name, e.start_ns, e.end_ns, top)


@requires_obs
def test_walk_rounds_counter_equals_the_walks_rounds():
    """`index_walk_rounds_total` counts the band rounds the walk ran: one
    `allpairs.walk.plan` span each."""
    eng = QueryEngine(P, band_rows=4, cache_entries=0)
    eng.add_dense(X)
    eng.topk(QUERIES, 5)
    before = eng.obs_snapshot()["index_walk_rounds_total"]
    with capture() as cap:
        eng.topk(QUERIES, 5)
    moved = eng.obs_snapshot()["index_walk_rounds_total"] - before
    plans = cap.named("allpairs.walk.plan")
    assert moved == len(plans) >= 2
    assert sorted(e.args["round"] for e in plans) == list(
        range(1, moved + 1))


@requires_obs
def test_walk_tiles_counter_covers_every_rounds_grid():
    """`index_walk_tiles_total` counts each in-place top-k call's grid
    steps: scored (listed tiles) plus skipped is rounds x grid length."""
    eng = QueryEngine(P, band_rows=4, cache_entries=0)
    eng.add_dense(X)
    eng.topk(QUERIES, 5)
    layout = eng._banded_layout()
    grid = layout.matrix.shape[0] // 4  # 4-row tiles: the band size

    def counts():
        snap = eng.obs_snapshot()
        tiles = snap["index_walk_tiles_total"]
        return (snap["index_walk_rounds_total"], tiles["state=scored"],
                tiles["state=skipped"])

    before = counts()
    eng.topk(QUERIES, 5)
    eng.topk(X[-3:], 2)
    rounds, scored, skipped = (a - b for a, b in zip(counts(), before))
    assert rounds >= 2 and scored > 0 and skipped > 0
    assert scored + skipped == rounds * grid


@requires_obs
def test_walk_compiles_one_program_per_query_bucket():
    """Flushes whose queries sit in different weight ranges (so their
    rounds hold different bands and rows) add no compiled program once
    their query bucket has been served: the walk's program depends on
    the bucket, the layout's size and k alone."""
    eng = QueryEngine(P, band_rows=4, cache_entries=0)
    eng.add_dense(X)
    order = np.argsort(np.count_nonzero(X, axis=1))
    light, heavy, mixed = X[order[:5]], X[order[-5:]], X[order[::13][:5]]
    reg = obs.get_registry()

    def compiles():
        return reg.snapshot().get("jax_compiles_total", {}).get(
            "phase=backend_compile", 0)

    eng.topk(light, 5)  # the 8-row bucket's first flush compiles
    before = compiles()
    for q in (heavy, mixed, light[::-1]):
        eng.topk(q, 5)
    assert compiles() == before
    eng.topk(np.concatenate([light, heavy, mixed]), 5)  # the 16-row bucket
    before = compiles()
    eng.topk(np.concatenate([heavy, mixed, light]), 5)
    assert compiles() == before


@requires_obs
def test_fresh_jit_shape_counts_a_compile():
    """The compile listener counts a jit's compile phases into the
    process-default registry and marks each with an `obs.compile`
    instant carrying its duration."""
    import jax
    import jax.numpy as jnp

    reg = obs.get_registry()

    def backend(snap, key):
        return snap.get(key, {}).get("phase=backend_compile", 0)

    before = reg.snapshot()
    with capture() as cap:
        jax.jit(lambda v: v * 3 + 1)(jnp.ones(4099)).block_until_ready()
    after = reg.snapshot()
    assert backend(after, "jax_compiles_total") >= \
        backend(before, "jax_compiles_total") + 1
    assert backend(after, "jax_compile_seconds_total") > \
        backend(before, "jax_compile_seconds_total")
    marks = cap.named("obs.compile")
    assert {"jaxpr_trace", "backend_compile"} <= {
        e.args["phase"] for e in marks}
    assert all(e.args["us"] >= 0 for e in marks)


def test_disabled_switch_records_no_program_span(obs_restore):
    """`obs.configure(False)`: a capture of a serving journey holds JAX's
    own events and not one program span or instant."""
    obs.configure(False)
    eng = QueryEngine(P, band_rows=8, cache_entries=0)
    with capture() as cap:
        eng.add_dense(X[:32])
        eng.topk(QUERIES, 5)
        eng.radius(QUERIES, 60.0)
    assert cap.names()
    assert _program_spans(cap) == set()


# ---------------------------------------------------------------------------
# migration_progress: exact at every crash/resume point
# ---------------------------------------------------------------------------


@requires_obs
@pytest.mark.parametrize("point", [
    "migrate.start", "migrate.batch.resketched", "migrate.batch.committed",
    "migrate.fold", "migrate.published"])
def test_migration_progress_gauge_exact_at_resume(tmp_path, point):
    """Crash the migration at `point`, restore FROM DISK ONLY, and require
    the progress gauge to be truthful at the resume state and monotone to
    1.0 as the migration is driven home."""
    x = _rows(26, seed=hash(point) % 1000)
    journal = str(tmp_path / "journal")
    eng = QueryEngine(P, cache_entries=0)
    eng.add_dense(x)
    eng.save(journal, step=0, keep=20)

    with faultinject.armed(point):
        try:
            eng.migrate(new_params=P_NEW, batch_rows=7, drive="manual",
                        journal_dir=journal, journal_every=1,
                        journal_keep=20)
            eng.migrate_all()
            crashed = False
        except faultinject.InjectedCrash:
            crashed = True
    assert crashed, f"never reached {point}"

    res = QueryEngine.restore(journal)

    def progress(e):
        return e.obs_snapshot()["engine_migration_progress"]

    p0 = progress(res)
    if res.migrating:
        m = res.stats()["migration"]
        assert p0 == m["progress"]
        # truthful against the migration's own row accounting
        done = res.migration.rows_migrated
        total = done + len(res.migration.src)
        assert p0 == (done / total if total else 1.0)
        assert 0.0 <= p0 <= 1.0
        # monotone to completion, exact at every step
        last = p0
        while res.migration_step():
            p = progress(res)
            assert p >= last
            last = p
    assert not res.migrating
    assert progress(res) == 1.0
    assert res.obs_snapshot()["engine_migration_cursor"] == -1.0


# ---------------------------------------------------------------------------
# thread safety: the front door's real threads vs exporters
# ---------------------------------------------------------------------------


def test_registry_reads_are_safe_under_concurrent_writes():
    """Writers hammer a histogram + counter while readers continuously
    render/snapshot/merge.  Pre-fix, snapshot and render_prom iterated
    live bucket dicts without the instrument lock ("dictionary changed
    size during iteration" under a concurrent observe); now every reader
    goes through Histogram.state().  Final totals must also be exact —
    no update may be lost to a read."""
    import threading

    reg = MetricsRegistry()
    h = reg.histogram("lat_ms")
    c = reg.counter("events_total")
    n_writers, per_writer = 4, 3000
    stop = threading.Event()
    errors = []

    def writer(seed):
        rng = np.random.default_rng(seed)
        vals = rng.random(per_writer) * 1e4
        for v in vals:
            h.observe(float(v))
            c.inc()

    def reader():
        sink = MetricsRegistry()
        while not stop.is_set():
            try:
                reg.render_prom()
                snap = reg.snapshot()
                hs = snap["lat_ms"]
                # a torn read would let count drift from the bucket sum
                assert hs["count"] >= 0
                h.quantile(99)
                sink.merge(reg)
            except Exception as e:  # pragma: no cover - the regression
                errors.append(e)
                return

    writers = [threading.Thread(target=writer, args=(s,))
               for s in range(n_writers)]
    readers = [threading.Thread(target=reader) for _ in range(3)]
    for t in readers + writers:
        t.start()
    for t in writers:
        t.join()
    stop.set()
    for t in readers:
        t.join()
    assert not errors, f"exporter raced a writer: {errors[:1]}"
    assert c.value == n_writers * per_writer
    buckets, count, total, mn, mx = h.state()
    assert count == n_writers * per_writer
    assert sum(buckets.values()) == count
    assert math.isfinite(total) and mn >= 0.0 and mx <= 1e4


def test_histogram_state_is_a_consistent_copy():
    h = Histogram()
    for v in (1.0, 3.0, 100.0):
        h.observe(v)
    buckets, count, total, mn, mx = h.state()
    assert count == 3 and total == pytest.approx(104.0)
    assert (mn, mx) == (1.0, 100.0)
    buckets[99] = 10**6  # mutating the copy must not touch the histogram
    assert h.state()[0] != buckets
    assert h.count == 3
