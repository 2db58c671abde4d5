"""repro.index.partition: the partition layer's pinned contracts.

Three load-bearing properties:

1. API boundary — `merge_topk_parts` / `kbest_lex_merge` reject k < 0 and
   return well-typed empties for empty inputs (shape (0, k), int64/float32),
   so cross-partition merges degrade to no-ops instead of crashing on an
   engine with zero shards' worth of candidates.
2. Sharded bit-identity — `shard(n_shards)` after ANY interleaved
   add/remove/compact/migrate history answers topk/radius/pairwise with
   exactly the bits the unsharded engine produces, both metrics, including
   queries served mid-migration (the partition exactness argument).
3. Shard-local maintenance — folds touch one shard's partitions and leave
   sibling base layouts untouched; per-partition gauges and the
   `partition.merge` span land in render_prom()/the trace.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from tests._hyp import given, settings, st

from repro.core import CabinParams, threshold_pairs, topk_rows
from repro.core.allpairs import kbest_lex_merge
from repro.core.cabin import sketch_dense
from repro.index import QueryEngine, merge_topk_parts
from repro.index.partition import shard_of
from repro.runtime import faultinject

N_DIMS = 500
D = 256
P = CabinParams.create(N_DIMS, D, seed=3)


def _rows(n, seed):
    rng = np.random.default_rng(seed)
    x = np.zeros((n, N_DIMS), np.int32)
    for i in range(n):
        density = int(rng.integers(10, 80))
        idx = rng.choice(N_DIMS, size=density, replace=False)
        x[i, idx] = rng.integers(1, 8, size=density)
    return x


X = _rows(96, seed=0)
SK = np.asarray(sketch_dense(P, jnp.asarray(X)))
QUERIES = X[:5]


# ---------------------------------------------------------------------------
# merge API boundary (satellite: k validation + well-typed empties)
# ---------------------------------------------------------------------------


def test_merge_topk_parts_negative_k_raises():
    part = (np.zeros((2, 3), np.int64), np.zeros((2, 3), np.float32))
    with pytest.raises(ValueError, match="k must be >= 0"):
        merge_topk_parts(-1, [part])


def test_kbest_lex_merge_negative_k_raises():
    with pytest.raises(ValueError, match="k must be >= 0"):
        kbest_lex_merge(-2, np.zeros((1, 2), np.float32),
                        np.zeros((1, 2), np.int64))


@pytest.mark.parametrize("kk", [0, 3])
def test_merge_topk_parts_empty_parts_well_typed(kk):
    """Zero partitions (an empty engine's shard walk) must merge to a
    well-typed empty answer, not an exception or an object array."""
    ids, vals = merge_topk_parts(kk, [])
    assert ids.shape == (0, kk) and vals.shape == (0, kk)
    assert ids.dtype == np.int64 and vals.dtype == np.float32


def test_merge_topk_parts_pads_narrow_parts():
    """A partition holding fewer than k rows contributes padded columns
    that always lose the lex merge — never garbage ids."""
    a = (np.array([[5]], np.int64), np.array([[1.0]], np.float32))
    b = (np.array([[2, 7]], np.int64), np.array([[0.5, 3.0]], np.float32))
    ids, vals = merge_topk_parts(3, [a, b])
    np.testing.assert_array_equal(ids, [[2, 5, 7]])
    np.testing.assert_array_equal(vals, np.array([[0.5, 1.0, 3.0]],
                                                 np.float32))


def test_shard_of_is_id_mod_n():
    ids = np.array([0, 1, 5, 8, 13], np.int64)
    np.testing.assert_array_equal(shard_of(ids, 3), ids % 3)


# ---------------------------------------------------------------------------
# partition topology invariants
# ---------------------------------------------------------------------------


def test_partitions_route_by_id_and_cover_alive_set():
    """Every alive id lands in exactly one shard's partitions, chosen by
    id % n_shards — deterministic and independent of insertion history."""
    eng = QueryEngine(P, band_rows=8, cache_entries=0)
    eng.add_dense(X[:48])
    eng.remove(np.arange(0, 48, 7))
    eng.shard(n_shards=3)
    lay = eng.sync_layout()
    seen = []
    for p in lay.partitions():
        assert p.kind in ("sorted-banded", "brute-delta")
        if p.n_rows:
            np.testing.assert_array_equal(p.ids % 3, p.shard)
        seen.append(p.ids)
    got = np.sort(np.concatenate(seen))
    np.testing.assert_array_equal(got, np.sort(eng.ids()))
    assert eng.stats()["n_shards"] == 3


def test_fold_is_shard_local():
    """Tombstoning one shard's rows folds THAT shard; the sibling shard's
    base layout object is untouched (no global rebuild)."""
    eng = QueryEngine(P, band_rows=4, merge_ratio=0.5, cache_entries=0)
    eng.add_dense(X[:32])
    eng.shard(n_shards=2)
    lay = eng.sync_layout()
    parts = lay.partitions()  # [base0, delta0, base1, delta1]
    base0, base1 = parts[0].banded, parts[2].banded
    merges0 = lay.n_merges
    # kill 14 of shard 0's 16 rows: dead_base > base_alive trips the fold
    eng.remove(np.arange(0, 28, 2))
    lay2 = eng.sync_layout()
    assert lay2 is lay  # same PartitionSet, synced in place
    parts2 = lay2.partitions()
    assert parts2[0].banded is not base0  # shard 0 folded
    assert parts2[2].banded is base1      # shard 1 untouched
    assert lay2.n_merges == merges0 + 1   # exactly one shard-local fold
    alive = eng.ids()
    ref_i, ref_v = topk_rows(SK[:4], SK[alive], 5, d=D, metric="cham")
    got_i, got_v = eng.topk(X[:4], 5)
    np.testing.assert_array_equal(got_i, alive[ref_i])
    np.testing.assert_array_equal(got_v, ref_v)


# ---------------------------------------------------------------------------
# sharded bit-identity over arbitrary histories (the tentpole contract)
# ---------------------------------------------------------------------------


def _assert_parity(ref, sh, rng):
    q = X[rng.integers(0, len(X), size=4)]
    k = int(rng.integers(1, 9))
    ri, rv = ref.topk(q, k)
    si, sv = sh.topk(q, k)
    np.testing.assert_array_equal(si, ri)
    np.testing.assert_array_equal(sv, rv)
    r = 60.0 if ref.metric == "cham" else 30.0
    for a, b in zip(sh.radius(q, r), ref.radius(q, r)):
        np.testing.assert_array_equal(a, b)
    if not ref.migrating:
        rp = ref.pairwise(q[:2])
        sp = sh.pairwise(q[:2])
        np.testing.assert_array_equal(sp[0], rp[0])
        np.testing.assert_array_equal(sp[1], rp[1])


@settings(max_examples=6, deadline=None)
@given(st.integers(0, 10**6), st.integers(0, 2))
def test_sharded_engine_bit_identical_any_history(seed, shard_idx):
    """The acceptance property: a sharded engine is bit-identical to the
    unsharded engine after ANY interleaved add/remove/compact/migrate
    history, for topk, radius AND pairwise, both metrics — including
    queries answered mid-migration across spec tiers."""
    n_shards = (2, 3, 8)[shard_idx]
    metric = ("cham", "hamming")[seed % 2]
    rng = np.random.default_rng(seed)
    kw = dict(metric=metric, band_rows=16, merge_ratio=0.5, cache_entries=0)
    ref = QueryEngine(P, **kw)
    sh = QueryEngine(P, **kw)
    sh.shard(n_shards=n_shards)
    pos = 0
    for _ in range(5):
        op = rng.random()
        if op < 0.50 or len(ref) < 8:
            c = int(rng.integers(1, 14))
            rows = np.arange(pos, pos + c) % len(X)
            pos += c
            np.testing.assert_array_equal(ref.add_dense(X[rows]),
                                          sh.add_dense(X[rows]))
        elif op < 0.72:
            alive = ref.ids()
            drop = rng.choice(alive, size=int(rng.integers(1, 5)),
                              replace=False)
            assert ref.remove(drop) == sh.remove(drop)
        elif op < 0.88 or ref.migrating:
            ref.compact()
            sh.compact()
        else:
            ref.migrate(d=320, drive="manual", batch_rows=16)
            sh.migrate(d=320, drive="manual", batch_rows=16)
            ref.migration_step()
            sh.migration_step()  # mid-migration: three-store serving
        _assert_parity(ref, sh, np.random.default_rng(seed + 1))
    if ref.migrating:
        ref.migrate_all()
        sh.migrate_all()
    _assert_parity(ref, sh, np.random.default_rng(seed + 2))


def test_reshard_changes_topology_not_answers():
    """shard() is a pure layout move: re-sharding an already-sharded
    engine (including back to 1) never changes a single answer bit."""
    eng = QueryEngine(P, band_rows=8, cache_entries=0)
    eng.add_dense(X[:64])
    eng.remove(np.arange(5))
    want_i, want_v = eng.topk(QUERIES, 6)
    for n in (4, 8, 1, 3):
        eng.shard(n_shards=n)
        got_i, got_v = eng.topk(QUERIES, 6)
        np.testing.assert_array_equal(got_i, want_i)
        np.testing.assert_array_equal(got_v, want_v)
        assert eng.stats()["n_shards"] == n


# ---------------------------------------------------------------------------
# observability: per-partition gauges + the merge span (satellite)
# ---------------------------------------------------------------------------


def test_partition_gauges_and_merge_span_shapes():
    from _capture import capture

    eng = QueryEngine(P, band_rows=8, cache_entries=0)
    eng.add_dense(X[:24])
    eng.shard(n_shards=2)
    with capture() as cap:
        eng.topk(QUERIES, 4)
    if eng.obs.is_null:  # REPRO_OBS=0: the instruments are no-ops
        pytest.skip("obs disabled in this environment")
    text = eng.render_prom()
    assert "partition_rows" in text
    for shard in ("0", "1"):
        assert f'shard="{shard}"' in text
    for kind in ("sorted-banded", "brute-delta"):
        assert f'kind="{kind}"' in text
    assert 'role="serve"' in text and 'device="host"' in text
    # the cross-partition walk: one span over both shards' walks
    [walk] = cap.named("partition.topk")
    assert walk.args["shards"] == 2
    assert "partition.merge" not in cap.names()
    assert all(e.within(walk) for e in cap.named("allpairs.walk"))


def test_reshard_frees_the_old_layout():
    """A gauge whose labels the new topology no longer has must not keep
    the old layout, and with it a device copy of the corpus, alive."""
    import gc
    import weakref

    eng = QueryEngine(P, band_rows=8, cache_entries=0)
    eng.add_dense(X[:24])
    eng.shard(n_shards=2)
    eng.topk(QUERIES, 4)
    old = weakref.ref(eng.sync_layout())
    eng.shard(n_shards=1)
    eng.topk(QUERIES, 4)
    gc.collect()
    assert old() is None
    if not eng.obs.is_null:  # shard 1's gauge now reads an empty layout
        assert 'shard="1"} 0' in eng.render_prom()


# ---------------------------------------------------------------------------
# crash safety: shard.rebalance is a derived-state point (satellite)
# ---------------------------------------------------------------------------


def test_shard_rebalance_crash_is_retryable():
    """A crash mid-rebalance loses no state: the layout is derived, the
    point fires before any group is swapped, so the next query simply
    rebuilds and serves the exact same bits as an engine that never
    crashed."""
    eng = QueryEngine(P, band_rows=8, cache_entries=0)
    eng.add_dense(X[:40])
    want_i, want_v = eng.topk(QUERIES, 5)
    eng.shard(n_shards=4)
    faultinject.record_hits(True)
    faultinject.clear_hits()
    try:
        with faultinject.armed("shard.rebalance"):
            with pytest.raises(faultinject.InjectedCrash) as exc:
                eng.topk(QUERIES, 5)  # first sharded query rebuilds
        assert exc.value.point == "shard.rebalance"
        got_i, got_v = eng.topk(QUERIES, 5)  # retry: rebuild succeeds
        np.testing.assert_array_equal(got_i, want_i)
        np.testing.assert_array_equal(got_v, want_v)
        assert eng.stats()["n_shards"] == 4
    finally:
        faultinject.record_hits(False)
