"""repro.index: the online index's bit-identity contracts vs the batch
engine (core.allpairs), plus store/cache/checkpoint/ingest behaviour.

The load-bearing property: no matter how the store reached its current
membership (chunked adds, tombstones, compactions, snapshot round-trips),
`topk` and `radius` return EXACTLY what core.allpairs returns on a freshly
assembled matrix of the same vectors — same ids, same float bits.
"""

import tempfile

import numpy as np
import jax.numpy as jnp
import pytest

from tests._hyp import given, settings, st

from repro.core import (CabinParams, threshold_pairs, topk_rows)
from repro.core.cabin import sketch_dense
from repro.index import BandedLayout, QueryEngine, SketchStore, \
    TieredLayout, ingest_documents

N_DIMS = 500
D = 256
P = CabinParams.create(N_DIMS, D, seed=3)


def _rows(n, seed):
    """Varied per-row density (10..80 features) so sketch weights spread —
    the structure the weight-banded layout exists to exploit."""
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


def _radius_ref(q_sk, data_sk, ids, r, metric):
    """Per-query sorted id arrays from the batch engine."""
    pairs = threshold_pairs(jnp.asarray(q_sk), jnp.asarray(data_sk), d=D,
                            threshold=r, metric=metric)
    return [np.sort(ids[pairs[pairs[:, 0] == qi, 1]])
            for qi in range(len(q_sk))]


# ---------------------------------------------------------------------------
# bit-identity vs the batch engine (the acceptance contract)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("metric", ["cham", "hamming"])
def test_engine_bit_identical_through_mutations(metric, tmp_path):
    """One full serving journey per metric: chunked build -> topk/radius
    parity -> remove -> parity -> compact -> parity -> more adds -> parity
    -> snapshot/restore -> parity.  Every comparison is exact equality
    against core.allpairs on the alive membership."""
    eng = QueryEngine(P, metric=metric, band_rows=16)

    def check():
        alive = eng.ids()
        data_sk = SK[alive]
        ref_i, ref_v = topk_rows(SK[:5], data_sk, 7, d=D, metric=metric)
        got_i, got_v = eng.topk(QUERIES, 7)
        np.testing.assert_array_equal(got_i, alive[ref_i])
        np.testing.assert_array_equal(got_v, ref_v)
        r = float(np.percentile(ref_v, 70) + 0.37)
        got_r = eng.radius(QUERIES, r)
        want_r = _radius_ref(SK[:5], data_sk, alive, r, metric)
        for a, b in zip(got_r, want_r):
            np.testing.assert_array_equal(a, b)

    eng.add_dense(X[:40])
    eng.add_dense(X[40:70])
    check()
    eng.remove(np.arange(10, 35))
    check()
    eng.compact()
    check()
    eng.add_dense(X[70:])
    check()
    eng.save(str(tmp_path / metric), step=2)
    restored = QueryEngine.restore(str(tmp_path / metric))
    assert restored.metric == metric
    with pytest.raises(ValueError, match="fixed by the snapshot"):
        QueryEngine.restore(str(tmp_path / metric), metric="cham")
    got_i, got_v = eng.topk(QUERIES, 7)
    res_i, res_v = restored.topk(QUERIES, 7)
    np.testing.assert_array_equal(res_i, got_i)
    np.testing.assert_array_equal(res_v, got_v)
    # restored engine keeps serving mutations from where it left off
    new_ids = restored.add_dense(X[:4])
    assert new_ids.min() > eng.ids().max()


def test_topk_ties_resolve_to_lower_id():
    """Duplicate vectors => equal distances; the winner must be the lower
    id, matching topk_rows' stable merge."""
    eng = QueryEngine(P)
    eng.add_dense(np.concatenate([X[:8], X[:8]]))  # ids 8..15 duplicate 0..7
    ids, vals = eng.topk(X[:8], 2)
    np.testing.assert_array_equal(ids[:, 0], np.arange(8))
    np.testing.assert_array_equal(ids[:, 1], np.arange(8, 16))
    np.testing.assert_array_equal(vals[:, 0], vals[:, 1])


def test_sparse_and_dense_ingest_agree():
    nz = [np.flatnonzero(row) for row in X[:20]]
    m = max(len(z) for z in nz)
    idx = np.zeros((20, m), np.int32)
    val = np.zeros((20, m), np.int32)
    for i, z in enumerate(nz):
        idx[i, : len(z)] = z
        val[i, : len(z)] = X[i, z]
    e1 = QueryEngine(P)
    e1.add_sparse(idx, val)
    e2 = QueryEngine(P)
    e2.add_dense(X[:20])
    g1 = e1.topk(QUERIES, 5)
    g2 = e2.topk(QUERIES, 5)
    np.testing.assert_array_equal(g1[0], g2[0])
    np.testing.assert_array_equal(g1[1], g2[1])
    # COO queries hit the same sketch space as dense queries
    gq = e2.topk((idx[:5], val[:5]), 5)
    np.testing.assert_array_equal(gq[0], g2[0])
    np.testing.assert_array_equal(gq[1], g2[1])


def test_pairwise_matches_topk_distances():
    eng = QueryEngine(P)
    eng.add_dense(X[:30])
    ids, dists = eng.pairwise(QUERIES)
    np.testing.assert_array_equal(ids, np.arange(30))
    top_i, top_v = eng.topk(QUERIES, 3)
    # cham: same exact integer stats, float estimator agrees to cross-graph
    # libm noise (see kernels.hamming.ops.dist_matrix)
    np.testing.assert_allclose(
        np.take_along_axis(dists, top_i.astype(np.int64), axis=1), top_v,
        rtol=1e-5, atol=1e-3)
    sub_ids, sub = eng.pairwise(QUERIES, ids=np.asarray([3, 7]))
    np.testing.assert_array_equal(sub, dists[:, [3, 7]])
    with pytest.raises(KeyError):
        eng.pairwise(QUERIES, ids=np.asarray([99]))
    # hamming: integer metric, exact equality end to end
    enh = QueryEngine(P, metric="hamming")
    enh.add_dense(X[:30])
    _, dh = enh.pairwise(QUERIES)
    hi, hv = enh.topk(QUERIES, 3)
    np.testing.assert_array_equal(
        np.take_along_axis(dh, hi.astype(np.int64), axis=1), hv)


# ---------------------------------------------------------------------------
# property tests: incremental == fresh, snapshot round-trip (tests/_hyp)
# ---------------------------------------------------------------------------


def _mutate(eng, rng, chunks):
    """Apply a random interleaving of chunked adds, removes, compactions."""
    pos = 0
    for c in chunks:
        take = X[pos: pos + c]
        if len(take) == 0:
            break
        eng.add_dense(take)
        pos += len(take)
        alive = eng.ids()
        if len(alive) > 3 and rng.random() < 0.7:
            k = int(rng.integers(1, max(2, len(alive) // 3)))
            eng.remove(rng.choice(alive, size=k, replace=False))
        if rng.random() < 0.3:
            eng.compact()
    return eng


@settings(max_examples=5, deadline=None)
@given(st.integers(0, 2**16), st.lists(st.integers(1, 14), min_size=1,
                                       max_size=6))
def test_incremental_build_equals_fresh_batch(seed, chunks):
    """An index built in random-sized chunks with interleaved deletes and
    compactions answers bit-identically to one built fresh from the
    surviving vectors."""
    rng = np.random.default_rng(seed)
    eng = _mutate(QueryEngine(P, band_rows=16), rng, chunks)
    survivors = eng.ids()
    if len(survivors) == 0:
        return
    fresh = QueryEngine(P, band_rows=16)
    fresh.add_dense(X[survivors])  # fresh ids = positions into survivors
    gi, gv = eng.topk(QUERIES, 5)
    fi, fv = fresh.topk(QUERIES, 5)
    np.testing.assert_array_equal(gi, survivors[fi])
    np.testing.assert_array_equal(gv, fv)
    r = float(np.percentile(gv, 60) + 0.37) if gv.size else 1.0
    ra = eng.radius(QUERIES, r)
    rb = fresh.radius(QUERIES, r)
    for a, b in zip(ra, rb):
        np.testing.assert_array_equal(a, survivors[b])


@settings(max_examples=4, deadline=None)
@given(st.integers(0, 2**16))
def test_snapshot_restore_roundtrips_exactly(seed):
    rng = np.random.default_rng(seed)
    chunks = [int(c) for c in rng.integers(1, 14, size=4)]
    eng = _mutate(QueryEngine(P, band_rows=16), rng, chunks)
    with tempfile.TemporaryDirectory() as td:
        eng.save(td, step=7)
        back = QueryEngine.restore(td)
    # store state is reproduced bit-for-bit, tombstones included
    a, b = eng.store.state_tree(), back.store.state_tree()
    assert set(a) == set(b)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)
    assert eng.store.state_meta() == back.store.state_meta()
    gi, gv = eng.topk(QUERIES, 4)
    ri, rv = back.topk(QUERIES, 4)
    np.testing.assert_array_equal(gi, ri)
    np.testing.assert_array_equal(gv, rv)


# ---------------------------------------------------------------------------
# store mechanics, cache, edge cases
# ---------------------------------------------------------------------------


def test_store_capacity_doubles_and_compacts():
    store = SketchStore(D)
    assert store.capacity == 8
    store.add(jnp.asarray(SK[:20]))
    assert store.capacity == 32 and store.size == 20 and len(store) == 20
    store.remove(np.arange(5, 19))
    assert len(store) == 6 and store.size == 20  # tombstones keep slots
    store.compact()
    assert store.size == 6 and store.capacity == 8
    np.testing.assert_array_equal(store.ids(), [0, 1, 2, 3, 4, 19])
    mat, n, ids = store.gather_alive()
    assert n == 6 and mat.shape[0] == 8
    np.testing.assert_array_equal(np.asarray(mat[:6]), SK[[0, 1, 2, 3, 4, 19]])


def test_store_errors():
    store = SketchStore(D)
    store.add(jnp.asarray(SK[:4]))
    with pytest.raises(KeyError):
        store.remove([11])
    store.remove([2])
    with pytest.raises(KeyError):  # double-remove
        store.remove([2])
    with pytest.raises(ValueError):  # duplicate batch
        store.remove([0, 0])
    with pytest.raises(ValueError):  # wrong packed width
        store.add(jnp.zeros((2, 3), jnp.int32))
    with pytest.raises(ValueError):  # over-declared valid count
        store.add(jnp.asarray(SK[:4]), n_valid=9)
    with pytest.raises(ValueError):  # negative valid count
        store.add(jnp.asarray(SK[:4]), n_valid=-3)
    with pytest.raises(ValueError):
        threshold_pairs(SK[:4], SK[:8], d=D, threshold=1.0, n_valid=6)
    with pytest.raises(ValueError):
        topk_rows(SK[:4], SK[:8], 2, d=D, m_valid=9)
    eng = QueryEngine(P)
    with pytest.raises(ValueError):  # wrong dense width
        eng.add_dense(np.zeros((2, 7), np.int32))
    with pytest.raises(ValueError):  # out-of-vocab COO index
        eng.add_sparse(np.full((1, 3), N_DIMS, np.int32),
                       np.ones((1, 3), np.int32))
    with pytest.raises(ValueError):
        QueryEngine(P, metric="cosine")


def test_empty_and_clamped_queries():
    eng = QueryEngine(P)
    ids, vals = eng.topk(QUERIES, 3)  # empty store
    assert ids.shape == (5, 0) and vals.shape == (5, 0)
    assert all(len(a) == 0 for a in eng.radius(QUERIES, 10.0))
    eng.add_dense(X[:2])
    ids, vals = eng.topk(QUERIES, 9)  # k clamps to n_alive
    assert ids.shape == (5, 2)
    ids0, _ = eng.topk(X[:0], 3)  # empty query batch
    assert ids0.shape == (0, 0)
    assert eng.radius(X[:0], 5.0) == []


@pytest.mark.parametrize("metric", ["cham", "hamming"])
def test_empty_traffic_is_well_typed_both_metrics(metric):
    """API-boundary hardening: n=0 stores and 0-row query batches answer
    as explicit host-side fast paths with well-typed empties — topk,
    radius AND pairwise — instead of riding pow2 padding of degenerate
    shapes through the kernels.  Validation does not weaken at q=0."""
    eng = QueryEngine(P, metric=metric)
    q0, q2 = X[:0], QUERIES[:2]
    # empty engine, live queries
    ids, vals = eng.topk(q2, 5)
    assert ids.shape == (2, 0) and ids.dtype == np.int64
    assert vals.shape == (2, 0) and vals.dtype == np.float32
    assert [len(h) for h in eng.radius(q2, 10.0)] == [0, 0]
    pids, pd = eng.pairwise(q2)
    assert pids.shape == (0,) and pd.shape == (2, 0)
    assert pd.dtype == np.float32
    with pytest.raises(KeyError):  # explicit ids on an empty store
        eng.pairwise(q2, ids=[0])
    # empty engine, empty batch
    pids, pd = eng.pairwise(q0)
    assert pids.shape == (0,) and pd.shape == (0, 0)
    # populated engine, 0-row batch
    stored = eng.add_dense(X[:6])
    ids, vals = eng.topk(q0, 5)
    assert ids.shape == (0, 0) and vals.shape == (0, 0)
    assert eng.radius(q0, 10.0) == []
    pids, pd = eng.pairwise(q0)
    np.testing.assert_array_equal(pids, np.sort(stored))
    assert pd.shape == (0, 6) and pd.dtype == np.float32
    pids, pd = eng.pairwise(q0, ids=stored[:2])
    assert pd.shape == (0, 2) and len(pids) == 2
    with pytest.raises(ValueError):  # duplicate ids still a caller bug
        eng.pairwise(q0, ids=[stored[0], stored[0]])
    with pytest.raises(KeyError):  # membership still enforced at q=0
        eng.pairwise(q0, ids=[10 ** 9])


def test_result_cache_hits_and_invalidates():
    eng = QueryEngine(P, cache_entries=4)
    eng.add_dense(X[:32])
    a = eng.topk(QUERIES, 4)
    assert (eng.cache_hits, eng.cache_misses) == (0, 1)
    b = eng.topk(QUERIES, 4)
    assert eng.cache_hits == 1
    np.testing.assert_array_equal(a[0], b[0])
    eng.radius(QUERIES, 50.0)
    eng.radius(QUERIES, 50.0)
    assert eng.cache_hits == 2
    eng.add_dense(X[32:34])  # mutation invalidates via version bump
    c = eng.topk(QUERIES, 4)
    assert eng.cache_misses == 3
    alive = eng.ids()
    ref_i, ref_v = topk_rows(SK[:5], SK[alive], 4, d=D)
    np.testing.assert_array_equal(c[0], alive[ref_i])
    np.testing.assert_array_equal(c[1], ref_v)
    # callers may mutate returned (writable) arrays without corrupting the
    # cache (the distance array is a read-only jax view — unmutable anyway)
    c[0].fill(-7)
    d2 = eng.topk(QUERIES, 4)
    np.testing.assert_array_equal(d2[0], alive[ref_i])
    np.testing.assert_array_equal(d2[1], ref_v)
    hits = eng.radius(QUERIES, 50.0)
    for h in hits:
        h.fill(-1)
    for h, ref in zip(eng.radius(QUERIES, 50.0),
                      _radius_ref(SK[:5], SK[alive], alive, 50.0, "cham")):
        np.testing.assert_array_equal(h, ref)


def test_topk_cache_hit_skips_gather_and_layout(monkeypatch):
    """An LRU hit must be O(1): no store gather, no banded layout, no
    device work — only the key bytes and the cached copy."""
    eng = QueryEngine(P, cache_entries=4)
    eng.add_dense(X[:40])
    a = eng.topk(QUERIES, 3)  # miss: builds the layout, runs the scan

    def _boom(what):
        def fn(*args, **kwargs):
            raise AssertionError(f"{what} touched on a cache hit")
        return fn

    monkeypatch.setattr(eng.store, "gather_alive", _boom("gather_alive"))
    monkeypatch.setattr(eng, "_banded_layout", _boom("_banded_layout"))
    monkeypatch.setattr(eng, "_layout", _boom("_layout"))
    b = eng.topk(QUERIES, 3)
    assert eng.cache_hits == 1
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])


@pytest.mark.parametrize("metric", ["cham", "hamming"])
def test_banded_topk_certificate_prunes_but_never_drops(metric, monkeypatch):
    """A narrow query's progressive expansion stops at the certificate
    after touching a fraction of the store, yet returns exactly the full
    scan's answer; a diverse batch degrades gracefully to a full visit."""
    from repro.core import allpairs as ap
    from repro.core.packing import np_popcount_rows

    eng = QueryEngine(P, metric=metric, band_rows=8, cache_entries=0)
    eng.add_dense(X)
    visited = []
    orig = ap._topk_tiles_impl

    def counting(a, b, rank, tiles, count, **kw):
        visited.append(int(count) * kw["tile"])  # rows of the listed tiles
        return orig(a, b, rank, tiles, count, **kw)

    monkeypatch.setattr(ap, "_topk_tiles_impl", counting)
    weights = np_popcount_rows(SK)
    qi = int(np.argmin(weights))  # narrowest sketch: strongest certificate
    got_i, got_v = eng.topk(X[qi: qi + 1], 3)
    assert 0 < sum(visited) < len(X)  # the certificate actually fired
    monkeypatch.setattr(ap, "_topk_tiles_impl", orig)
    ref_i, ref_v = topk_rows(SK[qi: qi + 1], SK, 3, d=D, metric=metric)
    np.testing.assert_array_equal(got_i, ref_i)
    np.testing.assert_array_equal(got_v, ref_v)
    # the full query mix (diverse weights) still answers exactly
    got5 = eng.topk(QUERIES, 7)
    ref5 = topk_rows(SK[:5], SK, 7, d=D, metric=metric)
    np.testing.assert_array_equal(got5[0], ref5[0])
    np.testing.assert_array_equal(got5[1], ref5[1])


class _Expired:
    expired = True


@pytest.mark.parametrize("case", ["plain", "tombstones", "init_kth",
                                  "deadline"])
@pytest.mark.parametrize("metric", ["cham", "hamming"])
def test_in_place_walk_equals_full_scan_in_id_order(metric, case):
    """The walk scores each round's bands in place, as a tile list, and
    returns what the full scan over the alive rows in id order returns:
    with copies of rows straddling band cuts (equal distances across
    tiles resolve to the lower id), after tombstones reach the layout
    through `refresh_alive`, under an `init_kth` bound at the true k-th
    distance, and under an expired deadline (then: the full scan over the
    first round's bands, those the weight bound cannot separate)."""
    from repro.core.allpairs import prune_score_host
    from repro.core.packing import np_popcount_rows, pad_rows_pow2

    k, band_rows = 5, 8
    x = np.concatenate([X[:40], np.repeat(X[40:52], 3, axis=0)])
    sk = np.asarray(sketch_dense(P, jnp.asarray(x)))
    eng = QueryEngine(P, metric=metric, band_rows=band_rows,
                      merge_ratio=None, cache_entries=0)
    ids = eng.add_dense(x)
    layout = eng._banded_layout()
    pos = np.argsort(layout.ids)  # id -> layout position
    copies = pos[ids[40:]].reshape(12, 3) // band_rows
    assert (copies.min(axis=1) < copies.max(axis=1)).any()  # cut copies
    if case == "tombstones":
        eng.remove(ids[[0, 41, 45, 60, 70]])
        eng.sync_layout()
        assert eng._banded_layout() is layout  # no rebuild
        assert layout.n_alive == len(x) - 5
        assert (np.asarray(layout.rank)[0, pos[ids[[0, 41]]]] == -1).all()
    q = sk[[41, 44, 50, 3]]
    qw = np_popcount_rows(q)
    alive = eng.ids()
    if case == "deadline":
        qs = prune_score_host(qw, D, metric)
        gap = np.maximum(np.maximum(layout.band_lo[None] - qs[:, None],
                                    qs[:, None] - layout.band_hi[None]), 0)
        first = np.flatnonzero(gap.min(axis=0) <= 0)
        assert 0 < len(first) < layout.n_bands
        rows = np.concatenate([np.arange(b * band_rows, (b + 1) * band_rows)
                               for b in first])
        alive = np.intersect1d(alive, layout.ids[rows[rows < layout.n]])
    ref_i, ref_v = topk_rows(q, sk[alive], k, d=D, metric=metric)
    info = {}
    got_i, got_v = layout.topk(
        pad_rows_pow2(jnp.asarray(q)), qw, k, q_valid=len(q),
        info_out=info,
        init_kth=ref_v[:, -1] if case == "init_kth" else None,
        deadline=_Expired() if case == "deadline" else None)
    np.testing.assert_array_equal(got_i, alive[ref_i])
    np.testing.assert_array_equal(got_v, ref_v)
    assert info["partial"] == (case == "deadline")


def test_banded_layout_prunes_but_never_drops():
    """With tiny bands, many get pruned for a small radius, yet the result
    equals the unpruned batch reference."""
    eng = QueryEngine(P, band_rows=8)
    eng.add_dense(X)
    layout = eng._banded_layout()
    assert isinstance(layout, BandedLayout) and layout.n_bands == 12
    # a single narrow query with a tight radius reaches only a few bands
    import repro.core.packing as packing
    q = X[2:3]
    r = 10.0
    weights = np.asarray(packing.popcount_rows(jnp.asarray(SK[2:3])))
    mask = layout.candidate_bands(weights, r)
    assert 0 < mask.sum() < layout.n_bands  # pruning actually happened
    got = eng.radius(q, r)
    want = _radius_ref(SK[2:3], SK, np.arange(96, dtype=np.int64), r, "cham")
    np.testing.assert_array_equal(got[0], want[0])
    # wide query mix still agrees with the unpruned batch reference
    got5 = eng.radius(QUERIES, 25.0)
    want5 = _radius_ref(SK[:5], SK, np.arange(96, dtype=np.int64), 25.0,
                        "cham")
    for a, b in zip(got5, want5):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# tiered layout: O(delta) serving after mutations (DESIGN.md 8.5)
# ---------------------------------------------------------------------------


def _check_exact(eng, jmap=None):
    """topk + radius of `eng` vs the batch engine on the alive membership.
    `jmap` maps external id -> row of X/SK (default: identity)."""
    alive = eng.ids()
    rows = alive if jmap is None else np.asarray([jmap[i] for i in alive])
    data_sk = SK[rows]
    ref_i, ref_v = topk_rows(SK[:3], data_sk, 5, d=D, metric=eng.metric)
    got_i, got_v = eng.topk(X[:3], 5)
    np.testing.assert_array_equal(got_i, alive[ref_i])
    np.testing.assert_array_equal(got_v, ref_v)
    r = float(np.percentile(ref_v, 60) + 0.37) if ref_v.size else 1.0
    got_r = eng.radius(X[:3], r)
    want_r = _radius_ref(SK[:3], data_sk, alive, r, eng.metric)
    for a, b in zip(got_r, want_r):
        np.testing.assert_array_equal(a, b)


def test_tiered_layout_serves_delta_without_rebuild():
    """The load-bearing tentpole property: after the base tier is built,
    adds land in the delta tier and removes in the alive masks — the base
    BandedLayout object SURVIVES the mutation (no O(N log N) rebuild), yet
    every answer stays bit-identical to a fresh batch build."""
    eng = QueryEngine(P, band_rows=16, merge_ratio=0.5, cache_entries=0)
    jmap = {}

    def add(rows):
        for i, j in zip(eng.add_dense(X[rows]), rows):
            jmap[int(i)] = int(j)

    add(np.arange(64))
    eng.topk(QUERIES, 5)  # first query builds the base tier
    lay = eng._tiered
    assert isinstance(lay, TieredLayout) and lay.n_merges == 0
    base0 = lay.base
    assert base0.n == 64 and lay.delta_n == 0

    add(np.arange(64, 80))  # 16 live delta <= 0.5 * 64: no merge
    _check_exact(eng, jmap)
    assert eng._tiered.base is base0, "add must not rebuild the base tier"
    assert eng._tiered.delta_n == 16 and eng._tiered.n_merges == 0

    # removes thread through per-tier alive masks — still no rebuild
    eng.remove([64, 3])  # one delta row, one base row
    _check_exact(eng, jmap)
    assert eng._tiered.base is base0
    assert eng._tiered.delta_n == 15 and eng._tiered.base.n_alive == 63
    assert eng.stats()["delta_rows"] == 15

    # an unmutated re-query syncs for free: same layout, same base
    _check_exact(eng, jmap)
    assert eng._tiered.base is base0

    # the size-ratio policy folds the tiers once delta outgrows its share
    add(np.arange(40))
    _check_exact(eng, jmap)
    assert eng._tiered.base is not base0
    assert eng._tiered.delta_n == 0 and eng._tiered.n_merges == 1

    # compact() bumps the slot epoch: the next query rebuilds and serves on
    eng.remove(eng.ids()[:5])
    eng.compact()
    _check_exact(eng, jmap)
    assert eng._tiered.delta_n == 0


def test_merge_ratio_zero_rebuilds_per_mutation():
    """merge_ratio=0 is the pre-tiered behaviour (the bench baseline):
    every mutation folds immediately, so the delta tier never persists."""
    eng = QueryEngine(P, band_rows=16, merge_ratio=0.0, cache_entries=0)
    eng.add_dense(X[:32])
    eng.topk(QUERIES, 4)
    base0 = eng._tiered.base
    eng.add_dense(X[32:40])
    _check_exact(eng)
    assert eng._tiered.base is not base0 and eng._tiered.delta_n == 0
    # remove-only mutations rebuild too — the old path had no alive masks
    base1 = eng._tiered.base
    eng.remove([5])
    _check_exact(eng)
    assert eng._tiered.base is not base1
    assert eng._tiered.base.n_alive == eng._tiered.base.n == 39


@settings(max_examples=6, deadline=None)
@given(st.integers(0, 2**16), st.integers(0, 2))
def test_mutate_query_interleaving_bit_identity(seed, ratio_idx):
    """Random add/remove/compact between EVERY query: topk and radius stay
    bit-identical to the batch engine across tier boundaries, merges, and
    cache hits, under both metrics and all three merge policies."""
    ratio = (0.0, 0.5, None)[ratio_idx]
    metric = ("cham", "hamming")[seed % 2]
    rng = np.random.default_rng(seed)
    eng = QueryEngine(P, metric=metric, band_rows=16, merge_ratio=ratio,
                      cache_entries=8)
    jmap: dict[int, int] = {}
    pos = 0
    saw_delta = False
    for _ in range(5):
        op = rng.random()
        if op < 0.55 or len(eng) < 4:
            c = int(rng.integers(1, 14))
            rows = np.arange(pos, pos + c) % len(X)
            pos += c
            for i, j in zip(eng.add_dense(X[rows]), rows):
                jmap[int(i)] = int(j)
        elif op < 0.85:
            alive = eng.ids()
            kk = int(rng.integers(1, max(2, len(alive) // 2)))
            eng.remove(rng.choice(alive, size=kk, replace=False))
        else:
            eng.compact()
        _check_exact(eng, jmap)
        _check_exact(eng, jmap)  # immediate re-ask: cache-hit path agrees
        saw_delta = saw_delta or bool(eng._tiered and eng._tiered.delta_n)
    if ratio is None and pos > 0:
        # with auto-merge off, at least one query must have been served
        # across a live tier boundary (first add builds base, later adds
        # can only leave via compact)
        assert saw_delta or eng._tiered is None or eng._tiered.n_merges > 0


# ---------------------------------------------------------------------------
# API-boundary regressions: k < 0, r <= 0, duplicate ids, stale gathers
# ---------------------------------------------------------------------------


def test_topk_negative_k_raises():
    eng = QueryEngine(P)
    eng.add_dense(X[:8])
    with pytest.raises(ValueError, match="k must be >= 0"):
        eng.topk(QUERIES, -1)
    with pytest.raises(ValueError, match="k must be >= 0"):
        eng.topk_packed(jnp.asarray(SK[:2]), -3)
    ids, vals = eng.topk(QUERIES, 0)  # k = 0 stays a valid empty query
    assert ids.shape == (5, 0) and vals.shape == (5, 0)


def test_radius_nonpositive_r_returns_empty():
    """dist >= 0 and the test is strict, so r <= 0 is a documented
    empty-results contract (not an error) — including on an empty store."""
    eng = QueryEngine(P)
    assert all(len(a) == 0 for a in eng.radius(QUERIES, -3.0))
    eng.add_dense(X[:16])
    for r in (-3.0, 0.0):
        out = eng.radius(QUERIES, r)
        assert len(out) == 5 and all(len(a) == 0 for a in out)
    out = eng.radius_packed(jnp.asarray(SK[:2]), -1.0)
    assert len(out) == 2 and all(len(a) == 0 for a in out)


def test_pairwise_duplicate_ids_raise():
    """Consistent with SketchStore.remove: duplicate ids are a caller bug,
    not a request for duplicated distance columns."""
    eng = QueryEngine(P)
    eng.add_dense(X[:8])
    with pytest.raises(ValueError, match="duplicate ids"):
        eng.pairwise(QUERIES, ids=np.asarray([3, 3]))
    sub_ids, sub = eng.pairwise(QUERIES, ids=np.asarray([3, 5]))
    np.testing.assert_array_equal(sub_ids, [3, 5])


def test_gather_alive_stale_view_is_rejected(monkeypatch):
    """A view held across a mutation must fail the cheap version check with
    a clear message — not surface as jax's 'Array has been deleted' after
    a donated append."""
    store = SketchStore(D)
    store.add(jnp.asarray(SK[:8]))
    view = store.gather_alive()
    store.check_fresh(view)  # fresh: fine
    assert view.n_alive == 8 and view.version == store.version
    store.add(jnp.asarray(SK[8:12]))
    with pytest.raises(RuntimeError, match="stale gather"):
        store.check_fresh(view)
    # engine consumer: pairwise guards its gather before the device compute
    eng = QueryEngine(P)
    eng.add_dense(X[:8])
    stale = eng.store.gather_alive()
    eng.add_dense(X[8:16])
    monkeypatch.setattr(eng.store, "gather_alive", lambda: stale)
    with pytest.raises(RuntimeError, match="stale gather"):
        eng.pairwise(QUERIES)
    with pytest.raises(RuntimeError, match="stale gather"):
        # the id-subset branch gathers from the view too: the guard must
        # fire before that dereference, not just before the kernel call
        eng.pairwise(QUERIES, ids=np.asarray([1, 2]))


def test_dedup_by_sketch_metric_param():
    """Ingest dedups in the ENGINE's metric: hamming thresholds group
    exactly the sketch-identical rows at threshold < 1."""
    from repro.data.dedup import dedup_by_sketch

    sk = np.concatenate([SK[:10], SK[:10]])
    res = dedup_by_sketch(sk, D, threshold=0.5, metric="hamming")
    assert res.n_removed == 10
    np.testing.assert_array_equal(res.group_ids[:10], res.group_ids[10:])


def test_ingest_documents_stream():
    from repro.data.dedup import docs_to_categorical
    from repro.data.pipeline import synthetic_documents

    vocab = 2048
    params = CabinParams.create(vocab, D, seed=5)
    eng = QueryEngine(params)
    gen = synthetic_documents(vocab, seed=5, dup_fraction=0.3)
    docs = [next(gen) for _ in range(90)]
    got = ingest_documents(eng, docs, window=32, dedup_threshold=40.0)
    assert got.shape == (90,)
    dropped = int((got == -1).sum())
    assert dropped > 0  # the stream really contains near-duplicates
    assert len(eng) == 90 - dropped
    np.testing.assert_array_equal(np.sort(got[got >= 0]), eng.ids())
    # no-dedup ingest keeps everything; max_docs consumes EXACTLY that many
    # docs from the caller's iterator (nothing pulled and dropped)
    eng2 = QueryEngine(params)
    it = iter(docs)
    got2 = ingest_documents(eng2, it, window=32, max_docs=50)
    assert got2.shape == (50,) and len(eng2) == 50
    leftover = list(it)
    assert len(leftover) == 40
    np.testing.assert_array_equal(leftover[0], docs[50])
    # ingested docs are queryable: each doc's nearest neighbour is itself
    idx_q, val_q = docs_to_categorical(docs[:6], vocab)
    ids, vals = eng2.topk((idx_q, val_q), 1)
    np.testing.assert_array_equal(ids[:, 0], got2[:6])


@settings(max_examples=5, deadline=None)
@given(st.integers(0, 2**16),
       st.lists(st.integers(0, 6), min_size=6, max_size=18))
def test_lru_accounting_matches_shadow_model(seed, ops):
    """The LRU's hit/miss accounting is EXACT against an independent shadow
    model of its policy (key = (op, args, store version, query bytes);
    capacity eviction in least-recent order; mutations invalidate via the
    version in the key; sync_layout touches nothing) — both the engine's
    python attrs and the repro.obs counter mirror, op by op."""
    from collections import OrderedDict

    rng = np.random.default_rng(seed)
    cap = 3
    eng = QueryEngine(P, cache_entries=cap, band_rows=16)
    eng.add_dense(X[:24])
    shadow: OrderedDict = OrderedDict()
    hits = misses = 0

    def probe(key):
        nonlocal hits, misses
        if key in shadow:
            shadow.move_to_end(key)
            hits += 1
        else:
            misses += 1
            shadow[key] = True
            if len(shadow) > cap:
                shadow.popitem(last=False)

    next_row = 24
    for op in ops:
        if op <= 2:  # topk on one of three fixed query batches
            q = X[8 * op: 8 * op + 3]
            eng.topk(q, 4)
            probe(("topk", min(4, len(eng)), eng.store.version, op))
        elif op == 3:  # radius (its own key space, same cache)
            eng.radius(QUERIES, 50.0)
            probe(("radius", 50.0, eng.store.version, "q"))
        elif op == 4:  # add: version bump invalidates every live key
            eng.add_dense(X[next_row % 64: next_row % 64 + 1])
            next_row += 1
        elif op == 5:  # remove one alive row (keep the store non-empty)
            alive = eng.ids()
            if len(alive) > 1:
                i = int(rng.integers(len(alive)))
                eng.remove(alive[i: i + 1])
        else:  # sync_layout: maintenance, not traffic — no cache effect
            eng.sync_layout()
        assert (eng.cache_hits, eng.cache_misses) == (hits, misses)
        assert len(eng._cache) == len(shadow)
        if not eng.obs.is_null:  # the obs mirror counts the same events
            snap = eng.obs_snapshot()
            assert snap.get("engine_cache_hits_total", 0) == hits
            assert snap.get("engine_cache_misses_total", 0) == misses
            assert snap["engine_lru_entries"] == float(len(shadow))
