"""Drift-tolerant serving: versioned specs, lazy re-sketch migration.

The load-bearing property (DESIGN.md section 10): a sketch is a PURE
function of (raw row, spec), so a COMPLETED migration must be bit-identical
to an engine freshly built at the new spec over the same membership — same
store buffers, same ids, same query answers, under both metrics, over any
add/remove/compact history, with mutations landing mid-flight.  While the
migration is in flight, serving answers must equal the (value, id)-lex
merge of per-store reference answers, each computed in its own sketch
space by the batch primitives.
"""

import numpy as np
import pytest

from tests._hyp import given, settings, st

from repro.core import theory, topk_rows, threshold_pairs
from repro.core.cabin import CabinParams
from repro.core.cham import binhamming_from_stats
from repro.core.packing import np_popcount_rows, pad_rows_pow2
from repro.index import (Migration, QueryEngine, RawArchive, SketchSpec,
                         merge_topk_parts)

N_DIMS = 300
D_OLD = 64
D_NEW = 128
P_OLD = CabinParams(n_dims=N_DIMS, sketch_dim=D_OLD, psi_seed=11, pi_seed=12)
P_NEW = CabinParams(n_dims=N_DIMS, sketch_dim=D_NEW, psi_seed=11, pi_seed=12)


def _rows(n, seed, lo=8, hi=30):
    rng = np.random.default_rng(seed)
    x = np.zeros((n, N_DIMS), np.int32)
    for r in range(n):
        nnz = rng.integers(lo, hi + 1)
        cols = rng.choice(N_DIMS, size=nnz, replace=False)
        x[r, cols] = rng.integers(1, 6, size=nnz)
    return x


def _fresh_at_new_spec(x_by_id, metric):
    """Reference: batch-build an engine at the new spec holding exactly the
    rows in `x_by_id` (an id -> dense row dict), preserving ids via the
    add-then-remove trick (ids are assignment order)."""
    eng = QueryEngine(P_NEW, metric=metric, cache_entries=0)
    hi = max(x_by_id) + 1
    full = np.zeros((hi, N_DIMS), np.int32)
    for i, row in x_by_id.items():
        full[i] = row
    eng.add_dense(full)
    gone = sorted(set(range(hi)) - set(x_by_id))
    if gone:
        eng.remove(np.asarray(gone, np.int64))
    return eng


# ---------------------------------------------------------------------------
# SketchSpec / RawArchive units
# ---------------------------------------------------------------------------


def test_spec_successor_and_meta_roundtrip():
    spec = SketchSpec(0, P_OLD)
    nxt = spec.successor(P_NEW)
    assert nxt.version == 1 and nxt.d == D_NEW
    assert SketchSpec.from_meta(nxt.meta()) == nxt
    bad = CabinParams(n_dims=N_DIMS + 1, sketch_dim=D_NEW,
                      psi_seed=11, pi_seed=12)
    with pytest.raises(ValueError):
        spec.successor(bad)


def test_raw_archive_roundtrip_and_dense_coo_equivalence():
    x = _rows(9, seed=0)
    arc = RawArchive()
    arc.put_dense(np.arange(9, dtype=np.int64), x)
    # batch() returns trimmed padded-COO that sketches like the dense rows
    idx, val = arc.batch([3, 5])
    dense_back = np.zeros((2, N_DIMS), np.int32)
    np.put_along_axis(dense_back, idx, val, axis=1)
    assert np.array_equal(dense_back, x[[3, 5]])
    arc.drop([4])
    assert 4 not in arc and len(arc) == 8
    assert arc.missing([2, 4, 99]).tolist() == [4, 99]
    with pytest.raises(KeyError):
        arc.batch([4])
    # snapshot roundtrip preserves exactly the live rows
    arc2 = RawArchive.from_state(arc.state_tree())
    assert len(arc2) == 8 and 4 not in arc2
    i1, v1 = arc.batch([0, 8])
    i2, v2 = arc2.batch([0, 8])
    assert np.array_equal(i1, i2) and np.array_equal(v1, v2)


def test_merge_topk_parts_equals_single_partition():
    """Merging a split partition reproduces the unsplit answer — the rule
    that makes cross-store serving exact."""
    rng = np.random.default_rng(3)
    q, k = 4, 5
    vals = rng.random((q, 12)).astype(np.float32)
    ids = np.tile(np.arange(12, dtype=np.int64), (q, 1))
    order = np.argsort(vals, axis=1, kind="stable")
    ref_ids = np.take_along_axis(ids, order, axis=1)[:, :k]
    ref_vals = np.take_along_axis(vals, order, axis=1)[:, :k]
    parts = []
    for sl in (slice(0, 7), slice(7, 12)):  # per-partition exact k'-best
        o = np.argsort(vals[:, sl], axis=1, kind="stable")[:, :k]
        parts.append((np.take_along_axis(ids[:, sl], o, axis=1),
                      np.take_along_axis(vals[:, sl], o, axis=1)))
    got_ids, got_vals = merge_topk_parts(k, parts)
    assert np.array_equal(got_ids, ref_ids)
    assert np.array_equal(got_vals, ref_vals)


# ---------------------------------------------------------------------------
# Completed migration == fresh build (the tentpole bit-identity)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("metric", ["cham", "hamming"])
def test_completed_migration_bit_identical_to_fresh_build(metric):
    x = _rows(40, seed=1)
    eng = QueryEngine(P_OLD, metric=metric, cache_entries=0)
    ids = eng.add_dense(x[:32])
    eng.remove(ids[5:9])
    eng.compact()
    eng.migrate(new_params=P_NEW, batch_rows=7, drive="manual")
    mid_adds = eng.add_dense(x[32:])          # land in the new-spec tier
    eng.remove([int(mid_adds[0])])
    eng.migrate_all()
    assert not eng.migrating and eng.d == D_NEW and eng.spec.version == 1

    alive = {int(i): x[i] for i in eng.ids()}
    ref = _fresh_at_new_spec(alive, metric)
    # store-level identity: same packed bits in the same slots
    m1, n1, i1 = eng.store.gather_alive()
    m2, n2, i2 = ref.store.gather_alive()
    assert n1 == n2 and np.array_equal(i1, i2)
    assert np.array_equal(np.asarray(m1[:n1]), np.asarray(m2[:n2]))
    # query-level identity
    q = _rows(5, seed=2)
    for k in (1, 4, 50):
        a_ids, a_d = eng.topk(q, k)
        b_ids, b_d = ref.topk(q, k)
        assert np.array_equal(a_ids, b_ids)
        assert np.array_equal(a_d, b_d)
    r = 30.0 if metric == "hamming" else 60.0
    for a, b in zip(eng.radius(q, r), ref.radius(q, r)):
        assert np.array_equal(a, b)


@settings(max_examples=6, deadline=None)
@given(st.lists(st.integers(0, 99), min_size=3, max_size=10),
       st.integers(0, 1))
def test_migration_identity_under_arbitrary_history(ops, metric_pick):
    """Any interleaving of add / remove / compact / migration batches still
    lands bit-identical to the fresh build — including histories where
    mutations race the migration itself."""
    metric = ("cham", "hamming")[metric_pick]
    rng = np.random.default_rng(sum(ops) + metric_pick)
    eng = QueryEngine(P_OLD, metric=metric, cache_entries=0)
    x_by_id: dict[int, np.ndarray] = {}
    next_seed = 100

    def add(n):
        nonlocal next_seed
        rows = _rows(n, seed=next_seed)
        next_seed += 1
        for i, row in zip(eng.add_dense(rows), rows):
            x_by_id[int(i)] = row

    add(12)
    eng.migrate(new_params=P_NEW, batch_rows=3, drive="manual")
    for op in ops:
        which = op % 4
        if which == 0:
            add(int(rng.integers(1, 5)))
        elif which == 1 and len(x_by_id) > 2:
            gone = rng.choice(sorted(x_by_id), size=2, replace=False)
            eng.remove(np.sort(gone))
            for g in gone:
                del x_by_id[int(g)]
        elif which == 2:
            eng.compact()
        else:
            eng.migration_step()
    eng.migrate_all()
    ref = _fresh_at_new_spec(x_by_id, metric)
    assert np.array_equal(eng.ids(), ref.ids())
    q = _rows(3, seed=99)
    a_ids, a_d = eng.topk(q, 5)
    b_ids, b_d = ref.topk(q, 5)
    assert np.array_equal(a_ids, b_ids) and np.array_equal(a_d, b_d)


# ---------------------------------------------------------------------------
# Mid-migration serving: exact w.r.t. per-store references
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("metric", ["cham", "hamming"])
def test_mid_migration_topk_and_radius_exact(metric):
    """Mid-flight answers equal the (value, id)-lex merge of per-store
    reference answers computed by the BATCH primitives, each store in its
    own sketch space — the defined exactness contract while rows live
    under two specs (for "cham" both spaces estimate original-space HD, so
    the merged ranking is also semantically coherent)."""
    x = _rows(36, seed=4)
    eng = QueryEngine(P_OLD, metric=metric, cache_entries=0)
    ids = eng.add_dense(x[:28])
    eng.remove(ids[2:5])
    eng.migrate(new_params=P_NEW, batch_rows=6, drive="manual")
    eng.migration_step()                       # src + dst both non-empty
    eng.add_dense(x[28:])                      # fresh tier non-empty too
    mig = eng.migration
    assert len(mig.src) and len(mig.dst) and len(mig.fresh)

    q = _rows(4, seed=5)
    k = 6
    parts = []
    stores = [(mig.src, P_OLD), (mig.dst, P_NEW), (mig.fresh, P_NEW)]
    probe = QueryEngine(P_OLD, metric=metric, cache_entries=0)
    for store, params in stores:
        sk, nq = probe._sketch(q, params=params)
        mat, n, sids = store.gather_alive()
        # gather_alive rows are in id order, so topk_rows' lower-column
        # tie-break IS the (value, id)-lex rule the merge expects
        t_idx, t_vals = topk_rows(
            pad_rows_pow2(sk), mat, min(k, n), d=params.sketch_dim,
            metric=metric, m_valid=n)
        parts.append((sids[np.asarray(t_idx[:nq])].astype(np.int64),
                      np.asarray(t_vals[:nq])))
    ref_ids, ref_vals = merge_topk_parts(min(k, len(eng)), parts)

    got_ids, got_vals = eng.topk(q, k)
    assert np.array_equal(got_ids, ref_ids)
    assert np.array_equal(got_vals, ref_vals)

    r = 30.0 if metric == "hamming" else 60.0
    got_r = eng.radius(q, r)
    for qi in range(len(q)):
        ref_hits = []
        for store, params in stores:
            sk, nq = probe._sketch(q, params=params)
            mat, n, sids = store.gather_alive()
            pairs = threshold_pairs(
                pad_rows_pow2(sk), mat, d=params.sketch_dim, threshold=r,
                metric=metric, n_valid=nq, m_valid=n)
            ref_hits.append(sids[pairs[pairs[:, 0] == qi, 1]])
        ref_union = np.sort(np.concatenate(ref_hits))
        assert np.array_equal(got_r[qi], ref_union)


def test_mid_migration_packed_and_pairwise_guarded():
    eng = QueryEngine(P_OLD, cache_entries=0)
    eng.add_dense(_rows(10, seed=6))
    sk, _ = eng._sketch(_rows(2, seed=7))
    eng.migrate(new_params=P_NEW, batch_rows=4, drive="manual")
    with pytest.raises(RuntimeError, match="spec-ambiguous"):
        eng.topk_packed(sk, 3)
    with pytest.raises(RuntimeError, match="spec-ambiguous"):
        eng.radius_packed(sk, 10.0)
    with pytest.raises(RuntimeError, match="mid-migration"):
        eng.pairwise(_rows(2, seed=7))
    with pytest.raises(RuntimeError, match="raw"):
        eng.add_packed(np.asarray(sk))
    with pytest.raises(RuntimeError, match="already in flight"):
        eng.migrate(new_params=P_NEW)


def test_migrate_requires_raw_archive():
    eng = QueryEngine(P_OLD, keep_raw=False)
    eng.add_dense(_rows(4, seed=8))
    with pytest.raises(RuntimeError, match="keep_raw"):
        eng.migrate(new_params=P_NEW)
    # rows ingested packed without raw strand the migration too
    eng2 = QueryEngine(P_OLD, cache_entries=0)
    sk, _ = eng2._sketch(_rows(3, seed=8))
    eng2.add_packed(np.asarray(sk))
    with pytest.raises(RuntimeError, match="no raw archive entry"):
        eng2.migrate(new_params=P_NEW)


# ---------------------------------------------------------------------------
# Journal / resume, drift auto-trigger
# ---------------------------------------------------------------------------


def test_journaled_migration_resumes_identically(tmp_path):
    x = _rows(30, seed=9)
    journal = str(tmp_path / "journal")

    eng = QueryEngine(P_OLD, metric="cham", cache_entries=0)
    eng.add_dense(x)
    eng.save(journal, step=0)
    eng.migrate(new_params=P_NEW, batch_rows=8, drive="manual",
                journal_dir=journal, journal_every=1, journal_keep=10)
    eng.migration_step()
    eng.migration_step()
    # abandon the in-memory engine; resume purely from disk
    res = QueryEngine.restore(journal)
    assert res.migrating and res.migration.rows_migrated == 16
    assert np.array_equal(res.ids(), eng.ids())
    res.migrate_all()

    ref = _fresh_at_new_spec({int(i): x[i] for i in range(30)}, "cham")
    q = _rows(3, seed=10)
    a, av = res.topk(q, 5)
    b, bv = ref.topk(q, 5)
    assert np.array_equal(a, b) and np.array_equal(av, bv)


def test_drift_auto_trigger_and_auto_publish():
    """Dense rows whose nnz percentile exceeds the Theorem-1 bound for the
    current dim must auto-start a lazy migration to theory.sketch_dim of
    the observed percentile — and traffic alone must drive it to done."""
    p_small = CabinParams(n_dims=N_DIMS, sketch_dim=32,
                          psi_seed=11, pi_seed=12)
    eng = QueryEngine(p_small, auto_migrate=True, drift_delta=0.2,
                      drift_window=64, drift_pct=95.0, cache_entries=0)
    bound = theory.max_density_for_dim(32, 0.2)
    dense = _rows(80, seed=12, lo=bound + 4, hi=bound + 8)
    eng.add_dense(dense[:64])
    assert eng.migrating, "density over the bound must trigger a migration"
    target = eng.migration.new_spec.d
    assert target > 32
    # lazy drive: ordinary traffic advances it to publication
    for i in range(80):
        if not eng.migrating:
            break
        eng.topk(dense[:1], 1)
    assert not eng.migrating and eng.d == target
    # the published engine answers identically to a fresh build at the
    # auto-chosen params
    ref = QueryEngine(eng.params, metric="cham", cache_entries=0)
    ref.add_dense(dense[:64])
    a, av = eng.topk(dense[64:67], 4)
    b, bv = ref.topk(dense[64:67], 4)
    assert np.array_equal(a, b) and np.array_equal(av, bv)


def test_drift_migration_dim_suits_the_tpu_kernels(monkeypatch):
    """A drift migration picks a dim the Cabin kernels take: on a TPU,
    where an unaligned dim raises, sparse ingest keeps working after the
    engine migrates itself.  Dispatch is decided as on a TPU; the kernel
    then runs in interpret mode."""
    from repro.core import cabin

    decide = cabin.kernel_dispatch

    def as_on_tpu(sketch_dim, use_pallas):
        with monkeypatch.context() as m:
            m.setattr(cabin.jax, "default_backend", lambda: "tpu")
            return decide(sketch_dim, use_pallas)

    monkeypatch.setattr(cabin, "kernel_dispatch", as_on_tpu)
    # seeds of its own: the sketch jit must trace here, not hit a cache
    p = CabinParams(n_dims=N_DIMS, sketch_dim=128, psi_seed=71, pi_seed=72)
    eng = QueryEngine(p, auto_migrate=True, drift_delta=0.2,
                      drift_window=64, cache_entries=0)
    bound = theory.max_density_for_dim(128, 0.2)
    rng = np.random.default_rng(13)
    n, m = 96, bound + 8
    idx = np.stack([rng.choice(N_DIMS, size=m, replace=False)
                    for _ in range(n)]).astype(np.int32)
    val = rng.integers(1, 6, size=(n, m)).astype(np.int32)
    eng.add_sparse(idx[:64], val[:64])
    assert eng.migrating
    target = eng.migration.new_spec.d
    ids = eng.add_sparse(idx[64:], val[64:])  # lands in the fresh tier
    assert theory.sketch_dim(m, 0.2) % 128, "the theory's dim is unaligned"
    assert target % 128 == 0 and target >= theory.sketch_dim(m, 0.2)
    eng.migrate_all()
    assert eng.d == target and len(eng) == n
    top, _ = eng.topk((idx[64:67], val[64:67]), 1)
    assert np.array_equal(top[:, 0], ids[:3])


def test_auto_migrate_requires_keep_raw():
    with pytest.raises(ValueError, match="keep_raw"):
        QueryEngine(P_OLD, keep_raw=False, auto_migrate=True)


def test_max_density_for_dim_inverts_sketch_dim():
    for d in (32, 64, 256, 1024):
        s = theory.max_density_for_dim(d, 0.1)
        assert theory.sketch_dim(s, 0.1) <= d
        assert theory.sketch_dim(s + 1, 0.1) > d


# ---------------------------------------------------------------------------
# Cham missing-category mask
# ---------------------------------------------------------------------------


def test_cham_mask_inactive_is_bit_identical():
    """When the estimates already sit inside the feasible polytope (exact
    synthetic stats) and the observed counts don't bind, the masked path
    returns the same float bits as the unmasked one — serving paths that
    opt in but never see misses pay nothing."""
    d = 64
    rng = np.random.default_rng(13)
    a = rng.uniform(2, 10, 16)
    b = rng.uniform(2, 10, 16)
    ip = rng.uniform(0, 1, 16) * np.minimum(a, b)
    big_d = 1.0 - 1.0 / d
    wu = d * (1.0 - big_d ** a)
    wv = d * (1.0 - big_d ** b)
    inner = wu + wv - d * (1.0 - big_d ** (a + b - ip))
    base = np.asarray(binhamming_from_stats(wu, wv, inner, d))
    huge = np.full(16, 10_000.0)
    masked = np.asarray(binhamming_from_stats(wu, wv, inner, d,
                                              obs_u=huge, obs_v=huge))
    assert np.array_equal(base, masked)


def test_cham_mask_bounds_saturated_rows():
    """A saturated sketch (weight ~ d) of a heavily truncated row explodes
    the unmasked density estimate through the log; the observed-dimension
    clamp keeps every estimate inside the feasible polytope, so the
    distance is bounded by the observable support."""
    d = 64
    wu = np.asarray([d - 1.0])
    wv = np.asarray([5.0])
    inner = np.asarray([3.0])
    obs_u = np.asarray([10.0])   # only 10 dims were observed for u
    obs_v = np.asarray([8.0])
    unmasked = float(np.asarray(binhamming_from_stats(wu, wv, inner, d))[0])
    masked = float(np.asarray(binhamming_from_stats(
        wu, wv, inner, d, obs_u=obs_u, obs_v=obs_v))[0])
    # h = 2u - a - b with a <= obs_u, b <= obs_v, u <= a + b
    assert masked <= float(obs_u[0] + obs_v[0]) + 1e-5
    assert masked <= unmasked
    assert masked >= 0.0
