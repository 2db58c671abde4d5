"""Streaming all-pairs engine vs dense references, and end-to-end
equivalence of the rewired dedup / k-mode consumers."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import allpairs
from repro.core.cabin import CabinParams, sketch_dense
from repro.core.cham import cham_matrix, hamming_matrix_exact
from repro.core.kmode import kmode_precomputed
from repro.data.dedup import (dedup_by_sketch, dedup_by_sketch_blocked,
                              docs_to_categorical, sketch_corpus)
from repro.data.pipeline import synthetic_documents

D = 512
_cham_jit = jax.jit(cham_matrix, static_argnums=2)


def _sketches(n_rows=96, n=2500, density=150, seed=0):
    rng = np.random.default_rng(seed)
    x = np.zeros((n_rows, n), np.int32)
    for i in range(n_rows):
        idx = rng.choice(n, size=density, replace=False)
        x[i, idx] = rng.integers(1, 10, size=density)
    p = CabinParams.create(n, D, seed=1)
    return np.asarray(sketch_dense(p, jnp.asarray(x)))


SK = _sketches()
REF = np.asarray(_cham_jit(jnp.asarray(SK), jnp.asarray(SK), D))
IU = np.triu_indices(len(SK), 1)


# ---------------------------------------------------------------------------
# threshold candidate extraction
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["matmul", "popcount", "pallas"])
@pytest.mark.parametrize("block", [17, 64, 96])
def test_threshold_pairs_matches_dense(mode, block):
    thr = float(np.percentile(REF[IU], 10))
    got = allpairs.threshold_pairs(SK, d=D, threshold=thr, block=block,
                                   mode=mode)
    want = {(i, j) for i, j in zip(*IU) if REF[i, j] < thr}
    assert {tuple(p) for p in got} == want
    assert got.dtype == np.int32 and got.shape[1] == 2


def test_threshold_pairs_overflow_retry():
    thr = float(np.percentile(REF[IU], 50))  # lots of candidates
    got = allpairs.threshold_pairs(SK, d=D, threshold=thr, block=32,
                                   capacity=4)  # forces doubling re-runs
    want = {(i, j) for i, j in zip(*IU) if REF[i, j] < thr}
    assert {tuple(p) for p in got} == want


def test_threshold_pairs_asymmetric_and_hamming():
    b = SK[:30]
    ref_ab = np.asarray(hamming_matrix_exact(jnp.asarray(SK), jnp.asarray(b)))
    thr = float(np.percentile(ref_ab, 15))
    got = allpairs.threshold_pairs(SK, b, d=D, threshold=thr,
                                   metric="hamming", block=25)
    want = set(zip(*np.where(ref_ab < thr)))
    assert {tuple(p) for p in got} == want


def test_threshold_pairs_empty_result():
    got = allpairs.threshold_pairs(SK, d=D, threshold=-1.0, block=64)
    assert got.shape == (0, 2)


def _off_boundary_threshold(vals: np.ndarray, q: float) -> float:
    """A threshold near the q-th percentile that sits in a wide gap of the
    distance distribution: the banded path's log-free comparison is exactly
    equivalent in real arithmetic but can flip knife-edge pairs whose
    distance EQUALS the threshold to the last float ulp."""
    s = np.unique(np.sort(vals))
    k = int(np.clip(np.searchsorted(s, np.percentile(vals, q)), 1, len(s) - 1))
    for off in range(len(s) - k - 1):
        lo, hi = s[k - 1 + off], s[k + off]
        if hi - lo > 1e-2:
            return float((lo + hi) / 2)
    return float(s[-1] + 1.0)


@pytest.mark.parametrize("block", [16, 32, 96])
def test_threshold_pairs_banded_matches_dense(block):
    """Weight-sorted banded fast path: same candidate set as the dense
    reference — the band bound (cham >= 2|a_hat - b_hat|) never drops a
    true candidate."""
    order = np.argsort(
        np.unpackbits(np.ascontiguousarray(SK).view(np.uint8), axis=1)
        .sum(axis=1), kind="stable")
    sks = SK[order]
    refs = np.asarray(_cham_jit(jnp.asarray(sks), jnp.asarray(sks), D))
    for q in [5, 40]:
        thr = _off_boundary_threshold(refs[IU], q)
        got = allpairs.threshold_pairs(sks, d=D, threshold=thr, block=block,
                                       sorted_by_weight=True)
        want = {(i, j) for i, j in zip(*IU) if refs[i, j] < thr}
        assert {tuple(p) for p in got} == want


def test_threshold_pairs_banded_rejects_unsorted():
    with pytest.raises(ValueError, match="not sorted"):
        # SK is in random order with overwhelming probability
        allpairs.threshold_pairs(SK, d=D, threshold=10.0,
                                 sorted_by_weight=True)


# ---------------------------------------------------------------------------
# row-wise reductions
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["matmul", "popcount"])
def test_argmin_rows_matches_dense(mode):
    rng = np.random.default_rng(3)
    centers = SK[rng.choice(len(SK), 7, replace=False)]
    refc = np.asarray(_cham_jit(jnp.asarray(SK), jnp.asarray(centers), D))
    for block in [3, 7]:
        idxs, vals = allpairs.argmin_rows(SK, centers, d=D, block=block,
                                          mode=mode)
        np.testing.assert_array_equal(idxs, refc.argmin(axis=1))
        np.testing.assert_allclose(vals, refc.min(axis=1), rtol=1e-6)


def test_topk_rows_matches_dense():
    idxs, vals = allpairs.topk_rows(SK, SK, 5, d=D, block=41)
    order = np.argsort(REF, axis=1, kind="stable")[:, :5]
    np.testing.assert_array_equal(idxs, order)
    np.testing.assert_allclose(vals, np.take_along_axis(REF, order, axis=1),
                               rtol=1e-6)
    # self is always the nearest neighbour at (near-)zero distance
    np.testing.assert_array_equal(idxs[:, 0], np.arange(len(SK)))
    assert float(np.abs(vals[:, 0]).max()) < 1e-3


def test_topk_rows_tie_break_across_tiles():
    """Duplicate rows scattered across tile boundaries => equal distances
    straddling the k cut; the O(k) lax.top_k merge must keep the LOWER
    column, exactly like the stable argsort it replaced."""
    b = np.concatenate([SK[:20], SK[:20], SK[:20]])  # 3 copies, cols i, i+20, i+40
    refd = np.asarray(_cham_jit(jnp.asarray(SK[:10]), jnp.asarray(b), D))
    order = np.argsort(refd, axis=1, kind="stable")[:, :5]
    for block in [7, 16, 60]:  # copies split across tiles every which way
        idxs, vals = allpairs.topk_rows(SK[:10], b, 5, d=D, block=block)
        np.testing.assert_array_equal(idxs, order)
        np.testing.assert_array_equal(
            vals, np.take_along_axis(refd, order, axis=1))


def test_argmin_rows_bucketed_no_recompile():
    """m is traced and b is pow2-bucketed: the k-mode medoid loop's drifting
    cluster sizes must reuse one compiled graph per bucket."""
    centers = SK[:13]
    before = allpairs._argmin_rows_impl._cache_size()
    for m in (5, 6, 7, 8):
        idxs, vals = allpairs.argmin_rows(SK[:10], centers[:m], d=D)
        ref = np.asarray(_cham_jit(jnp.asarray(SK[:10]),
                                   jnp.asarray(centers[:m]), D))
        np.testing.assert_array_equal(idxs, ref.argmin(axis=1))
        np.testing.assert_allclose(vals, ref.min(axis=1), rtol=1e-6)
    # all four sizes bucket to 8 rows -> exactly one new compile
    assert allpairs._argmin_rows_impl._cache_size() == before + 1


@pytest.mark.parametrize("metric", ["cham", "hamming"])
def test_topk_rows_banded_matches_full_scan(metric):
    """Progressive band expansion returns exactly the full scan's answer —
    positions, values, and (value, key) tie-break — for both the default
    positional keys and a shuffled external-id keying."""
    from repro.core.packing import np_popcount_rows

    weights = np_popcount_rows(SK)
    order = np.argsort(weights, kind="stable")
    sks = SK[order]
    w_sorted = weights[order]
    n = len(sks)
    band_rows = 8
    n_bands = -(-n // band_rows)
    scores = allpairs.prune_score_host(w_sorted, D, metric)
    band_lo = np.asarray([scores[b * band_rows] for b in range(n_bands)])
    band_hi = np.asarray(
        [scores[min((b + 1) * band_rows, n) - 1] for b in range(n_bands)])
    q = SK[:7]
    q_scores = allpairs.prune_score_host(np_popcount_rows(q), D, metric)

    pos, vals = allpairs.topk_rows_banded(
        q, jnp.asarray(sks), 5, d=D, metric=metric, q_scores=q_scores,
        band_lo=band_lo, band_hi=band_hi, band_rows=band_rows, n_valid=n,
        block=32)
    ref_i, ref_v = allpairs.topk_rows(q, sks, 5, d=D, metric=metric)
    np.testing.assert_array_equal(pos, ref_i)
    np.testing.assert_array_equal(vals, ref_v)

    # external-id keying: results must match the full scan over the rows
    # REARRANGED in key order (ties -> lower key), mapped back to positions
    ids = np.random.default_rng(5).permutation(n).astype(np.int64)
    key_order = np.argsort(ids, kind="stable")
    ref_ki, ref_kv = allpairs.topk_rows(q, sks[key_order], 5, d=D,
                                        metric=metric)
    pos2, vals2 = allpairs.topk_rows_banded(
        q, jnp.asarray(sks), 5, d=D, metric=metric, q_scores=q_scores,
        band_lo=band_lo, band_hi=band_hi, band_rows=band_rows, n_valid=n,
        order_by=ids, block=32)
    np.testing.assert_array_equal(pos2, key_order[ref_ki])
    np.testing.assert_array_equal(vals2, ref_kv)


def test_rowsum_matches_dense():
    got = allpairs.rowsum(SK, d=D, block=29)
    np.testing.assert_allclose(got, REF.sum(axis=1), rtol=1e-5)


# ---------------------------------------------------------------------------
# end-to-end consumer equivalence (the rewire contract)
# ---------------------------------------------------------------------------


def _corpus_sketches(n_docs=220, vocab=4096, seed=7):
    gen = synthetic_documents(vocab, seed=seed, dup_fraction=0.3)
    docs = [next(gen) for _ in range(n_docs)]
    idx, val = docs_to_categorical(docs, vocab)
    _, sk = sketch_corpus(idx, val, vocab, sketch_dim=D, seed=0)
    return sk


def test_dedup_streaming_equals_blocked_seed_path():
    sk = _corpus_sketches()
    new = dedup_by_sketch(sk, D, threshold=40.0, block=64)
    old = dedup_by_sketch_blocked(sk, D, threshold=40.0, block=64)
    np.testing.assert_array_equal(new.keep_mask, old.keep_mask)
    np.testing.assert_array_equal(new.group_ids, old.group_ids)
    assert new.n_groups == old.n_groups
    assert new.n_removed == old.n_removed
    assert new.n_removed > 0  # the corpus really contains duplicates


def test_dedup_handles_no_duplicates_and_empty():
    sk = _corpus_sketches(n_docs=40)
    none = dedup_by_sketch(sk, D, threshold=0.0)
    assert none.n_removed == 0 and none.n_groups == 40
    empty = dedup_by_sketch(sk[:0], D, threshold=40.0)
    assert empty.n_groups == 0 and empty.n_removed == 0


def test_kmode_precomputed_engine_equals_oracle():
    sk = _corpus_sketches(n_docs=150)

    def dist_fn(a, b):
        return np.asarray(_cham_jit(jnp.asarray(a), jnp.asarray(b), D))

    for seed in range(3):
        legacy = kmode_precomputed(dist_fn, sk.copy(), k=4, seed=seed)
        engine = kmode_precomputed(None, sk.copy(), k=4, seed=seed,
                                   sketch_dim=D)
        np.testing.assert_array_equal(legacy, engine)


def test_threshold_scan_lowering_names_its_scopes():
    """The radius tile loop's device ops carry stable scope names in the
    HLO metadata, which a profile's `tf_op` stat reports: the loop, the
    distance tile, and the hit extraction."""
    a = jnp.zeros((256, 4), jnp.int32)
    text = allpairs._threshold_pairs_impl.lower(
        a, a, jnp.zeros((1, 2), jnp.int32), jnp.float32(30.0),
        jnp.int32(256), jnp.int32(256), block=256, capacity=4096,
        symmetric=False, metric="cham", mode="popcount", d=128,
    ).as_text(debug_info=True)
    for scope in ("allpairs.threshold_scan", "allpairs.tile_dist",
                  "allpairs.append_hits"):
        assert scope in text, scope
