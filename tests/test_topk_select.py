"""Fused topk_select kernel (interpret mode) vs oracles.

Contracts pinned here:
  * the kernel's running compare-exchange merge equals the dense-matrix +
    stable-argsort reference — same columns, same (distance, column)
    tie-break — across ragged shapes, both metrics, with and without
    m_valid masking;
  * "hamming" distances are exact integers and match bit-for-bit on every
    path; "cham" indices match and values agree to cross-graph libm noise
    (the same ~1e-7-relative caveat kernels.hamming.ops.dist_matrix
    documents — the bit-identity contract belongs to core.allpairs, whose
    jnp path the serving layer uses off-TPU);
  * core.allpairs.topk_rows mode="pallas" (the TPU serving route) agrees
    with its jnp tile loop;
  * the in-place entry (`topk_select_tiles`, the band walk's) scores only
    the listed tiles, breaks ties by rank, never returns rank -1, and its
    jnp twin in core.allpairs returns the same bits.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import allpairs
from repro.kernels.topk_select import kernel as topk_kernel
from repro.kernels.topk_select.kernel import topk_select as topk_select_kernel
from repro.kernels.topk_select.ops import topk_select
from repro.kernels.topk_select.ref import topk_select_ref

RNG = np.random.default_rng(4321)
D = 256


def _rows(n, w):
    return jnp.asarray(
        RNG.integers(-(2**31), 2**31, size=(n, w)).astype(np.int32))


def _check(metric, kv, ki, rv, ri):
    np.testing.assert_array_equal(np.asarray(ki), np.asarray(ri))
    if metric == "hamming":  # exact integer distances: bit-identical
        np.testing.assert_array_equal(np.asarray(kv), np.asarray(rv))
    else:  # cham: same exact integer stats, cross-graph libm noise
        np.testing.assert_allclose(np.asarray(kv), np.asarray(rv),
                                   rtol=1e-5, atol=1e-3)


@pytest.mark.parametrize("metric", ["cham", "hamming"])
@pytest.mark.parametrize(
    "q,n,w,k,bq,bn",
    [
        (1, 1, 1, 1, 8, 8),
        (9, 37, 8, 5, 4, 8),       # ragged: padding on every axis
        (16, 64, 8, 3, 8, 16),     # exact tiling
        (33, 70, 9, 7, 16, 32),    # W = 9 words: d = 288 bits
        (5, 12, 4, 12, 8, 4),      # k == n: every column is a winner
    ],
)
def test_topk_select_shapes(metric, q, n, w, k, bq, bn):
    a = _rows(q, w)
    b = _rows(n, w)
    d = max(D, 32 * w)  # a packed row never holds more bits than d
    kv, ki = topk_select_kernel(a, b, n, k, metric=metric, d=d, bq=bq, bn=bn,
                                interpret=True)
    rv, ri = topk_select_ref(a, b, k, d=d, metric=metric)
    _check(metric, kv, ki, rv, ri)


def test_topk_select_sort_key_ranks_like_argsort():
    """The kernel ranks distances by an int32 key that orders exactly as
    argsort orders floats — -0.0 with +0.0, every NaN after +inf — so a NaN
    distance lands where topk_select_ref puts it, and the key maps back to
    the same value."""
    x = np.array([np.nan, 3.0, -0.0, np.inf, 0.0, -np.inf, 1e-30, -2.5,
                  np.nan, 7.5], np.float32)
    keys = np.asarray(topk_kernel._sort_key(jnp.asarray(x)))
    np.testing.assert_array_equal(np.argsort(keys, kind="stable"),
                                  np.argsort(x, kind="stable"))
    assert keys.max() < topk_kernel._KEY_EMPTY  # an empty slot ranks last
    back = np.asarray(topk_kernel._key_value(jnp.asarray(keys)))
    np.testing.assert_array_equal(back, np.where(x == 0.0, 0.0, x))


@pytest.mark.parametrize("metric", ["cham", "hamming"])
def test_topk_select_tie_break_lower_column(metric):
    """Duplicate store rows => equal distances straddling the k boundary on
    every tile edge; the winner must always be the LOWER column."""
    base = _rows(6, 8)
    b = jnp.concatenate([base, base, base], axis=0)  # 3 copies of each
    a = _rows(4, 8)
    kv, ki = topk_select_kernel(a, b, b.shape[0], 7, metric=metric, d=D,
                                bq=4, bn=4, interpret=True)
    rv, ri = topk_select_ref(a, b, 7, d=D, metric=metric)
    _check(metric, kv, ki, rv, ri)
    # self-query on the duplicated store: first two hits are copies at the
    # same distance, ordered by column
    kv2, ki2 = topk_select_kernel(base, b, b.shape[0], 2, metric=metric, d=D,
                                  bq=4, bn=8, interpret=True)
    np.testing.assert_array_equal(np.asarray(ki2[:, 0]), np.arange(6))
    np.testing.assert_array_equal(np.asarray(ki2[:, 1]), np.arange(6, 12))
    np.testing.assert_array_equal(np.asarray(kv2[:, 0]),
                                  np.asarray(kv2[:, 1]))


@pytest.mark.parametrize("metric", ["cham", "hamming"])
def test_topk_select_m_valid_masks_padding(metric):
    """Columns past the traced valid count can never be returned, whatever
    garbage the padding rows hold."""
    a = _rows(6, 8)
    b = _rows(40, 8)
    for m in (17, 32, 40):
        kv, ki = topk_select_kernel(a, b, m, 9, metric=metric, d=D,
                                    bq=8, bn=16, interpret=True)
        rv, ri = topk_select_ref(a, b, 9, d=D, metric=metric, m_valid=m)
        _check(metric, kv, ki, rv, ri)
        assert int(np.asarray(ki).max()) < m


def test_topk_select_ops_dispatch_and_errors():
    a = _rows(5, 8)
    b = _rows(21, 8)
    kv, ki = topk_select(a, b, 4, d=D, use_pallas=True, interpret=True)
    rv, ri = topk_select(a, b, 4, d=D, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(ki), np.asarray(ri))
    np.testing.assert_allclose(np.asarray(kv), np.asarray(rv),
                               rtol=1e-5, atol=1e-3)
    # k clamps to m_valid; empty edges return (Q, 0)
    kv0, ki0 = topk_select(a, b, 3, d=D, m_valid=0)
    assert kv0.shape == (5, 0) and ki0.shape == (5, 0)
    kv1, ki1 = topk_select(a[:0], b, 3, d=D)
    assert kv1.shape == (0, 0)
    with pytest.raises(ValueError, match="m_valid"):
        topk_select(a, b, 3, d=D, m_valid=22)
    with pytest.raises(ValueError, match="metric"):
        topk_select(a, b, 3, d=D, metric="cosine", use_pallas=False)


@pytest.mark.parametrize("metric", ["cham", "hamming"])
def test_topk_rows_pallas_mode_matches_jnp(metric):
    """The serving dispatch: allpairs.topk_rows mode="pallas" (fused kernel)
    vs its jnp tile loop — identical columns under both metrics."""
    a = _rows(9, 8)
    b = _rows(50, 8)
    pi, pv = allpairs.topk_rows(a, b, 6, d=D, metric=metric, mode="pallas",
                                block=16, m_valid=44)
    ji, jv = allpairs.topk_rows(a, b, 6, d=D, metric=metric,
                                block=16, m_valid=44)
    np.testing.assert_array_equal(pi, ji)
    if metric == "hamming":
        np.testing.assert_array_equal(pv, jv)
    else:
        np.testing.assert_allclose(pv, jv, rtol=1e-5, atol=1e-3)


# ---------------------------------------------------------------------------
# the in-place entry: listed tiles of a resident matrix, rank tie-break
# ---------------------------------------------------------------------------

TILE = 8


def _layout(n, p, w, dead=(), seed=0):
    """A resident matrix of p rows (n real ones), its weight row, and a
    rank row from a shuffled id order with `dead` positions at -1."""
    b = _rows(p, w)
    ids = np.random.default_rng(seed).permutation(n)
    rank, pos_of_rank = allpairs.key_rank(ids, p)
    rank[list(dead)] = -1
    weights = np.asarray(allpairs.packing.popcount_rows(b))[None, :]
    return b, jnp.asarray(weights), rank, pos_of_rank


def _tiles(listed, p, bn=TILE):
    tiles = np.zeros(p // bn, np.int32)
    tiles[: len(listed)] = listed
    return jnp.asarray(tiles)


def _in_place(a, b, weights, rank, listed, k, metric, count=None,
              bn=TILE):
    return topk_kernel.topk_select_tiles(
        a, b, weights, jnp.asarray(rank[None, :]),
        _tiles(listed, b.shape[0], bn),
        len(listed) if count is None else count, k, metric=metric, d=D,
        bq=8, bn=bn, interpret=True)


def _listed_rows(listed, rank):
    """The listed tiles' live rows, arranged in rank order."""
    rows = np.concatenate([np.arange(t * TILE, (t + 1) * TILE)
                           for t in listed])
    rows = rows[rank[rows] >= 0]
    return rows[np.argsort(rank[rows])]


@pytest.mark.parametrize("metric", ["cham", "hamming"])
def test_in_place_entry_equals_ref_over_listed_rows_in_rank_order(metric):
    """A shuffled tile list over a padded layout with dead rows: the
    kernel returns the ranks of the reference's k-best over the listed
    live rows laid out in rank order."""
    n, p = 61, 80
    b, weights, rank, _ = _layout(n, p, 8, dead=(3, 17, 40))
    a = _rows(13, 8)
    listed = [6, 1, 9, 3, 0, 7]
    kv, kr = _in_place(a, b, weights, rank, listed, 7, metric)
    rows = _listed_rows(listed, rank)
    rv, ri = topk_select_ref(a, b[rows], 7, d=D, metric=metric)
    np.testing.assert_array_equal(np.asarray(kr), rank[rows[np.asarray(ri)]])
    _check(metric, kv, ri, rv, ri)


@pytest.mark.parametrize("metric", ["cham", "hamming"])
def test_in_place_ties_across_tiles_resolve_to_lower_rank(metric):
    """Copies of each row in three tiles, ranked so that the copy in the
    LATER column has the LOWER rank: every tie resolves by rank."""
    base = _rows(TILE, 8)
    b = jnp.concatenate([base, base, base], axis=0)
    p = b.shape[0]
    rank = np.arange(p, dtype=np.int32)[::-1].copy()  # rank falls with column
    weights = jnp.asarray(allpairs.packing.popcount_rows(b))[None, :]
    kv, kr = _in_place(base, b, weights, rank, [0, 2, 1], 3, metric)
    kr = np.asarray(kr)
    # the three copies of query row i sit at columns i, i+8, i+16, at
    # distance zero from it; lower rank = higher column comes first
    cols = np.arange(TILE)[:, None] + np.array([16, 8, 0])[None, :]
    np.testing.assert_array_equal(kr, rank[cols])
    np.testing.assert_array_equal(np.asarray(kv)[:, 0], np.asarray(kv)[:, 2])


@pytest.mark.parametrize("metric", ["cham", "hamming"])
def test_in_place_never_returns_rank_minus_one(metric):
    """Rows ranked -1 (dead, padding) never come back, even when the
    listed tiles hold fewer than k live rows: the rest read (inf, -1)."""
    n, p = 20, 32
    dead = (0, 2, 5, 6, 9, 11, 13, 14)
    b, weights, rank, _ = _layout(n, p, 8, dead=dead)
    a = _rows(5, 8)
    listed = [1, 0, 3]  # 8 + 8 rows with 8 dead, and a tile of padding
    kv, kr = _in_place(a, b, weights, rank, listed, 12, metric)
    kv, kr = np.asarray(kv), np.asarray(kr)
    rows = _listed_rows(listed, rank)
    assert len(rows) == 8
    np.testing.assert_array_equal(np.sort(kr[:, :8], axis=1),
                                  np.tile(np.sort(rank[rows]), (5, 1)))
    np.testing.assert_array_equal(kr[:, 8:], -1)
    assert np.isinf(kv[:, 8:]).all() and np.isfinite(kv[:, :8]).all()


@pytest.mark.parametrize("metric", ["cham", "hamming"])
def test_in_place_steps_past_count_change_nothing(metric):
    """Entries of the tile list past `count` are never scored, whatever
    tiles they name."""
    n, p = 64, 64
    b, weights, rank, _ = _layout(n, p, 8)
    a = _rows(9, 8)
    short = _in_place(a, b, weights, rank, [4, 2], 5, metric)
    padded = _in_place(a, b, weights, rank, [4, 2, 0, 7, 5], 5, metric,
                       count=2)
    for x, y in zip(short, padded):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("metric", ["cham", "hamming"])
def test_in_place_jnp_twin_equals_kernel(metric):
    """core.allpairs' jnp twin of the in-place entry (the CPU walk) returns
    what the kernel returns, bit for bit, ranks and values (at tiles of
    128 rows: on the CPU, XLA rounds the estimator differently at some
    narrow tile widths)."""
    n, p, bn = 1100, 2048, 128
    b, weights, rank, _ = _layout(n, p, 8, dead=(7, 70, 700, 701))
    a = _rows(16, 8)
    listed = [6, 3, 8, 0, 5]
    kv, kr = _in_place(a, b, weights, rank, listed, 6, metric, bn=bn)
    tv, tr = allpairs._topk_tiles_impl(
        a, b, jnp.asarray(rank[None, :]), _tiles(listed, p, bn),
        np.int32(len(listed)), k=6, tile=bn, width=bn, metric=metric,
        mode="popcount", d=D)
    np.testing.assert_array_equal(np.asarray(tr), np.asarray(kr))
    np.testing.assert_array_equal(np.asarray(tv), np.asarray(kv))
