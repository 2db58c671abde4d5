"""Pallas TPU kernel: fused distance + running top-k select on packed rows.

The serving hot path (QueryEngine.topk -> core.allpairs.topk_rows) streams
store tiles past a query block and keeps the k best columns per query.  Run
as separate passes — a pair-stats kernel producing an f32 distance tile in
HBM, then a host/XLA select — every losing column (all but ~k of N) pays an
HBM round-trip for a value that is immediately discarded.  This kernel fuses
the two: the distance tile and the running k-best merge happen in one VMEM
pass, so the only HBM writes are the (Q, k) results.

VMEM carry layout: the (BQ, k) key and index OUTPUT tiles double as the
carry — their index_map pins them to (i, 0) for every column step j, so with
the column grid innermost they stay resident in VMEM across the whole sweep
(same revisiting discipline as the hamming kernel's accumulator) and are
flushed to HBM once per query tile.  Both live as full (key, index)-sorted
rows; k is kept at its logical size (Mosaic pads the trailing dim
internally), so carry VMEM is 8·BQ·k bytes on top of the (BQ, W) + (BN, W)
int32 input tiles.

Distances: the popcount inner product <q, b> is taken on the MXU, one bit
plane at a time — sum over s of ((q >> s) & 1) @ ((b >> s) & 1)^T with {0,1}
operands (exact even at bf16 input precision) and f32 accumulation, exact
for counts below 2^24 — so no
(BQ, BN, W) broadcast is ever built.  Row weights come in precomputed.

Ranking: each distance maps to an int32 sort key that orders exactly as
jnp.argsort orders floats (-0.0 folded into +0.0, every NaN after +inf), so
the kernel ranks a NaN distance where topk_select_ref does and every
extraction names a real column.  The keys are the carry; the wrapper turns
them back into distances.

Merge: per column chunk, min(k, chunk) compare-exchange rounds against the
chunk minimum.  Each round extracts the chunk's lexicographic (key, column)
minimum — ties resolve to the LOWER column via an iota-masked second min —
knocks it out of the chunk, and inserts it into the sorted carry with a
vectorised compare-exchange shift (count strictly-smaller carry entries,
shift the tail right by one, place).  Equal-key insertions land AFTER
existing carry entries, whose columns are always lower (earlier chunks), so
the carry is the exact (distance, column)-lexicographic k-best —
bit-identical to core.allpairs._topk_rows_impl's stable merge, which tests
pin.  Chunks and rounds are loops, not unrolled code, which bounds the
compiled program at any tile size.

Grid: (Q/BQ, N/BN) with the column dimension innermost; `m` (the traced
valid-column count) rides in SMEM so varying the live store size never
recompiles.

In-place entry (`topk_select_tiles`, the band walk's): b is a whole
resident layout and a scalar-prefetched tile list names the blocks to
score, so a round of bands is scored where it lies — no gathered chunk,
and one program per (Q, P, k) whatever the round holds.  The same merge
body runs with the row's RANK (from a (1, P) row beside b) as the carry's
identity instead of its column: the (key, rank) minimum over tiles in any
order equals the (key, column) minimum over the same rows laid out in
rank order.  Rank -1 (dead rows, padding) keys as empty and never enters
the carry.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.cham import binhamming_from_stats
from repro.core.packing import pad_to_multiple, popcount_rows

_SIGN_FREE = 0x7FFFFFFF
_KEY_INF = 0x7F800000  # sort key of +inf
_KEY_EMPTY = 0x7FFFFFFF  # above every real key, NaN included
_CHUNK = 512  # columns per merge chunk
_NO_IDENT = 2**31 - 1  # identity ranking after every real column or rank


def _sort_key(x: jnp.ndarray) -> jnp.ndarray:
    """f32 -> int32 key, monotone in jnp.sort's float order."""
    x = jnp.where(x == 0.0, 0.0, x)
    x = jnp.where(x != x, jnp.nan, x)
    i = jax.lax.bitcast_convert_type(x, jnp.int32)
    return i ^ ((i >> 31) & _SIGN_FREE)


def _key_value(key: jnp.ndarray) -> jnp.ndarray:
    """Inverse of `_sort_key` (the flip is an involution)."""
    return jax.lax.bitcast_convert_type(key ^ ((key >> 31) & _SIGN_FREE),
                                        jnp.float32)


def _inner(q: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """(BQ, W) x (BN, W) packed -> (BQ, BN) int32 bit inner products."""
    nt = (((1,), (1,)), ((), ()))

    def plane(s, acc):
        qs = ((q >> s) & 1).astype(jnp.float32)
        bs = ((b >> s) & 1).astype(jnp.float32)
        return acc + jax.lax.dot_general(qs, bs, nt,
                                         preferred_element_type=jnp.float32)

    acc = jnp.zeros((q.shape[0], b.shape[0]), jnp.float32)
    return jax.lax.fori_loop(0, 32, plane, acc).astype(jnp.int32)


def _distances(wq, wb, inner, metric: str, d: int) -> jnp.ndarray:
    """Same formulas as core.allpairs._tile_dist on exact integer stats."""
    if metric == "cham":
        return 2.0 * binhamming_from_stats(wq, wb, inner, d)
    if metric == "hamming":
        return (wq + wb - 2 * inner).astype(jnp.float32)
    raise ValueError(f"unknown metric {metric!r}")


def _init_carry(keys_ref, idxs_ref):
    keys_ref[...] = jnp.full_like(keys_ref, _KEY_EMPTY)
    idxs_ref[...] = jnp.full_like(idxs_ref, -1)


def _merge_block(q_ref, wq_ref, b_ref, wb_ref, keys_ref, idxs_ref, label, *,
                 k, bn, chunk, metric, d):
    """Merge one (BN, W) block of rows into the resident (BQ, k) carry.

    `label(off, dist)` gives the columns of the chunk at offset `off` their
    sort keys and identities: the identity breaks key ties (lower wins) and
    is what the carry's index tile records."""
    q = q_ref[...]
    wq = wq_ref[...]  # (BQ, 1)
    bq = q.shape[0]
    kiota = jax.lax.broadcasted_iota(jnp.int32, (bq, k), 1)
    big = jnp.int32(_NO_IDENT)

    def merge_chunk(off, carry):
        keys, idxs = carry  # (BQ, k) ascending by (key, index)
        inner = _inner(q, b_ref[pl.ds(off, chunk), :])
        dist = _distances(wq, wb_ref[:, pl.ds(off, chunk)], inner, metric, d)
        key, ident = label(off, dist)

        def extract(_, st):
            key, keys, idxs = st
            # lexicographic (key, identity) chunk minimum
            tmin = jnp.min(key, axis=1, keepdims=True)
            tidx = jnp.min(jnp.where(key == tmin, ident, big), axis=1,
                           keepdims=True)
            key = jnp.where(ident == tidx, _KEY_EMPTY, key)
            # compare-exchange insertion: strictly-smaller carry entries
            # stay, the tail shifts right one slot, the extracted pair drops
            # in.  An insertion past the end (pos == k) leaves the carry
            # untouched; empty slots rank after every real column.
            smaller = (keys < tmin) | ((keys == tmin) & (idxs < tidx))
            pos = jnp.sum(smaller.astype(jnp.int32), axis=1, keepdims=True)
            shift_k = jnp.concatenate([keys[:, :1], keys[:, :-1]], axis=1)
            shift_i = jnp.concatenate([idxs[:, :1], idxs[:, :-1]], axis=1)
            keep = kiota < pos
            here = kiota == pos
            keys = jnp.where(keep, keys, jnp.where(here, tmin, shift_k))
            idxs = jnp.where(keep, idxs, jnp.where(here, tidx, shift_i))
            return key, keys, idxs

        _, keys, idxs = jax.lax.fori_loop(0, min(k, chunk), extract,
                                          (key, keys, idxs))
        return keys, idxs

    carry = (keys_ref[...], idxs_ref[...])
    if bn == chunk:  # one chunk: a static offset, whatever its width
        keys, idxs = merge_chunk(0, carry)
    else:  # lane-tile-aligned chunks
        keys, idxs = jax.lax.fori_loop(
            0, bn // chunk,
            lambda c, cr: merge_chunk(pl.multiple_of(c * chunk, chunk), cr),
            carry)
    keys_ref[...] = keys
    idxs_ref[...] = idxs


def _topk_select_kernel(m_ref, q_ref, wq_ref, b_ref, wb_ref, keys_ref,
                        idxs_ref, *, k, bn, chunk, metric, d):
    """One (BQ, BN) column step of the running (BQ, k) select."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        _init_carry(keys_ref, idxs_ref)

    m = m_ref[0, 0]

    def label(off, dist):  # identity = column; columns past m rank as +inf
        col = (j * bn + off
               + jax.lax.broadcasted_iota(jnp.int32, dist.shape, 1))
        return jnp.where(col < m, _sort_key(dist), _KEY_INF), col

    _merge_block(q_ref, wq_ref, b_ref, wb_ref, keys_ref, idxs_ref, label,
                 k=k, bn=bn, chunk=chunk, metric=metric, d=d)


def _topk_tiles_kernel(tiles_ref, count_ref, q_ref, wq_ref, b_ref, wb_ref,
                       rank_ref, keys_ref, idxs_ref, *, k, bn, chunk, metric,
                       d):
    """Grid step j scores listed tile `tiles[j]` while j < count."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        _init_carry(keys_ref, idxs_ref)

    def label(off, dist):  # identity = rank; rank -1 never enters the carry
        r = rank_ref[:, pl.ds(off, chunk)]  # (1, chunk)
        live = r >= 0
        return (jnp.where(live, _sort_key(dist), _KEY_EMPTY),
                jnp.broadcast_to(jnp.where(live, r, _NO_IDENT), dist.shape))

    @pl.when(j < count_ref[0])
    def _score():
        _merge_block(q_ref, wq_ref, b_ref, wb_ref, keys_ref, idxs_ref, label,
                     k=k, bn=bn, chunk=chunk, metric=metric, d=d)


@functools.partial(
    jax.jit, static_argnames=("k", "metric", "d", "bq", "bn", "interpret"))
def topk_select(
    q: jnp.ndarray,
    b: jnp.ndarray,
    m,
    k: int,
    *,
    metric: str = "cham",
    d: int,
    bq: int = 128,
    bn: int = 1024,
    interpret: bool = False,
):
    """Fused k-nearest-columns: q (Q, W) x b (N, W) packed int32 ->
    (values (Q, k) f32, indices (Q, k) int32), ascending by (value, index).

    `m` is the TRACED count of valid leading rows of b (columns past it are
    masked to +inf); `k` must satisfy 1 <= k <= m for every result slot to
    be a real column (the ops wrapper validates).
    """
    assert q.ndim == 2 and b.ndim == 2 and q.shape[1] == b.shape[1]
    nq, w = q.shape
    bq_, bn_ = min(bq, nq), min(bn, b.shape[0])
    chunk = _CHUNK if bn_ % _CHUNK == 0 else bn_
    q_p = pad_to_multiple(q, bq_, 0)
    b_p = pad_to_multiple(b, bn_, 0)
    wq = popcount_rows(q_p)[:, None]
    wb = popcount_rows(b_p)[None, :]
    grid = (q_p.shape[0] // bq_, b_p.shape[0] // bn_)
    m_arr = jnp.asarray(m, jnp.int32).reshape(1, 1)

    keys, idxs = pl.pallas_call(
        functools.partial(_topk_select_kernel, k=k, bn=bn_, chunk=chunk,
                          metric=metric, d=d),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bq_, w), lambda i, j: (i, 0)),
            pl.BlockSpec((bq_, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bn_, w), lambda i, j: (j, 0)),
            pl.BlockSpec((1, bn_), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((bq_, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bq_, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((q_p.shape[0], k), jnp.int32),
            jax.ShapeDtypeStruct((q_p.shape[0], k), jnp.int32),
        ],
        interpret=interpret,
    )(m_arr, q_p, wq, b_p, wb)
    keys, idxs = keys[:nq], idxs[:nq]
    return jnp.where(idxs < 0, jnp.inf, _key_value(keys)), idxs


@functools.partial(
    jax.jit, static_argnames=("k", "metric", "d", "bq", "bn", "interpret"))
def topk_select_tiles(
    q: jnp.ndarray,
    b: jnp.ndarray,
    weights: jnp.ndarray,
    rank: jnp.ndarray,
    tiles: jnp.ndarray,
    count,
    k: int,
    *,
    metric: str = "cham",
    d: int,
    bq: int = 128,
    bn: int = 1024,
    interpret: bool = False,
):
    """Fused k-nearest rows over listed tiles of a resident matrix, in
    place: q (Q, W) x the tiles `tiles[:count]` of b (P, W) ->
    (values (Q, k) f32, ranks (Q, k) int32), ascending by (value, rank).

    Tile t is rows [t * bn, (t + 1) * bn) of b; `weights` and `rank` are
    int32 (1, P) rows beside b: each row's popcount, and its rank in the
    caller's tie-break order, or -1 for a row that must never be returned
    (dead, or padding).  `tiles` is int32 (P // bn,) and `count` is traced,
    so the program depends on (Q, P, k) alone, whatever a call lists.
    Grid steps past `count` revisit the last listed tile (no new block is
    fetched) and skip their compute.  Slots left without a live row read
    (inf, -1).  Requires P % bn == 0, and on a TPU bn a multiple of 128
    or P itself (the (1, bn) weight and rank blocks are lane-tiled).
    """
    assert q.ndim == 2 and b.ndim == 2 and q.shape[1] == b.shape[1]
    nq, w = q.shape
    n_tiles = b.shape[0] // bn
    assert n_tiles * bn == b.shape[0] and tiles.shape == (n_tiles,)
    assert weights.shape == rank.shape == (1, b.shape[0])
    bq_ = min(bq, nq)
    chunk = _CHUNK if bn % _CHUNK == 0 else bn
    q_p = pad_to_multiple(q, bq_, 0)
    wq = popcount_rows(q_p)[:, None]
    cnt = jnp.asarray(count, jnp.int32).reshape(1)

    def tile(j, tiles, cnt):  # steps past count stay on the last listed tile
        return tiles[jnp.minimum(j, jnp.maximum(cnt[0] - 1, 0))]

    keys, idxs = pl.pallas_call(
        functools.partial(_topk_tiles_kernel, k=k, bn=bn, chunk=chunk,
                          metric=metric, d=d),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(q_p.shape[0] // bq_, n_tiles),
            in_specs=[
                pl.BlockSpec((bq_, w), lambda i, j, t, c: (i, 0)),
                pl.BlockSpec((bq_, 1), lambda i, j, t, c: (i, 0)),
                pl.BlockSpec((bn, w), lambda i, j, t, c: (tile(j, t, c), 0)),
                pl.BlockSpec((1, bn), lambda i, j, t, c: (0, tile(j, t, c))),
                pl.BlockSpec((1, bn), lambda i, j, t, c: (0, tile(j, t, c))),
            ],
            out_specs=[
                pl.BlockSpec((bq_, k), lambda i, j, t, c: (i, 0)),
                pl.BlockSpec((bq_, k), lambda i, j, t, c: (i, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((q_p.shape[0], k), jnp.int32),
            jax.ShapeDtypeStruct((q_p.shape[0], k), jnp.int32),
        ],
        interpret=interpret,
    )(tiles.astype(jnp.int32), cnt, q_p, wq, b, weights, rank)
    keys, idxs = keys[:nq], idxs[:nq]
    return jnp.where(idxs < 0, jnp.inf, _key_value(keys)), idxs
