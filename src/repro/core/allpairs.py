"""Streaming all-pairs engine over packed Cabin sketches.

Every O(N^2) consumer in this repo (dedup candidate generation, k-mode
assignment, medoid updates) used to materialise full (N, M) Cham/Hamming
matrices and sync them to host block by block.  This module replaces that
with device-resident tiled passes: the distance tile is computed, REDUCED,
and discarded inside a single fused `lax` loop, so peak memory is
O(N * block) and exactly one host transfer happens per query — the compact
result.

This is a BATCH engine: it consumes whole matrices of packed sketches.  The
query-shaped API over a persistent, incrementally updated collection lives
in `repro.index` (SketchStore / QueryEngine, DESIGN.md section 8), which
drives the reductions below — `topk_rows` for k-NN serving and
`threshold_pairs` for radius queries — over its device-resident buffers and
is re-exported from `repro.core` for discoverability.

Reductions provided:

  threshold_pairs(a, b, d, threshold)  -> compact (i, j) candidate list of
                                          pairs with dist < threshold
                                          (dedup candidate generation)
  argmin_rows(a, b, d)                 -> per-row nearest column + distance
                                          (k-mode assignment)
  topk_rows(a, b, d, k)                -> per-row k smallest distances +
                                          indices (neighbour queries)
  rowsum(a, b, d)                      -> per-row total distance
                                          (k-medoid centre updates)

Distance semantics are IDENTICAL to repro.core.cham.cham_matrix /
hamming_matrix_exact: the pairwise statistics (wa, wb, inner) are exact
integers however the tile is computed, and the Cham estimator is elementwise
on those integers, so results are bit-identical to the dense reference
regardless of tiling — this is what lets data.dedup swap engines without
changing a single DedupResult.

Tile backends (`mode`):
  * "popcount" — the jnp SWAR popcount contraction (repro.core.cham): the
                 contraction depth is d/32 packed words, which XLA CPU
                 vectorises well — the default off-TPU.
  * "matmul"   — unpack the packed words to {0,1} float32 and take the tile
                 inner product as a GEMM.  Counts <= d < 2^24 are exactly
                 representable in float32, so this is EXACT too; it does
                 32x more raw MACs than "popcount" but wins on hardware
                 with idle matmul units.
  * "pallas"   — the repro.kernels.hamming pair_stats TPU kernel.
  * None       — auto: "pallas" on TPU, "popcount" elsewhere.

Metrics: "cham" (estimated HD of the original categorical vectors, float32)
and "hamming" (exact HD between packed binary rows, computed as
wa + wb - 2*inner, returned as float32 so both metrics share one code path).
"""

from __future__ import annotations

import functools
import math

import numpy as np
import jax
import jax.numpy as jnp

from repro import obs
from repro.core import packing
from repro.core.cham import binhamming_from_stats


def _auto_mode(mode: str | None) -> str:
    if mode is not None:
        return mode
    return "pallas" if jax.default_backend() == "tpu" else "popcount"


# Slack added to every weight-band prune test: distances are O(10..1000),
# cross-graph float noise between the bound and the estimator's internals is
# O(1e-3), so the margin makes the prune sound without costing selectivity.
PRUNE_MARGIN = 0.05


def prune_factor(metric: str) -> float:
    """`dist(i, j) >= prune_factor * |s_i - s_j|` for the per-row prune
    score s (see prune_score_host): 2 for cham, 1 for exact hamming."""
    if metric == "cham":
        return 2.0
    if metric == "hamming":
        return 1.0
    raise ValueError(f"unknown metric {metric!r}")


def _tile_inner(a_blk: jnp.ndarray, b_blk: jnp.ndarray, d: int, mode: str
                ) -> jnp.ndarray:
    """Exact pairwise <a_i, b_j> bit inner products for one tile."""
    if mode == "matmul":
        ua = packing.unpack_bits(a_blk, d).astype(jnp.float32)
        ub = packing.unpack_bits(b_blk, d).astype(jnp.float32)
        return jnp.dot(ua, ub.T,
                       preferred_element_type=jnp.float32).astype(jnp.int32)
    if mode == "popcount":
        return jnp.sum(
            packing.popcount32(a_blk[:, None, :] & b_blk[None, :, :]), axis=-1
        )
    if mode == "pallas":
        from repro.kernels.hamming import kernel as _hk

        inner, _ = _hk.pair_stats(a_blk, b_blk, op_ham=False,
                                  interpret=jax.default_backend() != "tpu")
        return inner
    raise ValueError(f"unknown tile mode {mode!r}")


def _tile_dist(a_blk: jnp.ndarray, b_blk: jnp.ndarray, d: int, metric: str,
               mode: str) -> jnp.ndarray:
    """One (bm, bn) float32 distance tile; bit-identical to cham_matrix /
    hamming_matrix_exact on the same rows."""
    wa = packing.popcount_rows(a_blk)
    wb = packing.popcount_rows(b_blk)
    inner = _tile_inner(a_blk, b_blk, d, mode)
    if metric == "cham":
        return 2.0 * binhamming_from_stats(wa[:, None], wb[None, :], inner, d)
    if metric == "hamming":
        return (wa[:, None] + wb[None, :] - 2 * inner).astype(jnp.float32)
    raise ValueError(f"unknown metric {metric!r}")


def _pad_rows(x: jnp.ndarray, block: int) -> jnp.ndarray:
    pad = (-x.shape[0]) % block
    return jnp.pad(x, ((0, pad), (0, 0))) if pad else x


_pow2_rows = packing.pad_rows_pow2


# ---------------------------------------------------------------------------
# threshold candidate extraction (dedup)
# ---------------------------------------------------------------------------


def _append_hits(carry, flat, n_hits, i0, j0, width, capacity):
    """Append this tile's hits to the (buf_i, buf_j, count) carry.

    Buffers carry `capacity` extra slack slots: each tile appends with one
    dynamic_update_slice of length min(capacity, tile size) — a tile never
    holds more hits than entries — starting at the running count; slots
    past the tile's hit count hold garbage but are overwritten by the next
    tile (its window starts exactly at the new count) and never escape the
    final [:count] slice.  Rank r's hit lives at the first flat index with
    cumsum == r: a log(tile) binary-search gather per output slot, far
    cheaper than scattering the whole tile into the buffer.  Tiles with no
    candidates skip extraction entirely.
    """
    window = min(capacity, flat.shape[0])

    def extract(c):
        bi, bj, cnt = c
        csum = jnp.cumsum(flat)
        ranks = jnp.arange(1, window + 1, dtype=csum.dtype)
        pos = jnp.searchsorted(csum, ranks)
        pos = jnp.minimum(pos, flat.shape[0] - 1)
        gi_v = (i0 + pos // width).astype(jnp.int32)
        gj_v = (j0 + pos % width).astype(jnp.int32)
        off = jnp.minimum(cnt, capacity)
        bi = jax.lax.dynamic_update_slice(bi, gi_v, (off,))
        bj = jax.lax.dynamic_update_slice(bj, gj_v, (off,))
        return bi, bj, cnt + n_hits

    return jax.lax.cond(
        n_hits > 0, extract, lambda c: (c[0], c[1], c[2] + n_hits), carry)


def _prune_scores(x_p, n_valid, d, metric):
    """Per-row lower-bound score s with the property
    dist(i, j) >= factor * |s_i - s_j| (factor 2 for cham, 1 for hamming):
    cham >= 2|a_hat - b_hat| because the union estimate u_hat >= max(a_hat,
    b_hat); exact HD >= |wu - wv|.  Padded rows get (+inf, -inf) so fully
    padded tiles always prune."""
    w = packing.popcount_rows(x_p).astype(jnp.float32)
    if metric == "cham":
        from repro.core.cham import density_estimate

        s = density_estimate(w, d)
    else:
        s = w
    valid = jnp.arange(x_p.shape[0]) < n_valid
    s_min = jnp.where(valid, s, jnp.inf)
    s_max = jnp.where(valid, s, -jnp.inf)
    return s_min, s_max


@functools.partial(
    jax.jit,
    static_argnames=("block", "capacity", "symmetric", "metric", "mode", "d"),
)
def _threshold_pairs_impl(a_p, b_p, offsets, threshold, n, m, *, block,
                          capacity, symmetric, metric, mode, d):
    # n and m are TRACED valid-row counts: repro.index pads its query batches
    # and store gathers to power-of-two shapes, so the compile cache must key
    # on the bucketed shapes only, not on the live row counts.
    n_tiles = offsets.shape[0]
    row_iota = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
    col_iota = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
    factor = prune_factor(metric)
    # weight-band tile prune: per-block score ranges; a tile whose blocks'
    # score intervals are further apart than threshold/factor cannot contain
    # a candidate, so its distance tile is never computed (PRUNE_MARGIN
    # absorbs float noise between this bound and the estimator's internals).
    sa_min, sa_max = _prune_scores(a_p, n, d, metric)
    sb_min, sb_max = _prune_scores(b_p, m, d, metric)
    blk_a_min = sa_min.reshape(-1, block).min(axis=1)
    blk_a_max = sa_max.reshape(-1, block).max(axis=1)
    blk_b_min = sb_min.reshape(-1, block).min(axis=1)
    blk_b_max = sb_max.reshape(-1, block).max(axis=1)
    buf_len = 2 * capacity  # slack slots for _append_hits windows

    def body(t, carry):
        i0 = offsets[t, 0]
        j0 = offsets[t, 1]
        ib = i0 // block
        jb = j0 // block
        gap = jnp.maximum(
            jnp.maximum(blk_b_min[jb] - blk_a_max[ib],
                        blk_a_min[ib] - blk_b_max[jb]), 0.0)
        prunable = factor * gap >= threshold + PRUNE_MARGIN

        def compute(carry):
            a_blk = jax.lax.dynamic_slice(a_p, (i0, 0), (block, a_p.shape[1]))
            b_blk = jax.lax.dynamic_slice(b_p, (j0, 0), (block, b_p.shape[1]))
            with jax.named_scope("allpairs.tile_dist"):
                dist = _tile_dist(a_blk, b_blk, d, metric, mode)
            gi = i0 + row_iota
            gj = j0 + col_iota
            mask = (dist < threshold) & (gi < n) & (gj < m)
            if symmetric:
                mask &= gi < gj
            flat = mask.ravel().astype(jnp.int32)
            with jax.named_scope("allpairs.append_hits"):
                return _append_hits(carry, flat, jnp.sum(flat), i0, j0,
                                    block, capacity)

        return jax.lax.cond(prunable, lambda c: c, compute, carry)

    buf_i = jnp.full((buf_len,), -1, jnp.int32)
    buf_j = jnp.full((buf_len,), -1, jnp.int32)
    count = jnp.int32(0)
    # named scopes are HLO metadata: they name the tile loop's device ops
    # in a profile and leave the compiled program unchanged
    with jax.named_scope("allpairs.threshold_scan"):
        buf_i, buf_j, count = jax.lax.fori_loop(
            0, n_tiles, body, (buf_i, buf_j, count))
    return buf_i, buf_j, count


@functools.partial(
    jax.jit,
    static_argnames=("n", "block", "width", "capacity", "metric", "mode",
                     "d", "logfree"),
)
def _banded_pairs_impl(a_pp, threshold, *, n, block, width, capacity, metric,
                       mode, d, logfree):
    """Symmetric weight-sorted fast path: for each row block, all candidate
    columns j > i live in [i0, i0 + width) — one (block, width) strip per
    row block instead of a tile grid, so the loop has few, large, well-
    vectorised iterations.

    `logfree` (cham, no saturated sketches) replaces the per-pair log-based
    estimator with the exactly equivalent inner-product test

        cham(u, v) < t  <=>  st > wa + wb - d + d * D^(t/4) * ra * rb,
        ra = sqrt(1 - wa/d) = D^(a_hat/2),

    obtained by inverting the monotone union estimate u_hat: the per-pair
    work drops from three logarithm evaluations to one multiply.  Requires
    max weight < d (else the estimator's log clamping has no inner-product
    twin; the caller checks and falls back)."""
    n_blocks = (n + block - 1) // block
    row_iota = jax.lax.broadcasted_iota(jnp.int32, (block, width), 0)
    col_iota = jax.lax.broadcasted_iota(jnp.int32, (block, width), 1)
    buf_len = 2 * capacity
    w_rows = packing.popcount_rows(a_pp).astype(jnp.float32)
    if logfree:
        log_d = math.log1p(-1.0 / d)
        k_thr = jnp.float32(d) * jnp.exp(log_d * threshold * 0.25)
        radii = jnp.sqrt(jnp.maximum(1.0 - w_rows / d, 0.0))

    def body(ib, carry):
        i0 = ib * block
        a_blk = jax.lax.dynamic_slice(a_pp, (i0, 0), (block, a_pp.shape[1]))
        strip = jax.lax.dynamic_slice(a_pp, (i0, 0), (width, a_pp.shape[1]))
        gi = i0 + row_iota
        gj = i0 + col_iota
        if logfree:
            inner = _tile_inner(a_blk, strip, d, mode).astype(jnp.float32)
            wa = jax.lax.dynamic_slice(w_rows, (i0,), (block,))
            wb = jax.lax.dynamic_slice(w_rows, (i0,), (width,))
            ra = jax.lax.dynamic_slice(radii, (i0,), (block,))
            rb = jax.lax.dynamic_slice(radii, (i0,), (width,))
            bound = (wa[:, None] + wb[None, :] - d
                     + k_thr * ra[:, None] * rb[None, :])
            mask = (inner > bound) & (gi < gj) & (gj < n)
        else:
            dist = _tile_dist(a_blk, strip, d, metric, mode)  # (block, width)
            mask = (dist < threshold) & (gi < gj) & (gj < n)
        flat = mask.ravel().astype(jnp.int32)
        return _append_hits(carry, flat, jnp.sum(flat), i0, i0, width,
                            capacity)

    buf_i = jnp.full((buf_len,), -1, jnp.int32)
    buf_j = jnp.full((buf_len,), -1, jnp.int32)
    count = jnp.int32(0)
    buf_i, buf_j, count = jax.lax.fori_loop(
        0, n_blocks, body, (buf_i, buf_j, count))
    return buf_i, buf_j, count


# pad sentinel for k-best candidate lists: a (inf, KBEST_KEY_PAD) entry
# sorts after every real (value, key) candidate in kbest_lex_merge
KBEST_KEY_PAD = np.iinfo(np.int64).max


def kbest_lex_merge(k: int, values: np.ndarray, keys: np.ndarray,
                    *extras: np.ndarray) -> tuple[np.ndarray, ...]:
    """Exact (value, key)-lexicographic k-best over per-row candidate
    lists: `values`/`keys`/`extras` are (Q, C >= k) concatenated candidate
    columns; returns each reduced to its k best columns, ascending by
    (value, key).  THE one merge rule behind every multi-list top-k in the
    repo — topk_rows_banded's cross-chunk merge and the index's cross-tier
    merge share it, which is what makes their bit-identity with a single
    `topk_rows` scan structural rather than by convention.  Pad candidate
    lists short of k with (np.inf, KBEST_KEY_PAD) entries; they sort after
    any real candidate and survive only if fewer than k real ones exist.
    k must be >= 0 (k = 0 is a valid empty reduction)."""
    if k < 0:
        raise ValueError(f"kbest_lex_merge: k must be >= 0, got {k}")
    order = np.lexsort((keys, values), axis=-1)[:, :k]

    def take(a: np.ndarray) -> np.ndarray:
        return np.take_along_axis(a, order, axis=1)

    return (take(values), take(keys)) + tuple(take(a) for a in extras)


def prune_score_host(weights: np.ndarray, d: int, metric: str) -> np.ndarray:
    """Host twin of _prune_scores for band planning (float64; PRUNE_MARGIN
    absorbs the f32/f64 gap).  Shared with repro.index.bands, which uses the
    same `dist >= prune_factor * |s_i - s_j|` bound to skip whole weight
    bands of its store before any distance tile is computed."""
    if metric == "cham":
        w = weights.astype(np.float64)
        return np.log(np.clip(1.0 - w / d, 1e-9, 1.0)) / np.log1p(-1.0 / d)
    return weights.astype(np.float64)


def _band_width(scores: np.ndarray, n: int, block: int, threshold: float,
                factor: float) -> int:
    """Max strip width so that every j >= i0 + width is prunable for row
    block i0 (columns beyond it satisfy factor*gap >= threshold + margin)."""
    reach = (threshold + PRUNE_MARGIN) / factor
    width = block
    for i0 in range(0, n, block):
        s_hi = scores[min(i0 + block, n) - 1]
        hi = int(np.searchsorted(scores, s_hi + reach, side="left"))
        width = max(width, hi - i0)
    n_pad = ((n + block - 1) // block) * block
    # bucket to a block multiple: fewer recompiles across similar corpora
    return min(((width + block - 1) // block) * block, n_pad)


def threshold_pairs(
    a,
    b=None,
    *,
    d: int,
    threshold: float,
    metric: str = "cham",
    block: int = 256,
    capacity: int | None = None,
    mode: str | None = None,
    sorted_by_weight: bool = False,
    weights: np.ndarray | None = None,
    n_valid: int | None = None,
    m_valid: int | None = None,
) -> np.ndarray:
    """All pairs (i, j) with dist(a[i], b[j]) < threshold, as a compact
    (K, 2) int32 host array.

    b=None scans the upper triangle of a vs itself (i < j) — the dedup case.
    `capacity` bounds the candidate buffer on device; on overflow the pass
    transparently re-runs with doubled capacity (a recompile, so size it
    generously when the duplicate rate is known).

    `n_valid` / `m_valid` declare how many leading rows of a / b are real
    when the caller has padded the arrays to bucketed shapes (repro.index
    pads to powers of two so its query mix reuses a handful of compiled
    graphs); rows past the valid count never produce pairs.  The counts are
    traced, so varying them does NOT recompile.  Asymmetric path only.

    `sorted_by_weight=True` (symmetric only) promises the rows are sorted by
    sketch Hamming weight; the scan then switches to banded strips whose
    width comes from the weight bound dist >= factor*|s_i - s_j| — columns
    outside the band provably cannot be candidates, so total work drops from
    O(N^2/2) to O(N * band).  The banded cham pass also swaps the per-pair
    log estimator for the exactly-equivalent log-free inner-product test
    (see _banded_pairs_impl); it decides knife-edge pairs whose distance
    equals the threshold to within a float ulp by different rounding than
    the log formula, so choose thresholds away from exact distance values
    when bit-stable candidate sets matter.  `weights` optionally passes the
    per-row sketch Hamming weights the caller already has (skips one
    device popcount + host sync).
    """
    with obs.span("allpairs.threshold_pairs"):
        symmetric = b is None
        if symmetric and (n_valid is not None or m_valid is not None):
            raise ValueError("n_valid/m_valid require an explicit b "
                             "(asymmetric scan)")
        a = jnp.asarray(a)
        b_arr = a if symmetric else jnp.asarray(b)
        n = a.shape[0] if n_valid is None else n_valid
        m = b_arr.shape[0] if m_valid is None else m_valid
        if not (0 <= n <= a.shape[0] and 0 <= m <= b_arr.shape[0]):
            raise ValueError(f"n_valid/m_valid ({n}, {m}) outside the "
                             f"supplied rows ({a.shape[0]}, "
                             f"{b_arr.shape[0]})")
        if n == 0 or m == 0:
            return np.zeros((0, 2), np.int32)
        # block and capacity are STATIC jit args of the impls: derive block
        # from the (bucketed) array shapes and round capacity to a power of
        # two, so callers whose valid counts drift by a few rows per call
        # (the index engine's radius path under add/remove churn) reuse
        # compiled graphs
        block = max(1, min(block, max(a.shape[0], b_arr.shape[0])))
        if capacity is None:
            capacity = max(4096, 8 * max(n, m))
        capacity = packing.pow2_bucket(capacity)
        mode = _auto_mode(mode)

        def run_with_capacity(run, capacity):
            # overflow -> transparent re-run with a doubled (recompiled) buffer
            while True:
                bi, bj, cnt = run(capacity)
                cnt = int(cnt)
                if cnt <= capacity:
                    return np.stack(
                        [np.asarray(bi)[:cnt], np.asarray(bj)[:cnt]], axis=1)
                capacity = packing.pow2_bucket(max(2 * capacity, cnt))

        if symmetric and sorted_by_weight:
            if weights is None:
                weights = np.asarray(packing.popcount_rows(a))
            if np.any(np.diff(weights) < 0):
                raise ValueError("sorted_by_weight=True but rows are not "
                                 "sorted by sketch weight")
            scores = prune_score_host(weights, d, metric)
            factor = prune_factor(metric)
            width = _band_width(scores, n, block, threshold, factor)
            n_pad = ((n + block - 1) // block) * block
            a_pp = jnp.pad(a, ((0, n_pad + width - n), (0, 0)))
            # log-free inner-product test needs the estimator unclamped
            logfree = metric == "cham" and int(weights.max(initial=0)) < d
            return run_with_capacity(
                lambda cap: _banded_pairs_impl(
                    a_pp, jnp.float32(threshold), n=n, block=block,
                    width=width, capacity=cap, metric=metric, mode=mode, d=d,
                    logfree=logfree),
                capacity)

        a_p = _pad_rows(a, block)
        b_p = a_p if symmetric else _pad_rows(b_arr, block)
        nb_a = a_p.shape[0] // block
        nb_b = b_p.shape[0] // block
        if symmetric:
            offs = [(i * block, j * block)
                    for i in range(nb_a) for j in range(i, nb_b)]
        else:
            offs = [(i * block, j * block)
                    for i in range(nb_a) for j in range(nb_b)]
        offsets = jnp.asarray(offs, dtype=jnp.int32)

        return run_with_capacity(
            lambda cap: _threshold_pairs_impl(
                a_p, b_p, offsets, jnp.float32(threshold), jnp.int32(n),
                jnp.int32(m), block=block, capacity=cap, symmetric=symmetric,
                metric=metric, mode=mode, d=d),
            capacity)


# ---------------------------------------------------------------------------
# row-wise argmin (k-mode assignment)
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit, static_argnames=("block", "metric", "mode", "d"))
def _argmin_rows_impl(a_p, b_p, m, *, block, metric, mode, d):
    # m is a TRACED valid-row count (cf. _rowsum_impl): the k-mode medoid
    # loop calls this with a different member/centre count per cluster per
    # iteration, so the jit cache must key on the (power-of-two bucketed)
    # shapes only — a static m recompiled per cluster size.
    n_tiles = b_p.shape[0] // block

    def body(t, carry):
        best, besti = carry
        j0 = t * block
        b_blk = jax.lax.dynamic_slice(b_p, (j0, 0), (block, b_p.shape[1]))
        dist = _tile_dist(a_p, b_blk, d, metric, mode)  # (n, block)
        col = j0 + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
        dist = jnp.where(col < m, dist, jnp.inf)
        tmin = jnp.min(dist, axis=1)
        targ = j0 + jnp.argmin(dist, axis=1).astype(jnp.int32)
        # strict < keeps the FIRST global minimum — matches np.argmin on the
        # full (n, m) matrix, which is what the seed k-mode loop used
        upd = tmin < best
        return jnp.where(upd, tmin, best), jnp.where(upd, targ, besti)

    best = jnp.full((a_p.shape[0],), jnp.inf, jnp.float32)
    besti = jnp.zeros((a_p.shape[0],), jnp.int32)
    return jax.lax.fori_loop(0, n_tiles, body, (best, besti))


def argmin_rows(a, b, *, d: int, metric: str = "cham", block: int = 2048,
                mode: str | None = None, m_valid: int | None = None):
    """Per-row nearest column: returns (indices (N,), distances (N,)) on
    host, streaming over blocks of b.  Tie-break = first minimum, identical
    to np.argmin over the dense matrix.  Both row counts are bucketed to
    powers of two and the valid column count is traced, so repeated calls
    with drifting sizes (the k-mode loops) reuse O(log N) compiled graphs.

    `m_valid` declares how many leading rows of b are real when the caller
    hands over an already pow2-padded block (repro.core.kmode keeps its
    centre block device-resident and padded once, instead of reshaping it
    per iteration); it is traced, so varying it does not recompile."""
    a = jnp.asarray(a)
    b = jnp.asarray(b)
    n, m = a.shape[0], b.shape[0] if m_valid is None else m_valid
    if not 0 <= m <= b.shape[0]:
        raise ValueError(f"m_valid={m} outside the {b.shape[0]} supplied "
                         "rows")
    a_p = _pow2_rows(a)
    b_p2 = _pow2_rows(b)
    block = max(1, min(block, b_p2.shape[0]))
    b_p = _pad_rows(b_p2, block)
    best, besti = _argmin_rows_impl(a_p, b_p, jnp.int32(m), block=block,
                                    metric=metric, mode=_auto_mode(mode), d=d)
    return np.asarray(besti)[:n], np.asarray(best)[:n]


# ---------------------------------------------------------------------------
# row-wise top-k (neighbour queries)
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit, static_argnames=("k", "block", "metric", "mode", "d"))
def _topk_rows_impl(a, b_p, m, *, k, block, metric, mode, d):
    # m is a TRACED valid-row count (cf. _threshold_pairs_impl): repro.index
    # queries a power-of-two-padded store gather whose live size changes with
    # every add/remove — keying the compile cache on it would recompile per
    # mutation.  Columns past m are masked to +inf and can never be returned.
    n_tiles = b_p.shape[0] // block
    n = a.shape[0]
    kt = min(k, block)  # per-tile survivors: a tile holds `block` candidates

    def body(t, carry):
        vals, idxs = carry  # (n, k) running smallest, (value, index)-sorted
        j0 = t * block
        b_blk = jax.lax.dynamic_slice(b_p, (j0, 0), (block, b_p.shape[1]))
        dist = _tile_dist(a, b_blk, d, metric, mode)  # (n, block)
        col = j0 + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
        dist = jnp.where(col < m, dist, jnp.inf)
        # O(k) merge, no (k + block) argsort: top_k of the negated tile
        # keeps its kt smallest (ties -> lower position = lower column), and
        # a second top_k over [carry | survivors] — carry FIRST, so on equal
        # values the earlier (lower-index) entry wins, exactly the stable-
        # argsort tie-break this merge replaced.  Negation is a sign-bit
        # flip, so round-tripping through -x is bit-exact.
        tile_neg, tpos = jax.lax.top_k(-dist, kt)
        tile_i = jnp.take_along_axis(
            jnp.broadcast_to(col, (n, block)), tpos, axis=1)
        cand_v = jnp.concatenate([vals, -tile_neg], axis=1)
        cand_i = jnp.concatenate([idxs, tile_i], axis=1)
        best_neg, bpos = jax.lax.top_k(-cand_v, k)
        return -best_neg, jnp.take_along_axis(cand_i, bpos, axis=1)

    vals = jnp.full((n, k), jnp.inf, jnp.float32)
    idxs = jnp.full((n, k), -1, jnp.int32)
    return jax.lax.fori_loop(0, n_tiles, body, (vals, idxs))


def topk_rows(a, b, k: int, *, d: int, metric: str = "cham",
              block: int = 2048, mode: str | None = None,
              m_valid: int | None = None, pad_k: bool = False):
    """Per-row k nearest columns of b: (indices (N, k), distances (N, k)),
    ascending by distance, streaming over blocks of b.  Ties are broken by
    the LOWER column index (stable merge).  `m_valid` declares how many
    leading rows of b are real when b is padded to a bucketed shape
    (repro.index); it is traced, so varying it does not recompile.

    `pad_k=True` keeps the requested k even when it exceeds the valid row
    count: the surplus tail columns come back as (+inf, -1) padding.  This
    is the small-tier serving mode — k is a STATIC jit argument, so a
    caller whose collection drifts through sizes below k (the index
    engine's delta tier) must NOT let k track the size, or every mutation
    recompiles; with pad_k the compile key stays fixed and the caller
    strips the pads in its own merge.  Forces the jnp tile loop (the
    fused kernel assumes k <= m, and a collection this small never wants
    a kernel launch anyway).

    mode "pallas" routes through the fused repro.kernels.topk_select kernel
    (distance tile + running k-best merge in one VMEM pass — losing columns
    never materialise an f32 row in HBM); the jnp tile loop above is the
    reference the kernel is pinned against."""
    a = jnp.asarray(a)
    b = jnp.asarray(b)
    m = b.shape[0] if m_valid is None else m_valid
    if not 0 <= m <= b.shape[0]:
        raise ValueError(f"m_valid={m} outside the {b.shape[0]} supplied "
                         "rows")
    if pad_k:
        if k < 0:
            raise ValueError(f"k must be >= 0, got {k}")
        mode = "popcount" if _auto_mode(mode) == "pallas" else mode
    else:
        k = min(k, m)
    if k == 0:
        return (np.zeros((a.shape[0], 0), np.int32),
                np.zeros((a.shape[0], 0), np.float32))
    mode = _auto_mode(mode)
    if mode == "pallas":
        from repro.kernels.topk_select import ops as _topk_ops

        vals, idxs = _topk_ops.topk_select(a, b, k, d=d, metric=metric,
                                           m_valid=m, bn=block,
                                           use_pallas=True)
        return np.asarray(idxs), np.asarray(vals)
    block = max(1, min(block, b.shape[0]))
    b_p = _pad_rows(b, block)
    vals, idxs = _topk_rows_impl(a, b_p, jnp.int32(m), k=k, block=block,
                                 metric=metric, mode=mode, d=d)
    return np.asarray(idxs), np.asarray(vals)


@functools.partial(
    jax.jit, static_argnames=("k", "tile", "width", "metric", "mode", "d"))
def _topk_tiles_impl(a, b, rank, tiles, count, *, k, tile, width, metric,
                     mode, d):
    # the jnp twin of kernels.topk_select.topk_select_tiles: the listed
    # tiles of b scored in place, ties broken by rank, rank -1 never
    # returned.  `count` is traced, so one graph serves every round.  A
    # step scores width // tile listed tiles side by side, `width` being
    # the tile topk_rows takes over all of b (min(block, len(b))): XLA's
    # CPU code rounds the estimator differently at some tile widths (4
    # and 8 columns against 16 or more), so the walk keeps topk_rows' bits.
    from repro.kernels.topk_select.kernel import (
        _KEY_EMPTY, _NO_IDENT, _key_value, _sort_key)

    n = a.shape[0]
    group = width // tile
    offs = jnp.arange(tile, dtype=jnp.int32)

    def body(s, carry):
        keys, ranks = carry  # (n, k) ascending by (sort key, rank)
        j = s * group + jnp.arange(group, dtype=jnp.int32)
        rows = (tiles[j][:, None] * tile + offs).reshape(-1)
        r = jnp.where(jnp.repeat(j < count, tile), rank[0, rows], -1)[None]
        live = r >= 0
        dist = _tile_dist(a, b[rows], d, metric, mode)
        key = jnp.where(live, _sort_key(dist), _KEY_EMPTY)
        ident = jnp.broadcast_to(jnp.where(live, r, _NO_IDENT), key.shape)
        keys, ranks = jax.lax.sort(
            (jnp.concatenate([keys, key], axis=1),
             jnp.concatenate([ranks, ident], axis=1)),
            dimension=1, num_keys=2)
        return keys[:, :k], ranks[:, :k]

    keys = jnp.full((n, k), _KEY_EMPTY, jnp.int32)
    ranks = jnp.full((n, k), _NO_IDENT, jnp.int32)
    keys, ranks = jax.lax.fori_loop(0, (count + group - 1) // group, body,
                                    (keys, ranks))
    empty = ranks == _NO_IDENT
    return (jnp.where(empty, jnp.inf, _key_value(keys)),
            jnp.where(empty, -1, ranks))


def key_rank(keys: np.ndarray, n_rows: int,
             alive: np.ndarray | None = None
             ) -> tuple[np.ndarray, np.ndarray]:
    """(rank (n_rows,) int32, pos_of_rank (len(keys),) int64): each of the
    first len(keys) rows' position in ascending (stable) key order, and its
    inverse.  Rows past len(keys), and rows `alive` marks dead, rank -1 —
    the in-place walk never returns them."""
    pos_of_rank = np.argsort(keys, kind="stable")
    rank = np.full(n_rows, -1, np.int32)
    rank[pos_of_rank] = np.arange(len(keys), dtype=np.int32)
    if alive is not None:
        rank[: len(keys)][~alive[: len(keys)]] = -1
    return rank, pos_of_rank


def topk_rows_banded(a, b, k: int, *, d: int, q_scores: np.ndarray,
                     band_lo: np.ndarray, band_hi: np.ndarray,
                     band_rows: int, n_valid: int, metric: str = "cham",
                     block: int = 2048, mode: str | None = None,
                     order_by: np.ndarray | None = None,
                     q_valid: int | None = None,
                     alive: np.ndarray | None = None,
                     stats_out: dict | None = None,
                     deadline=None,
                     init_kth: np.ndarray | None = None,
                     weights: jnp.ndarray | None = None,
                     rank: jnp.ndarray | None = None,
                     pos_of_rank: np.ndarray | None = None):
    """Progressive band-expansion top-k over weight-banded rows.

    `b` holds `n_valid` rows sorted by ascending prune score and cut into
    contiguous bands of `band_rows` rows whose host score intervals are
    `[band_lo[i], band_hi[i]]` (repro.index.BandedLayout layout).  Bands are
    visited in ascending prune-score distance from the query batch; after
    each round the running k-th best distance is compared against the weight
    bound of every unvisited band, and the scan STOPS with an exactness
    certificate once

        prune_factor(metric) * gap(q, band) >= kth(q) + PRUNE_MARGIN

    holds for every query and unvisited band: any unseen row is then
    provably strictly farther than the current k-th neighbour (the strict
    margin also settles knife-edge ties), so the answer equals the full
    scan's.  Rounds double in row count.  Each round's bands are scored
    where they lie in b: the round's band numbers become a list of tiles
    (gcd(band_rows, len(b), block) rows each) for one top-k call over the
    whole of b, whose program depends on the query bucket, len(b) and k
    alone — nothing is gathered, and no round compiles.

    `order_by` assigns each row the tie-break key the results must honour
    (repro.index passes external ids; default: row position).  The top-k
    call breaks distance ties by each row's RANK in ascending key order, so
    across tiles listed in any order it returns the (value, key)-
    lexicographic k-best, and the host-side merge across rounds is exact.

    `alive` optionally masks rows out (bool over the n_valid sorted rows —
    the tiered layout's tombstones): dead rows rank -1 and can never be
    returned.  The band score intervals are computed over the UNMASKED
    rows, which makes them conservative supersets for the alive subset —
    the certificate under-prunes but stays sound, and the result equals
    `topk_rows` over just the alive rows in key order.

    `weights`, `rank` and `pos_of_rank` are what a caller that walks the
    same rows repeatedly builds once (repro.index.BandedLayout): int32
    (1, len(b)) rows of popcounts and ranks (`key_rank` of `order_by`, with
    `alive` applied), and the rank -> position map.  Left out, they are
    built here from `order_by` and `alive`.

    `deadline` (any object with an `expired` property — repro.serve's
    Deadline) turns the walk into a budgeted one: between rounds, an
    expired deadline stops band expansion where the certificate check
    would have continued it.  The first round always completes (a
    budgeted call returns the gap-zero bands' candidates at minimum),
    and `stats_out` reports `partial=True` with `cert_gap` = how far
    the certificate was from closing (max over queries and unvisited
    bands of `kth + PRUNE_MARGIN - prune_factor * gap`, 0 when it holds,
    inf when fewer than k rows were seen) — the serving layer's
    graceful-degradation contract.  Without a deadline (or when the walk
    finishes before expiry) results are exact and `partial` stays False.

    `init_kth` (f32, one entry per valid query) is a cross-partition upper
    bound on the GLOBAL k-th best value — the running bound a
    `repro.index.partition.PartitionSet` accumulates while walking sibling
    partitions.  The certificate then prunes against
    `min(local kth, init_kth)`: any band it discards holds only rows
    strictly farther than the global k-th neighbour, so the rows this walk
    returns are still a SUFFICIENT SET for the cross-partition
    (value, key)-lex merge — the merged answer stays bit-identical to one
    scan over the union.  With a finite bound the walk may stop before k
    local candidates exist (including before visiting any band at all);
    unfilled columns carry position -1 / value inf even in exact
    (non-partial) results, and merge away against any real candidate.

    `stats_out` also counts the top-k calls' grid steps: `tiles_scored`
    (listed tiles) and `tiles_skipped` (the rest of each call's fixed
    grid of len(b) / tile steps).

    Returns (positions (Q, k) int64 into b's rows, distances (Q, k) f32) —
    bit-identical to `topk_rows` over the same rows arranged in key order.
    Positions can be -1 (column unfilled) only in a partial result or
    under an `init_kth` bound.
    """
    a = jnp.asarray(a)
    q = a.shape[0] if q_valid is None else q_valid
    n_bands = len(band_lo)
    starts = np.arange(n_bands) * band_rows
    band_size = np.minimum(band_rows, n_valid - starts)
    band_live = (band_size if alive is None or n_bands == 0 else
                 np.add.reduceat(alive[:n_valid], starts, dtype=np.int64))
    k = min(k, int(band_live.sum()))
    if stats_out is not None:
        # filled below; pre-set so early returns still report a full record
        stats_out.update(n_bands=n_bands, bands_visited=0,
                         rows_visited=0, rounds=0, early_stop=False,
                         partial=False, cert_gap=0.0, tiles_scored=0,
                         tiles_skipped=0)
    if q == 0 or k == 0:
        return np.zeros((q, 0), np.int64), np.zeros((q, 0), np.float32)
    # one span for the walk; each round is three: `allpairs.walk.plan`
    # (the round's bands and their tile list), `.score` (the top-k call up
    # to its host copy: where the host waits on the device) and `.merge`
    # (the k-best merge and the certificate)
    with obs.span("allpairs.walk", bands=n_bands, q=q, k=k):
        if rank is None:
            keys = (np.arange(n_valid) if order_by is None
                    else np.asarray(order_by)[:n_valid])
            rank_h, pos_of_rank = key_rank(keys, b.shape[0], alive)
            rank = jnp.asarray(rank_h[None, :])
        tile = math.gcd(math.gcd(band_rows, b.shape[0]), block)
        if _auto_mode(mode) == "pallas":
            from repro.kernels.topk_select import kernel as _tk

            if weights is None:
                weights = packing.popcount_rows(b)[None, :]
            score = functools.partial(
                _tk.topk_select_tiles, a, b, weights, rank, k=k,
                metric=metric, d=d, bn=tile,
                interpret=jax.default_backend() != "tpu")
        else:
            score = functools.partial(
                _topk_tiles_impl, a, b, rank, k=k, tile=tile,
                width=min(block, b.shape[0]), metric=metric,
                mode=_auto_mode(mode), d=d)
        tiles_per_band = band_rows // tile
        n_tiles = b.shape[0] // tile
        live_tiles = -(-n_valid // tile)  # tiles past it hold padding only
        tiles = np.zeros(n_tiles, np.int32)
        q_scores = np.asarray(q_scores, np.float64)
        factor = prune_factor(metric)
        # per-(query, band) weight-bound gaps; visit priority = nearest first
        gap = np.maximum(np.maximum(band_lo[None, :] - q_scores[:, None],
                                    q_scores[:, None] - band_hi[None, :]), 0.0)
        if init_kth is not None:
            init_kth = np.asarray(init_kth, np.float32)[:q]
            if np.all(factor * gap >= init_kth[:, None] + PRUNE_MARGIN):
                # every band is already outside the cross-partition bound:
                # nothing here can enter the merged top-k, skip the walk
                if stats_out is not None:
                    stats_out["early_stop"] = True
                return np.zeros((q, 0), np.int64), np.zeros((q, 0), np.float32)
        band_gap = gap.min(axis=0)
        visit = np.argsort(band_gap, kind="stable")

        best_v = np.full((q, k), np.inf, np.float32)
        best_key = np.full((q, k), KBEST_KEY_PAD, np.int64)
        best_pos = np.full((q, k), -1, np.int64)

        ptr = 0
        visited_rows = 0
        rounds = 0
        tiles_scored = tiles_skipped = 0
        while ptr < n_bands:
            rounds += 1
            with obs.span("allpairs.walk.plan", round=rounds):
                take = [visit[ptr]]
                ptr += 1
                if visited_rows == 0:
                    # round 1: every band the weight bound cannot separate
                    # from some query (gap == 0) — the bands the answers
                    # almost surely live in
                    while ptr < n_bands and band_gap[visit[ptr]] <= 0.0:
                        take.append(visit[ptr])
                        ptr += 1
                else:
                    target = max(visited_rows, band_rows)  # geometric growth
                    cnt = band_size[take[0]]
                    while ptr < n_bands and cnt < target:
                        take.append(visit[ptr])
                        cnt += band_size[visit[ptr]]
                        ptr += 1
                live = int(band_live[take].sum())
                visited_rows += live
                listed = (np.asarray(take)[:, None] * tiles_per_band
                          + np.arange(tiles_per_band)).ravel()
                listed = listed[listed < live_tiles]
                count = len(listed)
                tiles[:count] = listed
            if live:  # a round of dead rows only scores nothing
                with obs.span("allpairs.walk.score", tiles=count):
                    val_c, rank_c = score(tiles, np.int32(count))
                    val_c = np.asarray(val_c)[:q]
                    rank_c = np.asarray(rank_c)[:q].astype(np.int64)
                tiles_scored += count
                tiles_skipped += n_tiles - count
            with obs.span("allpairs.walk.merge", round=rounds):
                if live:
                    # rank order is key order, so ranks serve as merge keys
                    real = rank_c >= 0
                    gpos = np.where(real, pos_of_rank[rank_c], -1)
                    gkey = np.where(real, rank_c, KBEST_KEY_PAD)
                    best_v, best_key, best_pos = kbest_lex_merge(
                        k, np.concatenate([best_v, val_c], axis=1),
                        np.concatenate([best_key, gkey], axis=1),
                        np.concatenate([best_pos, gpos], axis=1))
                if ptr >= n_bands:
                    break
                kth = best_v[:, k - 1]
                if init_kth is not None:
                    kth = np.minimum(kth, init_kth)
                bound = factor * gap[:, visit[ptr:]]
                if np.all(bound >= kth[:, None] + PRUNE_MARGIN):
                    if stats_out is not None:
                        stats_out["early_stop"] = True
                    break
                if deadline is not None and deadline.expired:
                    # budget exhausted before the certificate closed: stop
                    # here and report the residual gap — the distance the
                    # kth bound would have to move for the partial answer
                    # to be provably exact (inf when fewer than k candidates
                    # were even seen)
                    if stats_out is not None:
                        stats_out["partial"] = True
                        stats_out["cert_gap"] = float(np.max(np.maximum(
                            kth[:, None] + PRUNE_MARGIN - bound, 0.0)))
                    break
        if stats_out is not None:
            stats_out.update(bands_visited=ptr, rows_visited=visited_rows,
                             rounds=rounds, tiles_scored=tiles_scored,
                             tiles_skipped=tiles_skipped)
        return best_pos, best_v


# ---------------------------------------------------------------------------
# row sums (k-medoid centre update)
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit, static_argnames=("block", "metric", "mode", "d"))
def _rowsum_impl(a_p, b_p, m, *, block, metric, mode, d):
    # m is a TRACED scalar: rowsum is called from the k-mode medoid loop
    # with a different member count per cluster per iteration, so the jit
    # cache must key on the (power-of-two bucketed) shapes only
    n_tiles = b_p.shape[0] // block

    def body(t, acc):
        j0 = t * block
        b_blk = jax.lax.dynamic_slice(b_p, (j0, 0), (block, b_p.shape[1]))
        dist = _tile_dist(a_p, b_blk, d, metric, mode)
        col = j0 + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
        dist = jnp.where(col < m, dist, 0.0)
        return acc + jnp.sum(dist, axis=1)

    return jax.lax.fori_loop(
        0, n_tiles, body, jnp.zeros((a_p.shape[0],), jnp.float32))


def rowsum(a, b=None, *, d: int, metric: str = "cham", block: int = 2048,
           mode: str | None = None, m_valid: int | None = None) -> np.ndarray:
    """Per-row total distance to all rows of b (b=None: of a itself),
    streaming over blocks of b.  Used for medoid selection; shapes are
    bucketed to powers of two so repeated calls with varying row counts
    (the k-mode medoid loop) reuse a handful of compiled graphs.

    `m_valid` declares how many leading rows of b (of a, when b is None)
    are real: columns past it contribute zero.  It is traced — the k-mode
    medoid loop passes `padded_take` member gathers whose pad rows
    REPLICATE row 0 and must not be counted.  Rows of a past the valid
    count still get (meaningless) sums; callers slice them off."""
    a = jnp.asarray(a)
    b = a if b is None else jnp.asarray(b)
    n, m = a.shape[0], b.shape[0] if m_valid is None else m_valid
    if not 0 <= m <= b.shape[0]:
        raise ValueError(f"m_valid={m} outside the {b.shape[0]} supplied "
                         "rows")
    a_p = _pow2_rows(a)
    b_p2 = _pow2_rows(b)
    block = max(1, min(block, b_p2.shape[0]))
    b_p = _pad_rows(b_p2, block)
    out = _rowsum_impl(a_p, b_p, jnp.int32(m), block=block, metric=metric,
                       mode=_auto_mode(mode), d=d)
    return np.asarray(out)[:n]
