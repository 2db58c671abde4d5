"""Weight-banded layouts: the query-pruning structures over a store.

A Cabin sketch's Hamming weight bounds how close it can be to anything:
dist(u, v) >= prune_factor(metric) * |s_u - s_v| for the per-row prune score
s (repro.core.allpairs.prune_score_host — the density estimate under cham,
the raw weight under exact hamming).  PR 1 exploited this bound INSIDE the
batch engine's tile loop; the index subsystem hoists it one level up: rows
are kept weight-sorted and partitioned into contiguous BANDS, each band
carrying its host-side score interval, so a radius query discards whole
bands on host — before a single distance tile, device gather, or compile is
touched — and a k-NN query expands outward through the bands nearest the
query, stopping at the exactness certificate (DESIGN.md sections 8.2/8.4).

This module holds ONE layer: `BandedLayout`, an immutable weight-sorted
banded snapshot of a slot set, plus a refreshable ALIVE mask so tombstones
thread through without invalidating the sort or the device matrix.  A
layout can cover any slot subset (a shard's membership, not just the whole
store) and commit its matrix to a specific device — it is the
``sorted-banded`` partition kind of `repro.index.partition` (DESIGN.md
section 13), where the incremental tiering, sharding, and cross-partition
merge logic live (`PartitionSet`, historically `TieredLayout`, plus
`merge_topk_parts` — both re-exported here for back-compat).

Every prune is sound (the weight bound holds with PRUNE_MARGIN slack for
float noise), and the cross-partition merge is the same (value, id)-
lexicographic k-best used inside `topk_rows_banded`, so results are
bit-identical to a fresh batch build of the same membership — banding,
tiering, and sharding are pure serving optimisations with zero
bit-identity risk.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import allpairs
from repro.core.allpairs import (KBEST_KEY_PAD, PRUNE_MARGIN, prune_factor,
                                 prune_score_host)
from repro.core.packing import padded_take, pow2_bucket
from repro.index.store import SketchStore
from repro.obs.registry import NULL_REGISTRY


@jax.jit
def _drop_ranks(rank: jnp.ndarray, pos: jnp.ndarray) -> jnp.ndarray:
    return rank.at[0, pos].set(-1)


class BandedLayout:
    """Immutable weight-sorted banded snapshot of a slot set.

    Rows are sorted by (sketch weight, id) — a total, history-independent
    order — then cut into bands of `band_rows` consecutive rows.  The device
    matrix holds the sorted rows padded to a power of two; `ids` maps sorted
    positions back to external ids and `slots` back to store slots.  The
    snapshot can cover any slot SUBSET (`slots` — a shard's membership; the
    default is the whole alive store) and commit its matrix to a `device`,
    so the distance tiles against it run where its rows live.

    The snapshot itself never mutates; later tombstones are threaded
    through `refresh_alive`, which re-reads the store's host bitmap at the
    snapshot's slots (O(n) host work) and drops the newly dead rows' ranks
    in one device scatter.  Band score
    intervals are computed over the snapshot's rows and therefore stay
    conservative supersets for any alive subset — masked queries prune a
    little less but never wrongly.
    """

    def __init__(self, store: SketchStore, metric: str,
                 band_rows: int = 1024, registry=None,
                 slots: np.ndarray | None = None, device=None):
        # banding effectiveness counters: visited vs pruned per query, the
        # walk's rounds, and how often the exactness certificate stopped
        # the scan early.  The instruments are cached here once — under
        # NULL_REGISTRY they are shared no-ops and the stats_out dict is
        # never even built.
        reg = NULL_REGISTRY if registry is None else registry
        self._obs_off = reg.is_null
        self._c_queries = reg.counter("index_banded_queries_total")
        self._c_visited = reg.counter("index_bands_visited_total")
        self._c_pruned = reg.counter("index_bands_pruned_total")
        self._c_rounds = reg.counter("index_walk_rounds_total")
        self._c_early = reg.counter("index_band_early_stops_total")
        self._c_scored = reg.counter("index_walk_tiles_total", state="scored")
        self._c_skipped = reg.counter("index_walk_tiles_total",
                                      state="skipped")
        self.metric = metric
        self.d = store.d
        self.band_rows = int(band_rows)
        self.version = store.version
        self.device = device
        if slots is None:
            slots = store.alive_slots()
        weights = store.weights_at(slots)
        # stable sort over id-ordered rows => total order (weight, id):
        # incremental and fresh builds of the same membership agree exactly,
        # and so do sharded and unsharded builds of the same shard subset
        # (slots arrive in ascending id order either way).
        order = np.argsort(weights, kind="stable")
        self.n = len(slots)
        self.slots = slots[order]
        self.ids = store.ids_at(slots)[order]
        w_sorted = weights[order]
        self.matrix = padded_take(store.sk_buf, self.slots)
        # the top-k walk scores bands in place: beside the matrix, each
        # row's popcount and its rank in id order (-1: dead or padding),
        # and the rank -> position map its merge reads
        n_pad = self.matrix.shape[0]
        rank, self.pos_of_rank = allpairs.key_rank(self.ids, n_pad)
        self.rank = jnp.asarray(rank[None, :])
        self.weights = jnp.asarray(np.pad(
            w_sorted.astype(np.int32), (0, n_pad - self.n))[None, :])
        if device is not None:
            self.matrix, self.rank, self.weights = jax.device_put(
                (self.matrix, self.rank, self.weights), device)
        self.alive = np.ones(self.n, bool)
        self._n_alive = self.n
        self.n_bands = -(-self.n // self.band_rows) if self.n else 0
        scores = prune_score_host(w_sorted, self.d, metric)
        self.band_lo = np.asarray(
            [scores[b * self.band_rows] for b in range(self.n_bands)])
        self.band_hi = np.asarray(
            [scores[min((b + 1) * self.band_rows, self.n) - 1]
             for b in range(self.n_bands)])

    @property
    def n_alive(self) -> int:
        return self._n_alive

    def refresh_alive(self, store: SketchStore) -> None:
        """Re-read the store's tombstone bitmap at this snapshot's slots —
        how removes reach a layout without any rebuild: the newly dead
        rows' ranks drop to -1 in one device scatter (tombstones never
        resurrect)."""
        if self.n:
            alive = store.alive_at(self.slots)
            dead = np.flatnonzero(self.alive & ~alive)
            if len(dead):
                # pow2-padded with repeats: one compiled scatter per bucket
                pos = np.full(pow2_bucket(len(dead)), dead[0], np.int32)
                pos[: len(dead)] = dead
                self.rank = _drop_ranks(self.rank, pos)
            self.alive = alive
            self._n_alive = int(np.count_nonzero(self.alive))

    def _mask(self) -> np.ndarray | None:
        # None keeps the fully-alive hot path identical to the pre-mask one
        return None if self._n_alive == self.n else self.alive

    def candidate_bands(self, query_weights: np.ndarray, radius: float
                        ) -> np.ndarray:
        """Bool mask over bands: band b survives iff SOME query's score is
        within reach of its [lo, hi] score interval — i.e. the weight bound
        cannot rule out every row in it."""
        if self.n == 0 or len(query_weights) == 0:
            return np.zeros(self.n_bands, bool)
        qs = prune_score_host(np.asarray(query_weights), self.d, self.metric)
        factor = prune_factor(self.metric)
        gap = np.maximum(
            np.maximum(self.band_lo[None, :] - qs[:, None],
                       qs[:, None] - self.band_hi[None, :]), 0.0)
        return (factor * gap < radius + PRUNE_MARGIN).any(axis=0)

    def topk(self, queries_padded: jnp.ndarray, query_weights: np.ndarray,
             k: int, *, q_valid: int, block: int = 2048,
             mode: str | None = None, deadline=None,
             info_out: dict | None = None,
             init_kth: np.ndarray | None = None
             ) -> tuple[np.ndarray, np.ndarray]:
        """Progressive band-expansion k-NN: (ids (Q, k'), dists (Q, k')),
        k' = min(k, n_alive), ascending by (distance, id) — exactly what
        core.allpairs.topk_rows returns over the alive membership in id
        order.

        Bands are visited in ascending prune-score distance from the query
        batch, the running k-th best distance is tracked, and the scan stops
        with the certificate `prune_factor * gap >= kth + PRUNE_MARGIN` for
        every (query, unvisited band) pair — see allpairs.topk_rows_banded
        for the exactness argument.  `queries_padded` is the pow2-padded
        packed query batch (first `q_valid` rows real); `query_weights` its
        host sketch weights, used for band planning only.

        `init_kth` seeds the certificate with a cross-partition k-th bound
        (per query, length >= q_valid): rows pruned under it are provably
        outside the GLOBAL merged top-k, so this layout returns a
        sufficient — not necessarily full — k-best whose unfilled columns
        carry KBEST_KEY_PAD and merge away.  `deadline` bounds the band
        walk (allpairs budgeted mode); when it fires, `info_out` (if given)
        reports partial=True + the residual cert_gap.  Exact calls leave
        info_out with partial=False, cert_gap=0.0."""
        if info_out is not None:
            info_out.update(partial=False, cert_gap=0.0)
        if self._n_alive == 0 or k <= 0 or q_valid == 0:
            return (np.zeros((q_valid, 0), np.int64),
                    np.zeros((q_valid, 0), np.float32))
        qs = prune_score_host(np.asarray(query_weights)[:q_valid], self.d,
                              self.metric)
        st = None if (self._obs_off and info_out is None
                      and deadline is None) else {}
        pos, vals = allpairs.topk_rows_banded(
            queries_padded, self.matrix, k, d=self.d, metric=self.metric,
            q_scores=qs, band_lo=self.band_lo, band_hi=self.band_hi,
            band_rows=self.band_rows, n_valid=self.n, block=block,
            mode=mode, q_valid=q_valid, alive=self._mask(), stats_out=st,
            deadline=deadline, init_kth=init_kth, weights=self.weights,
            rank=self.rank, pos_of_rank=self.pos_of_rank)
        if st is not None and not self._obs_off:
            self._c_queries.inc()
            self._c_visited.inc(st["bands_visited"])
            self._c_pruned.inc(st["n_bands"] - st["bands_visited"])
            self._c_rounds.inc(st["rounds"])
            self._c_scored.inc(st["tiles_scored"])
            self._c_skipped.inc(st["tiles_skipped"])
            if st["early_stop"]:
                self._c_early.inc()
        if info_out is not None and st is not None:
            info_out.update(partial=st["partial"],
                            cert_gap=st["cert_gap"],
                            bands_visited=st["bands_visited"],
                            rows_visited=st["rows_visited"])
        # a budget-stopped walk — or a cross-partition bound proving rows
        # here can't enter the merged top-k — can leave columns unfilled
        # (pos == -1); map them to the KBEST pad id instead of wrapping
        # through ids[-1]
        if (pos < 0).any():
            ids = np.full(pos.shape, KBEST_KEY_PAD, np.int64)
            real = pos >= 0
            ids[real] = self.ids[pos[real]]
            return ids, vals
        return self.ids[pos], vals

    def select(self, band_mask: np.ndarray
               ) -> tuple[jnp.ndarray, int, np.ndarray]:
        """Gather the surviving bands' alive rows: (matrix (pow2, w),
        n_selected, ids (n_selected,)).  Bands are contiguous runs of the
        sorted matrix, so selection is a single padded device take."""
        kept = np.flatnonzero(band_mask)
        if len(kept) == 0:
            return self.matrix[:0], 0, self.ids[:0]
        rows = np.concatenate([
            np.arange(b * self.band_rows,
                      min((b + 1) * self.band_rows, self.n))
            for b in kept])
        mask = self._mask()
        if mask is not None:
            rows = rows[mask[rows]]
        if len(rows) == 0:
            return self.matrix[:0], 0, self.ids[:0]
        return padded_take(self.matrix, rows), len(rows), self.ids[rows]


def __getattr__(name: str):
    # back-compat lazy re-exports: the LSM tier layer moved to
    # repro.index.partition (TieredLayout is PartitionSet's n_shards=1
    # face, merge_topk_parts is the shared cross-partition merge rule).
    # PEP 562 indirection instead of a top-level import keeps
    # bands -> partition -> bands from becoming an import cycle.
    if name in ("TieredLayout", "merge_topk_parts"):
        from repro.index import partition
        return getattr(partition, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
