"""The partition model: tiers, shards, and spec tiers as ONE object.

Three subsystems grew the same serving discipline independently: the
tiered layout's base+delta tiers (PR 4), the migration's per-spec
src/dst/fresh tiers (PR 6), and mesh sharding's per-device row placement
(ROADMAP item 1).  All three answer a query as "(value, id)-lex-mergeable
partial results over disjoint slot ranges" — the mergeable-summary
structure of the streaming sketch literature.  This module is that shared
layer (DESIGN.md section 13):

  * `Partition` — one unit of serving state: a slot subset of one store,
    a device placement, a layout kind (``sorted-banded``: a weight-banded
    `BandedLayout` snapshot served through the progressive band walk;
    ``brute-delta``: an unsorted slot list scanned brute-force), the
    SketchSpec its rows were sketched under, and an alive mask.  Version
    RANGE stamps live on the owning set — validity is "the store moved
    from stamp A to stamp B and the set absorbed the difference", not
    version equality.
  * `PartitionSet` — the serving object the engine holds: `n_shards`
    groups of (base, delta) partitions over one store, rows routed by
    ``id % n_shards`` (deterministic, history-independent, stable across
    compaction).  It owns the disciplines that used to be smeared across
    engine.py / bands.py / migrate.py: pow2 micro-batch bucketing (every
    gather goes through `padded_take`), version-range invalidation
    (`sync`), per-partition band pruning with a GLOBAL running k-th bound
    (a tight bound from shard 0 prunes bands in shard 7 — threaded as
    `init_kth` into `allpairs.topk_rows_banded`), shard-local compaction
    / merge policy (each shard folds its own delta independently),
    cross-partition `merge_topk_parts`, per-partition deadline budgets,
    and per-partition obs gauges.
  * module functions — `merge_topk_parts` (THE one cross-partition merge
    rule), `topk_across_tiers` (the cross-spec mid-migration merge, same
    bound threading), `radius_hits` (the shared per-tier threshold-scan
    collection), `snapshot_subtrees` (one checkpoint subtree per backing
    store).

Exactness: partitions are DISJOINT and exhaustive over the alive
membership, each returns an exact — or, under the running bound, a
provably sufficient — (value, id)-lex k-best over its rows, and the merge
is the same lexicographic rule `topk_rows_banded` uses across chunks.  So
a PartitionSet at ANY shard count is bit-identical to a single batch scan
of the same membership, for every mutation history and both metrics —
sharding, like tiering, is a pure serving optimisation with zero
bit-identity risk.  `TieredLayout` is the ``n_shards=1`` face of this
object, kept as an alias.

Crash safety: layouts are DERIVED state.  A sharded rebuild fires the
``shard.rebalance`` faultinject point before any group is replaced, so an
injected crash leaves the previous groups intact and the next sync simply
retries — the crash-matrix entry in tests/test_faultinject.py pins that
serving stays exact through it.
"""

from __future__ import annotations

import functools
import weakref

import numpy as np
import jax
import jax.numpy as jnp

from repro import obs
from repro.core import allpairs
from repro.core.allpairs import KBEST_KEY_PAD, kbest_lex_merge
from repro.core.packing import padded_take
from repro.index.bands import BandedLayout
from repro.index.mergeable import MergeIncompatible, check_spec_compatible
from repro.index.store import SketchStore
from repro.obs.registry import NULL_REGISTRY
from repro.runtime import faultinject

_CP_REBALANCE = faultinject.declare("shard.rebalance")

PARTITION_KINDS = ("sorted-banded", "brute-delta")


# ---------------------------------------------------------------------------
# the one cross-partition merge rule
# ---------------------------------------------------------------------------


def merge_topk_parts(kk: int, parts: list[tuple[np.ndarray, np.ndarray]]
                     ) -> tuple[np.ndarray, np.ndarray]:
    """Merge per-partition k-best lists into THE exact (value, id)-lex
    k-best: `parts` is a list of (ids (Q, <=kk), vals (Q, <=kk)) answers
    over DISJOINT row partitions, each already exact (or provably
    sufficient under a running k-th bound) over its partition.  Shared by
    the base+delta tier merge, the cross-shard merge, and the migration's
    cross-spec (old store / new store / fresh store) merge — one rule, so
    partitioned serving is bit-identical to a single scan by construction.
    Short lists are padded with (KBEST_KEY_PAD, inf), which sorts after any
    real candidate; pads survive only when the union holds < kk rows.

    kk must be >= 0; an empty `parts` list returns the well-typed empty
    answer ((0, kk) ids / vals) — there are zero queries to answer for."""
    if kk < 0:
        raise ValueError(f"merge_topk_parts: k must be >= 0, got {kk}")
    if len(parts) == 0:
        return (np.zeros((0, kk), np.int64), np.zeros((0, kk), np.float32))
    if len(parts) == 1:
        return parts[0]  # a lone partition is already the exact k'-best

    def pad_cols(ids: np.ndarray, vals: np.ndarray):
        have = ids.shape[1]
        if have == kk:
            return ids, vals
        padw = ((0, 0), (0, kk - have))
        return (np.pad(ids, padw, constant_values=KBEST_KEY_PAD),
                np.pad(vals, padw, constant_values=np.inf))

    padded = [pad_cols(i, v) for i, v in parts]
    vals, ids = kbest_lex_merge(
        kk, np.concatenate([v for _, v in padded], axis=1),
        np.concatenate([i for i, _ in padded], axis=1))
    return ids, vals


def _tighten(running: np.ndarray | None, vals: np.ndarray, kk: int
             ) -> np.ndarray | None:
    """Fold a merged candidate list into the running global k-th bound.
    The bound only ever tightens; lists still short of kk columns carry
    no bound (and inf pads inside a full-width list are harmless — the
    min just keeps the previous bound there)."""
    if vals.shape[1] < kk:
        return running
    kth = vals[:, kk - 1]
    return kth.copy() if running is None else np.minimum(running, kth)


def shard_of(ids: np.ndarray, n_shards: int) -> np.ndarray:
    """THE row-routing rule: ``id % n_shards``.  Deterministic and
    history-independent, so the same membership shards identically no
    matter how it was built, and stable across compaction (ids survive,
    slots don't).  Slot-level routing lives on the store
    (`SketchStore.route_slots`, the same rule)."""
    return np.asarray(ids, np.int64) % int(n_shards)


# ---------------------------------------------------------------------------
# Partition: one tier of one shard
# ---------------------------------------------------------------------------


class Partition:
    """One unit of partitioned serving state (see module docstring).

    ``sorted-banded`` wraps a `BandedLayout` over the given slot subset
    (weight-sorted, banded, progressive-walk served); ``brute-delta``
    holds an unsorted slot list in ascending id order, gathered lazily to
    a pow2-padded device matrix and scanned brute-force.  Both carry the
    device they are placed on and the SketchSpec their rows were sketched
    under; alive masks thread through without rebuilds (`refresh`).
    """

    __slots__ = ("kind", "shard", "device", "spec", "banded",
                 "slots", "ids", "_cache", "_store")

    def __init__(self, kind: str, shard: int, store: SketchStore, *,
                 device=None, metric: str | None = None,
                 band_rows: int = 1024, registry=None,
                 slots: np.ndarray | None = None):
        if kind not in PARTITION_KINDS:
            raise ValueError(
                f"partition kind must be one of {PARTITION_KINDS}, "
                f"got {kind!r}")
        self.kind = kind
        self.shard = int(shard)
        self.device = device
        self.spec = store.spec
        self._store = store
        if kind == "sorted-banded":
            self.banded = BandedLayout(store, metric, band_rows=band_rows,
                                       registry=registry, slots=slots,
                                       device=device)
            self.slots = self.banded.slots
            self.ids = self.banded.ids
        else:
            self.banded = None
            self.slots = (np.zeros(0, np.int64) if slots is None
                          else np.asarray(slots, np.int64))
            self.ids = store.ids_at(self.slots)
        self._cache: jnp.ndarray | None = None

    @property
    def n_rows(self) -> int:
        """Alive rows this partition serves."""
        if self.banded is not None:
            return self.banded.n_alive
        return len(self.slots)

    # -- brute-delta maintenance (O(delta) host work, no device traffic) ----

    def extend(self, slots: np.ndarray) -> None:
        """Append fresh store slots (brute-delta only) — the gathered view
        is invalidated, not rebuilt: a burst of adds between two queries
        pays for one gather, not one per mutation."""
        if len(slots):
            self.slots = np.concatenate([self.slots, slots])
            self._cache = None

    def refresh(self, store: SketchStore,
                mask: np.ndarray | None = None) -> None:
        """Drop tombstoned slots (they never resurrect; `mask` is the
        alive bitmap the owning set's sync already read, when it read one)
        and re-read the id map — the brute-delta twin of
        `BandedLayout.refresh_alive`."""
        changed = False
        if mask is not None and not mask.all():
            self.slots = self.slots[mask]
            changed = True
        if changed or len(self.slots) != len(self.ids):
            self._cache = None
        self.ids = store.ids_at(self.slots)
        self._store = store

    @property
    def matrix(self) -> jnp.ndarray | None:
        """The pow2-padded device matrix, gathered lazily at first use
        after a sync and committed to this partition's device (so the
        distance tiles against it run THERE — uncommitted query arrays
        follow committed operands).  jnp.take copies, so the view survives
        later donated appends to the store buffer."""
        if self.banded is not None:
            return self.banded.matrix
        if self._cache is None and len(self.slots):
            m = padded_take(self._store.sk_buf, self.slots)
            if self.device is not None:
                m = jax.device_put(m, self.device)
            self._cache = m
        return self._cache


class _ShardGroup:
    """One shard's (base, delta) partition pair."""

    __slots__ = ("shard", "device", "base", "delta")

    def __init__(self, shard: int, device, base: Partition,
                 delta: Partition):
        self.shard = shard
        self.device = device
        self.base = base
        self.delta = delta


# ---------------------------------------------------------------------------
# PartitionSet: the serving object
# ---------------------------------------------------------------------------


class PartitionSet:
    """`n_shards` (base, delta) partition groups over one store — the
    engine's serving structure (DESIGN.md sections 8.5 and 13).

    Per shard, the base partition is a `BandedLayout` over the shard's
    membership at the last fold; fresh adds route by ``id % n_shards``
    into per-shard brute-delta partitions; removes flip per-partition
    alive masks.  `sync` advances the set across any version range of the
    same slot epoch in O(delta); compaction (an epoch bump) rebuilds, and
    the size-ratio merge policy folds each shard's delta into its base
    INDEPENDENTLY (shard-local compaction — one hot shard folding does
    not touch its siblings).

    `topk` walks the groups accumulating a global running k-th bound:
    each banded walk receives the bound as `init_kth` and prunes against
    it, each partial answer merges through `merge_topk_parts`, and the
    bound tightens after every merge.  Exactness per partition + disjoint
    memberships + the shared lex merge = bit-identical to one batch scan,
    at every shard count, for every mutation history and both metrics.

    With ``n_shards=1`` this is exactly the old TieredLayout (the alias
    below); `devices` places shard s's matrices on
    ``devices[s % len(devices)]`` (None: default device — logical
    sharding, which CI exercises without a mesh).
    """

    def __init__(self, store: SketchStore, metric: str,
                 band_rows: int = 1024, merge_ratio: float | None = 0.125,
                 registry=None, n_shards: int = 1, devices=None,
                 role: str = "serve"):
        if int(n_shards) < 1:
            raise ValueError(f"n_shards must be >= 1, got {n_shards}")
        self.metric = metric
        self.d = store.d
        self.band_rows = int(band_rows)
        self.merge_ratio = merge_ratio
        self.registry = NULL_REGISTRY if registry is None else registry
        self.n_shards = int(n_shards)
        self.devices = list(devices) if devices else None
        self.role = role
        self.n_merges = -1  # the initial build below is not a merge
        self._groups: list[_ShardGroup] = []
        self._rebuild(store)
        self._register_gauges()

    def _device_for(self, shard: int):
        if not self.devices:
            return None
        return self.devices[shard % len(self.devices)]

    # -- construction / synchronisation ------------------------------------

    def _build_group(self, shard: int, store: SketchStore,
                     slots: np.ndarray) -> _ShardGroup:
        dev = self._device_for(shard)
        base = Partition("sorted-banded", shard, store, device=dev,
                         metric=self.metric, band_rows=self.band_rows,
                         registry=self.registry, slots=slots)
        delta = Partition("brute-delta", shard, store, device=dev)
        return _ShardGroup(shard, dev, base, delta)

    def _rebuild(self, store: SketchStore) -> None:
        """Re-route the alive membership to shards and fold every shard
        into a freshly sorted base partition (the O(N log N) path `sync`
        exists to avoid paying per mutation).  The groups are built into a
        local list and swapped in at the end: an injected crash at the
        ``shard.rebalance`` point (or a real one) leaves the previous —
        stale but internally consistent — groups in place, and the next
        sync retries.  Layouts are derived state; the store is never
        touched."""
        if self.n_shards > 1:
            faultinject.crash_point(_CP_REBALANCE)
        slots = store.alive_slots()
        groups = [self._build_group(s, store, sh_slots)
                  for s, sh_slots in enumerate(
                      store.route_slots(slots, self.n_shards))]
        self._groups = groups
        self._store = store
        # per-set spec record: every row this set serves was sketched
        # under it, and the cross-version merge keys the query sketch on it
        self.spec = store.spec
        st = store.stamp()
        self.version, self.epoch, self.seen_size = (
            st.version, st.epoch, st.size)
        self.seen_removed = store.removed_count
        self.n_merges += 1

    def _fold_group(self, g: _ShardGroup, store: SketchStore) -> None:
        """Shard-local merge: fold ONE shard's delta back into its base.
        Siblings keep their layouts (and their band walks' warm device
        matrices) untouched — the policy that makes a hot shard's churn a
        local cost."""
        slots = store.alive_slots()
        if self.n_shards > 1:
            keep = shard_of(store.ids_at(slots), self.n_shards) == g.shard
            slots = slots[keep]
        fresh = self._build_group(g.shard, store, slots)
        g.base, g.delta = fresh.base, fresh.delta
        self.n_merges += 1

    def sync(self, store: SketchStore) -> "PartitionSet":
        """Advance to the store's current (version, epoch) — THE entry the
        engine calls before serving.  Version unchanged: free.  Adds
        within the epoch: route the new slots to the per-shard delta
        partitions (O(delta)).  Removes: refresh the per-partition alive
        masks (O(n) host bitmap reads).  Epoch change (compaction) or
        merge_ratio=0: full rebuild; the merge policy tripping folds only
        the shard that tripped it."""
        st = store.stamp()
        self._store = store
        if (st.version, st.epoch) == (self.version, self.epoch):
            return self
        if st.epoch != self.epoch or self.merge_ratio == 0:
            # epoch bump (compaction renumbered slots), or merge_ratio=0:
            # the pre-tiered rebuild-per-version baseline, which rebuilt
            # on EVERY mutation — removes included
            self._rebuild(store)
            return self
        added = st.size > self.seen_size
        new_by_shard = None
        if added:
            new_by_shard = store.route_slots(
                store.tail_slots(self.seen_size), self.n_shards)
            self.seen_size = st.size
        removed = store.removed_count != self.seen_removed
        if removed:
            self.seen_removed = store.removed_count
        for g in self._groups:
            if added:
                g.delta.extend(new_by_shard[g.shard])
            delta_mask = None
            if removed:
                # only a version range that actually contains removes pays
                # the O(n) host bitmap re-read — append-heavy traffic skips
                g.base.banded.refresh_alive(store)
                delta_mask = store.alive_at(g.delta.slots)
                live_delta = int(np.count_nonzero(delta_mask))
            else:
                live_delta = len(g.delta.slots)
            base_alive = g.base.banded.n_alive
            dead_base = g.base.banded.n - base_alive
            # merge policy (per shard): fold when the delta outgrows its
            # share of the base (brute-force delta scans stop being cheap),
            # or when tombstones outnumber alive base rows.  None never
            # auto-folds (the caller manages folding via compact()).
            if (self.merge_ratio is not None
                    and (live_delta > self.merge_ratio * max(base_alive, 1)
                         or dead_base > max(base_alive, 1))):
                self._fold_group(g, store)
                continue
            if added or removed:
                g.delta.refresh(store, delta_mask)
        self.version = st.version
        return self

    # -- merge (the Mergeable contract, repro.index.mergeable) --------------

    def merge(self, other: "PartitionSet | None" = None) -> "PartitionSet":
        """Absorb the backing store's just-merged rows and return self —
        the layout half of the Mergeable contract, called by
        `QueryEngine.merge` AFTER `SketchStore.merge` committed.

        Layouts are DERIVED state, so the merge IS a sync against the
        already-merged store: an append-path store merge arrives as
        ordinary tail slots, re-routed by ``id % n_shards`` into each
        shard's brute-delta partition (shard-local absorption — no base
        rebuild, sibling shards untouched until their own fold policy
        trips); an interleave-path merge bumped the store epoch, so the
        set rebuilds, exactly as after a compaction.  `other` (the
        discarded set of the absorbed store, when one exists) is only
        VALIDATED — metric/spec compatibility — never read: its
        partitions index a store that no longer serves.  Gauges re-point
        at the live groups afterwards (a registry merge may have frozen
        them to snapshot values)."""
        if other is not None:
            if other.metric != self.metric:
                raise MergeIncompatible(
                    f"PartitionSet.merge: metric mismatch "
                    f"({self.metric!r} vs {other.metric!r})")
            if self.spec is not None or other.spec is not None:
                check_spec_compatible(other.spec, self.spec,
                                      what="PartitionSet.merge")
        self.sync(self._store)
        self._register_gauges()
        return self

    # -- introspection ------------------------------------------------------

    def partitions(self) -> list[Partition]:
        """Every partition in shard order, base before delta — the
        introspection surface obs gauges and tests read."""
        out: list[Partition] = []
        for g in self._groups:
            out.append(g.base)
            out.append(g.delta)
        return out

    @property
    def base(self) -> BandedLayout:
        """The single-shard base tier (introspection + tests).  A sharded
        set has one base PER SHARD — use `partitions()` there."""
        if len(self._groups) != 1:
            raise AttributeError(
                f"a {self.n_shards}-shard PartitionSet has no single base "
                "tier; iterate partitions()")
        return self._groups[0].base.banded

    @property
    def delta_n(self) -> int:
        return sum(g.delta.n_rows for g in self._groups)

    @property
    def n_alive(self) -> int:
        return sum(g.base.n_rows + g.delta.n_rows for g in self._groups)

    @property
    def base_rows(self) -> int:
        return sum(g.base.banded.n for g in self._groups)

    @property
    def base_alive(self) -> int:
        return sum(g.base.banded.n_alive for g in self._groups)

    @property
    def n_bands(self) -> int:
        return sum(g.base.banded.n_bands for g in self._groups)

    # -- obs ----------------------------------------------------------------

    def _register_gauges(self) -> None:
        """Per-partition structural gauges: `partition_rows` labelled by
        (shard, kind, role, device) — read-time callbacks onto the live
        groups, so a fold or rebalance is visible at the next scrape.
        Re-registering the same labels (a successor set after a migration
        publish) swaps the callback to the newest set.  The callbacks hold
        the set weakly: a set the engine dropped (after `shard()` changed
        the labels) reads 0 and frees its device matrices."""
        if self.registry.is_null:
            return
        ref = weakref.ref(self)

        def rows(shard: int, kind: str) -> float:
            live = ref()
            return 0.0 if live is None else float(live._rows_of(shard, kind))

        for g in self._groups:
            dev = "host" if g.device is None else str(g.device)
            for kind in PARTITION_KINDS:
                self.registry.gauge_fn(
                    "partition_rows", functools.partial(rows, g.shard, kind),
                    shard=str(g.shard), kind=kind, role=self.role,
                    device=dev)

    def _rows_of(self, shard: int, kind: str) -> int:
        if shard >= len(self._groups):
            return 0
        g = self._groups[shard]
        return g.base.n_rows if kind == "sorted-banded" else g.delta.n_rows

    # -- serving ------------------------------------------------------------

    def topk(self, queries_padded: jnp.ndarray, query_weights: np.ndarray,
             k: int, *, q_valid: int, block: int = 2048,
             mode: str | None = None, deadline=None,
             info_out: dict | None = None,
             init_kth: np.ndarray | None = None
             ) -> tuple[np.ndarray, np.ndarray]:
        """Cross-partition k-NN: (ids (Q, k'), dists (Q, k')), k' = min(k,
        n_alive), ascending by (distance, id) — bit-identical to
        core.allpairs.topk_rows over the full alive membership in id
        order, at every shard count.

        Groups are walked in shard order, base partition then delta; the
        running global k-th bound tightens after every merge and enters
        the next banded walk as its `init_kth`, so a tight bound from an
        early shard prunes (possibly ALL of) a later shard's bands.
        `deadline` budgets every banded walk (per-partition budgets — the
        brute-delta scans are already O(delta) and exact); any partial
        walk makes the merged answer partial, with the max residual
        cert_gap.  `init_kth` seeds the bound from partitions OUTSIDE this
        set (the cross-spec mid-migration merge)."""
        if info_out is not None:
            info_out.update(partial=False, cert_gap=0.0)
        kk = min(k, self.n_alive)
        if kk <= 0 or q_valid == 0:
            return (np.zeros((q_valid, 0), np.int64),
                    np.zeros((q_valid, 0), np.float32))
        best: tuple[np.ndarray, np.ndarray] | None = None
        running = (None if init_kth is None
                   else np.asarray(init_kth, np.float32)[:q_valid])
        partial, cert_gap = False, 0.0
        bands_visited = rows_visited = 0
        want_info = info_out is not None or deadline is not None
        with obs.span("partition.topk", shards=self.n_shards, k=kk,
                      role=self.role):
            for g in self._groups:
                if g.base.banded.n_alive:
                    st: dict | None = {} if want_info else None
                    part = g.base.banded.topk(
                        queries_padded, query_weights, kk, q_valid=q_valid,
                        block=block, mode=mode, deadline=deadline,
                        info_out=st, init_kth=running)
                    if st is not None:
                        partial |= bool(st.get("partial"))
                        cert_gap = max(cert_gap, st.get("cert_gap", 0.0))
                        bands_visited += st.get("bands_visited", 0)
                        rows_visited += st.get("rows_visited", 0)
                    best = (part if best is None
                            else merge_topk_parts(kk, [best, part]))
                    running = _tighten(running, best[1], kk)
                if g.delta.n_rows:
                    # pad_k keeps k == kk even while the delta holds fewer
                    # rows: k is a static jit arg, so letting it track the
                    # delta size would recompile on every add (tail pads
                    # merge away below)
                    pos, vals = allpairs.topk_rows(
                        queries_padded, g.delta.matrix, kk, d=self.d,
                        metric=self.metric, block=block, mode=mode,
                        m_valid=g.delta.n_rows, pad_k=True)
                    pos, vals = pos[:q_valid], vals[:q_valid]
                    ids = np.full(pos.shape, KBEST_KEY_PAD, np.int64)
                    real = pos >= 0
                    ids[real] = g.delta.ids[pos[real]]
                    part = (ids, vals)
                    best = (part if best is None
                            else merge_topk_parts(kk, [best, part]))
                    running = _tighten(running, best[1], kk)
        if info_out is not None:
            info_out.update(partial=partial, cert_gap=cert_gap,
                            bands_visited=bands_visited,
                            rows_visited=rows_visited)
        assert best is not None  # kk > 0 implies some non-empty partition
        return best

    def radius_tiers(self, query_weights: np.ndarray, radius: float
                     ) -> list[tuple[jnp.ndarray, int, np.ndarray]]:
        """Per-partition (matrix, n_selected, ids) selections for a radius
        query: each shard's base after its band prune, each delta whole
        (it is small by the merge policy — brute-force is the prune).
        Partition memberships partition the alive set, so the per-tier
        `threshold_pairs` hits union to exactly the batch engine's answer
        on the full membership."""
        out = []
        with obs.span("partition.radius_tiers", shards=self.n_shards):
            for g in self._groups:
                bl = g.base.banded
                if bl.n_alive:
                    mask = bl.candidate_bands(query_weights, radius)
                    if not self.registry.is_null:
                        kept = int(np.count_nonzero(mask))
                        bl._c_queries.inc()
                        bl._c_visited.inc(kept)
                        bl._c_pruned.inc(bl.n_bands - kept)
                    sel, n_sel, sel_ids = bl.select(mask)
                    if n_sel:
                        out.append((sel, n_sel, sel_ids))
                if g.delta.n_rows:
                    out.append((g.delta.matrix, g.delta.n_rows,
                                g.delta.ids))
        return out


# the n_shards=1 face of PartitionSet — the name the LSM-tier PRs used
TieredLayout = PartitionSet


# ---------------------------------------------------------------------------
# cross-set serving helpers (the mid-migration / cross-spec paths)
# ---------------------------------------------------------------------------


def topk_across_tiers(kk: int, tiers, *, q_valid: int, block: int,
                      mode: str | None
                      ) -> tuple[np.ndarray, np.ndarray]:
    """Global (value, id)-lex k-best across PARTITION SETS — the
    mid-migration path, where each tier is a whole PartitionSet over one
    store under one spec and the query was sketched once per spec.
    `tiers` is a list of (layout, queries_padded, query_weights); the
    running k-th bound threads ACROSS sets too (each set receives it as
    `init_kth` and returns a sufficient part), so the merged answer equals
    merging per-store reference answers, each under its own spec."""
    best: tuple[np.ndarray, np.ndarray] | None = None
    running: np.ndarray | None = None
    with obs.span("partition.merge", tiers=len(tiers), k=kk):
        for layout, queries_padded, query_weights in tiers:
            part = layout.topk(queries_padded, query_weights, kk,
                               q_valid=q_valid, block=block, mode=mode,
                               init_kth=running)
            best = (part if best is None
                    else merge_topk_parts(kk, [best, part]))
            running = _tighten(running, best[1], kk)
    if best is None:
        return (np.zeros((q_valid, 0), np.int64),
                np.zeros((q_valid, 0), np.float32))
    return best


def radius_hits(layout, queries_padded: jnp.ndarray,
                query_weights: np.ndarray, q: int, r: float, *,
                metric: str, block: int, mode: str | None,
                hits: list[list[np.ndarray]]) -> None:
    """Accumulate one PartitionSet's radius hits into per-query buckets —
    the shared half of `QueryEngine.radius` and its mid-migration twin:
    per-partition threshold scans, then ONE sort/group pass per selection
    instead of a pairs scan per query."""
    for sel, n_sel, sel_ids in layout.radius_tiers(query_weights, r):
        pairs = allpairs.threshold_pairs(
            queries_padded, sel, d=layout.d, threshold=r, metric=metric,
            block=block, mode=mode, n_valid=q, m_valid=n_sel)
        with obs.span("partition.radius_group", pairs=len(pairs)):
            by_q = pairs[np.argsort(pairs[:, 0], kind="stable")]
            splits = np.searchsorted(by_q[:, 0], np.arange(q + 1))
            for qi in range(q):
                seg = sel_ids[by_q[splits[qi]: splits[qi + 1], 1]]
                if seg.size:
                    hits[qi].append(seg)


def snapshot_subtrees(store: SketchStore, raw=None, migration=None) -> dict:
    """Per-partition snapshot subtrees: one checkpoint subtree per backing
    store (layouts are derived state and are never persisted — a restored
    engine rebuilds them, sharded or not, from the stores alone).  The
    subtree names are the `repro.index.v2` on-disk contract
    `QueryEngine.restore` reads."""
    tree: dict = {"store": store.state_tree()}
    if raw is not None:
        tree["raw"] = raw.state_tree()
    if migration is not None:
        tree["mig_dst"] = migration.dst.state_tree()
        tree["mig_fresh"] = migration.fresh.state_tree()
    return tree
