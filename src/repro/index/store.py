"""SketchStore: a growing, device-resident collection of packed sketches.

The batch engine (repro.core.allpairs) answers "given these two matrices,
which pairs are close" — a one-shot question.  A serving system instead owns
a COLLECTION that mutates between queries: documents arrive, stale ones are
deleted, and every query must see the current membership without paying a
rebuild.  SketchStore is that collection, designed around two invariants
(DESIGN.md section 8.1):

  * Power-of-two buffers.  Sketches and their Hamming weights live in device
    buffers whose capacity is always a power of two; appends write through a
    single jitted dynamic_update_slice whose compile key is the (bucketed)
    buffer and batch shape.  Across any mutation history the store compiles
    O(log N) append graphs total — `add` and `remove` never trigger per-call
    recompiles, which is the difference between O(100us) and O(100ms) per
    request on a warm server.
  * Insertion-order slots.  Slot order equals id order: appends go to the
    tail, deletes only tombstone (a host-side bitmap — the device buffer is
    untouched), and compaction preserves relative order.  Alive rows are
    therefore always a stable, id-sorted sequence, which is what makes query
    results bit-identical to a fresh batch build no matter how the store
    got to its current membership (the tier-1 property tests pin this).

Host mirrors (ids, alive bitmap, weights) ride along for planning work that
is latency-bound rather than bandwidth-bound: band layout, capacity checks,
and id translation all happen on host without touching the device buffers.

Stores are MERGEABLE (repro.index.mergeable, DESIGN.md section 14): the
collection is no longer single-writer-only.  N workers may build private
stores in parallel and `merge` combines them — id-disjoint, spec-checked,
and through the same jitted append graph as `add` when the inputs' id
ranges don't interleave (the merge-tree bulk-load case), so a combine
costs one device concat, not a recompile or a re-sketch.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import packing
from repro.core.cabin import CabinParams
from repro.core.packing import pow2_bucket  # the shared bucketing rule
from repro import obs
from repro.index.mergeable import (MergeIncompatible, check_id_disjoint,
                                   check_spec_compatible)
from repro.obs.registry import NULL_REGISTRY
from repro.runtime import faultinject

_CP_COMPACT = faultinject.declare("store.compact")
_CP_MERGE = faultinject.declare("merge.combine")


@dataclass(frozen=True)
class SketchSpec:
    """A VERSIONED sketch-space identity: the CabinParams every row in a
    store was sketched under, plus a monotone generation counter.

    The params alone already define the sketch space; the version exists so
    serving code can ask the cheap question "same space?" without comparing
    seeds, and so snapshots/journals can name which generation a tier
    belongs to.  index/migrate.py moves an engine from spec v to v+1 by
    re-sketching rows — two stores with different specs hold incomparable
    bits, and the cross-version serving path must sketch each query once
    per spec it touches.
    """

    version: int
    params: CabinParams

    @property
    def d(self) -> int:
        return self.params.sketch_dim

    def successor(self, params: CabinParams) -> "SketchSpec":
        if params.n_dims != self.params.n_dims:
            raise ValueError(
                f"spec migration cannot change n_dims "
                f"({self.params.n_dims} -> {params.n_dims}): the raw rows "
                "live in the original categorical space")
        return SketchSpec(self.version + 1, params)

    def meta(self) -> dict:
        return {"version": self.version, "n_dims": self.params.n_dims,
                "sketch_dim": self.params.sketch_dim,
                "psi_seed": self.params.psi_seed,
                "pi_seed": self.params.pi_seed}

    @classmethod
    def from_meta(cls, m: dict) -> "SketchSpec":
        return cls(int(m["version"]), CabinParams(
            n_dims=int(m["n_dims"]), sketch_dim=int(m["sketch_dim"]),
            psi_seed=int(m["psi_seed"]), pi_seed=int(m["pi_seed"])))


class VersionStamp(NamedTuple):
    """A store snapshot identity for layout synchronisation.

    `version` counts every mutation; `epoch` counts only the mutations that
    invalidate SLOT identity (compaction — slots shuffle); `size` is the
    append watermark.  Within one epoch, the rows added between two stamps
    are exactly the slots [old.size, new.size) (`tail_slots`), which is what
    lets the tiered layout absorb adds as an O(delta) delta tier instead of
    rebuilding on every version bump.
    """

    version: int
    epoch: int
    size: int


class AliveView(tuple):
    """The (matrix, n_alive, ids) triple from `gather_alive`, stamped with
    the store version it was taken at.

    Unpacks like the plain 3-tuple it always was; the extra `.version`
    attribute lets consumers (`SketchStore.check_fresh`) reject a view held
    across a mutation with a clear error instead of the accelerator
    backends' late "Array has been deleted" (the append fast path returns
    the live buffer, which the next `add` donates)."""

    def __new__(cls, matrix, n_alive, ids, version: int):
        self = tuple.__new__(cls, (matrix, n_alive, ids))
        self.version = version
        return self

    @property
    def matrix(self):
        return self[0]

    @property
    def n_alive(self) -> int:
        return self[1]

    @property
    def ids(self) -> np.ndarray:
        return self[2]


def _append_rows_fn(sk_buf, wt_buf, rows, start):
    """Write a (kpad, w) batch at a traced offset.  Rows past the caller's
    valid count land in slots beyond `size` — they are never alive and the
    next append overwrites them, so they never escape."""
    sk_buf = jax.lax.dynamic_update_slice(sk_buf, rows, (start, 0))
    wt_buf = jax.lax.dynamic_update_slice(
        wt_buf, packing.popcount_rows(rows), (start,))
    return sk_buf, wt_buf


@functools.cache
def _append_rows() -> "jax.stages.Wrapped":
    """The jitted append, built at first use: it donates the buffers so
    accelerator appends update in place (no O(capacity) copy per request);
    CPU has no donation — skip it there to avoid the per-call "donated
    buffers were not usable" warning.  Deciding at first use, not at
    import, keeps `import repro.index` from initialising a JAX backend."""
    donate = (0, 1) if jax.default_backend() != "cpu" else ()
    return jax.jit(_append_rows_fn, donate_argnums=donate)


class SketchStore:
    """Append/tombstone/compact container for packed d-bit sketches.

    Rows are addressed by EXTERNAL ids (monotone int64, assigned at `add`,
    stable across compaction and checkpoint restore) — never by slot.
    """

    def __init__(self, d: int, spec: SketchSpec | None = None):
        self.spec = spec  # which sketch space the rows live in (may be None
        # for spec-agnostic uses; the engine always sets it)
        if spec is not None and spec.d != int(d):
            raise ValueError(f"d={d} disagrees with spec.d={spec.d}")
        self.d = int(d)
        self.w = packing.packed_width(self.d)
        cap = pow2_bucket(0)
        self._sk_buf = jnp.zeros((cap, self.w), jnp.int32)
        self._wt_buf = jnp.zeros((cap,), jnp.int32)
        self._ids = np.zeros(cap, np.int64)
        self._alive = np.zeros(cap, bool)
        self._weights = np.zeros(cap, np.int64)
        self._size = 0  # slots in use (alive + tombstoned)
        self._n_alive = 0
        self._next_id = 0
        self.version = 0  # bumped on every mutation; caches key on it
        self._epoch = 0  # bumped only when slot identity changes (compact)
        self._n_removed_total = 0  # monotone; lets layouts skip mask work
        self._placement = None  # opt-in sharding callback (see `place`)
        self._gather_cache: tuple | None = None
        self._listeners: list = []  # mutation observers (see `subscribe`)
        self.set_registry(None)

    def set_registry(self, registry) -> None:
        """Point the store's mutation counters at a MetricsRegistry (None
        resets to the shared no-op registry).  The engine calls this with
        its per-engine registry so ingest/tombstone/compaction volume shows
        up next to the query histograms it drives."""
        reg = NULL_REGISTRY if registry is None else registry
        self._c_added = reg.counter("store_rows_added_total")
        self._c_removed = reg.counter("store_rows_removed_total")
        self._c_compactions = reg.counter("store_compactions_total")
        self._c_merges = reg.counter("store_merges_total")

    # -- introspection ------------------------------------------------------

    def __len__(self) -> int:
        return self._n_alive

    @property
    def capacity(self) -> int:
        return self._sk_buf.shape[0]

    @property
    def size(self) -> int:
        """Slots in use, including tombstones (compact() to reclaim)."""
        return self._size

    @property
    def epoch(self) -> int:
        """Slot-identity generation: stable across add/remove (slots only
        append or tombstone), bumped by `compact` (slots shuffle).  Layouts
        that cache slot positions are valid exactly while it holds."""
        return self._epoch

    def stamp(self) -> VersionStamp:
        """(version, epoch, size) — the identity a layout snapshot records
        so a later `tail_slots`/alive-mask sync can replay just the delta."""
        return VersionStamp(self.version, self._epoch, self._size)

    @property
    def removed_count(self) -> int:
        """Monotone count of rows ever tombstoned.  A layout that recorded
        it at its last sync can tell "this version range contains no
        removes" without touching the bitmap — the common mutation mix
        (append-heavy) then pays zero alive-mask work per sync."""
        return self._n_removed_total

    def tail_slots(self, since_size: int) -> np.ndarray:
        """Slots appended since a stamp taken at `since_size` — the
        per-version row range a delta tier is built from.  Only valid
        within the stamp's epoch (compaction renumbers slots; compare
        `epoch` first)."""
        if not 0 <= since_size <= self._size:
            raise ValueError(
                f"since_size={since_size} outside the store's slot range "
                f"[0, {self._size}] (stale stamp from another epoch?)")
        return np.arange(since_size, self._size, dtype=np.int64)

    def alive_at(self, slots: np.ndarray) -> np.ndarray:
        """Alive bitmap at the given slots (host, no device sync)."""
        return self._alive[slots]

    def ids_at(self, slots: np.ndarray) -> np.ndarray:
        """External ids at the given slots (host, no device sync)."""
        return self._ids[slots]

    def weights_at(self, slots: np.ndarray) -> np.ndarray:
        """Host sketch Hamming weights at the given slots."""
        return self._weights[slots]

    @property
    def sk_buf(self) -> jnp.ndarray:
        """The live packed-sketch buffer.  On accelerator backends the next
        `add` donates it — do not hold across mutations (see
        gather_alive)."""
        return self._sk_buf

    def alive_slots(self) -> np.ndarray:
        """Slots of alive rows, in slot (= insertion = id) order."""
        return np.flatnonzero(self._alive[: self._size])

    def route_slots(self, slots: np.ndarray, n_shards: int
                    ) -> list[np.ndarray]:
        """Split `slots` by shard assignment — THE row-routing rule is
        ``id % n_shards``: deterministic, history-independent (the same
        membership shards identically no matter how it was built), and
        stable across compaction (ids survive, slots don't).  Within each
        shard the incoming ascending-id order is preserved, which is what
        keeps sharded and unsharded layout builds bit-comparable."""
        if int(n_shards) == 1:
            return [slots]
        shard = self._ids[slots] % int(n_shards)
        return [slots[shard == s] for s in range(int(n_shards))]

    def ids(self) -> np.ndarray:
        """External ids of alive rows, ascending."""
        return self._ids[self.alive_slots()]

    def weights(self) -> np.ndarray:
        """Host sketch Hamming weights of alive rows, in id order."""
        return self._weights[self.alive_slots()]

    def contains(self, id_: int) -> bool:
        slot = np.searchsorted(self._ids[: self._size], id_)
        return (slot < self._size and self._ids[slot] == id_
                and bool(self._alive[slot]))

    # -- mutation observers -------------------------------------------------

    def subscribe(self, callback) -> None:
        """Register `callback(event, ids, slots)` to run after every
        mutation commits — the hook per-row SIDECARS (repro.cluster's
        ClusterIndex labels, or any structure keyed on membership) use to
        stay in sync even when the store is mutated directly, not through
        them.  Events: "add" (ids/slots of the appended rows — the slots
        are valid immediately, so the callback may gather the new sketches
        before any later append donates the buffer), "remove" (ids/slots
        tombstoned), "merge" (ids/slots of another store's ALIVE rows just
        absorbed by `merge` — same freshness guarantee as "add"; absorbed
        tombstones fire no event), "compact" (empty arrays; slot identity
        changed — read fresh state from the store).  Callbacks run
        synchronously inside
        the mutation, in subscription order; they must not mutate the
        store re-entrantly.  Pair with `unsubscribe` when the observer is
        discarded — the store holds a strong reference."""
        self._listeners.append(callback)

    def unsubscribe(self, callback) -> None:
        """Remove a `subscribe`d callback (ValueError if absent)."""
        self._listeners.remove(callback)

    def _notify(self, event: str, ids: np.ndarray, slots: np.ndarray) -> None:
        for cb in self._listeners:
            cb(event, ids, slots)

    # -- mutation -----------------------------------------------------------

    def _bump(self) -> None:
        self.version += 1
        self._gather_cache = None

    def _place(self, arr: jnp.ndarray) -> jnp.ndarray:
        if self._placement is None:
            return arr
        return jax.device_put(arr, self._placement(arr.shape))

    def _grow_to(self, cap: int) -> None:
        pad = cap - self.capacity
        self._sk_buf = self._place(jnp.pad(self._sk_buf, ((0, pad), (0, 0))))
        self._wt_buf = self._place(jnp.pad(self._wt_buf, ((0, pad),)))
        self._ids = np.pad(self._ids, (0, pad))
        self._alive = np.pad(self._alive, (0, pad))
        self._weights = np.pad(self._weights, (0, pad))

    def add(self, packed, n_valid: int | None = None) -> np.ndarray:
        """Append packed rows; returns their assigned ids (k,) int64.

        `packed` is (kp, w) int32; `n_valid` (default kp) marks how many
        leading rows are real — the engine hands over its power-of-two
        padded sketch batches unchanged, so no reshape happens here.
        """
        packed, k = self._check_batch(packed, n_valid)
        if k == 0:
            return np.zeros(0, np.int64)
        new_ids = np.arange(self._next_id, self._next_id + k, dtype=np.int64)
        return self._append(packed, k, new_ids, notify=True)

    def add_with_ids(self, packed, ids, n_valid: int | None = None,
                     *, notify: bool = False) -> np.ndarray:
        """Append packed rows under EXPLICIT external ids — the migration
        path (index/migrate.py), which rebuilds a store row-by-row while
        preserving the original id assignment.  `ids` must be strictly
        ascending and greater than every id already appended, so the
        slot-order == id-order invariant survives by construction.
        Defaults to notify=False: a migrated row is not new membership, and
        per-id sidecars (ClusterIndex labels) must NOT double-count it."""
        packed, k = self._check_batch(packed, n_valid)
        ids = np.atleast_1d(np.asarray(ids, np.int64))
        if len(ids) != k:
            raise ValueError(f"{len(ids)} ids for {k} valid rows")
        if k == 0:
            return np.zeros(0, np.int64)
        floor = self._ids[self._size - 1] if self._size else -1
        if ids[0] <= floor or (k > 1 and (np.diff(ids) <= 0).any()):
            raise ValueError(
                "add_with_ids requires strictly ascending ids above the "
                f"store's last id ({floor}); got head {ids[:4]}")
        return self._append(packed, k, ids, notify=notify)

    def add_packed(self, packed, spec: SketchSpec | None,
                   n_valid: int | None = None) -> np.ndarray:
        """Spec-checked `add`: the caller names the SketchSpec its packed
        rows were sketched under, and a mismatch with the store's spec
        raises MergeIncompatible naming BOTH specs — before any device
        work.  The check exists because a wrong `d` only fails later as an
        opaque jax shape error, and wrong hash seeds never fail at all
        (same shapes, silently corrupt distances).  `spec=None` asserts
        nothing beyond the width check (the trusting legacy path)."""
        if spec is not None:
            check_spec_compatible(spec, self.spec,
                                  what="SketchStore.add_packed")
        return self.add(packed, n_valid=n_valid)

    def _check_batch(self, packed, n_valid) -> tuple[jnp.ndarray, int]:
        packed = jnp.asarray(packed)
        if packed.ndim != 2 or packed.shape[1] != self.w:
            whose = "" if self.spec is None else \
                f" (store spec: d={self.spec.d}, v{self.spec.version})"
            raise ValueError(
                f"expected (k, {self.w}) packed rows, got "
                f"{packed.shape}{whose}")
        k = packed.shape[0] if n_valid is None else int(n_valid)
        if not 0 <= k <= packed.shape[0]:
            raise ValueError(
                f"n_valid={k} outside the {packed.shape[0]} supplied rows")
        return packed, k

    def _append(self, packed: jnp.ndarray, k: int, new_ids: np.ndarray,
                *, notify: bool) -> np.ndarray:
        with obs.span("store.add", rows=k):
            kpad = pow2_bucket(k)
            if packed.shape[0] < kpad:
                packed = jnp.pad(packed, ((0, kpad - packed.shape[0]), (0, 0)))
            elif packed.shape[0] > kpad:
                packed = packed[:kpad]
            if self._size + kpad > self.capacity:
                self._grow_to(pow2_bucket(self._size + kpad))
            self._sk_buf, self._wt_buf = _append_rows()(
                self._sk_buf, self._wt_buf, packed, jnp.int32(self._size))
            if self._placement is not None:
                self._sk_buf = self._place(self._sk_buf)
                self._wt_buf = self._place(self._wt_buf)
            sl = slice(self._size, self._size + k)
            self._ids[sl] = new_ids
            self._alive[sl] = True
            # host weight mirror reads back the device popcounts just
            # written by _append_rows — k ints, cheaper than re-deriving
            # from the packed batch on host
            self._weights[sl] = np.asarray(self._wt_buf[sl], np.int64)
            self._size += k
            self._n_alive += k
            self._next_id = max(self._next_id, int(new_ids[-1]) + 1)
            self._c_added.inc(k)
            self._bump()
            if notify:
                self._notify("add", new_ids,
                             np.arange(self._size - k, self._size,
                                       dtype=np.int64))
            return new_ids

    def remove(self, ids, *, notify: bool = True) -> int:
        """Tombstone rows by id (device buffers untouched).  Raises KeyError
        on unknown or already-removed ids.  Returns the number removed.

        notify=False is the QUIET tombstone the migration uses when a row
        leaves this store because it moved to the new-spec store: membership
        is unchanged globally, so per-id sidecars must not see a "remove" —
        but version/removed_count still bump so layouts resync."""
        ids = np.atleast_1d(np.asarray(ids, np.int64))
        with obs.span("store.remove", rows=len(ids)):
            return self._remove(ids, notify)

    def _remove(self, ids: np.ndarray, notify: bool) -> int:
        if len(np.unique(ids)) != len(ids):
            raise ValueError("duplicate ids in remove batch")
        slots = np.searchsorted(self._ids[: self._size], ids)
        for id_, slot in zip(ids.tolist(), slots.tolist()):
            if (slot >= self._size or self._ids[slot] != id_
                    or not self._alive[slot]):
                raise KeyError(f"id {id_} not in store")
        self._alive[slots] = False
        self._n_alive -= len(ids)
        self._n_removed_total += len(ids)
        self._c_removed.inc(len(ids))
        self._bump()
        if notify:
            self._notify("remove", ids, slots.astype(np.int64))
        return len(ids)

    def compact(self) -> None:
        """Drop tombstoned slots, preserving insertion order, and shrink the
        buffers to the smallest power-of-two capacity that fits."""
        with obs.span("store.compact", size=self._size,
                      n_alive=self._n_alive):
            self._compact()

    def _compact(self) -> None:
        faultinject.crash_point(_CP_COMPACT)
        self._c_compactions.inc()
        slots = self.alive_slots()
        n = len(slots)
        cap = pow2_bucket(n)
        self._sk_buf = self._place(packing.padded_take(self._sk_buf, slots))
        self._wt_buf = self._place(packing.padded_take(self._wt_buf, slots))
        ids = np.zeros(cap, np.int64)
        ids[:n] = self._ids[slots]
        weights = np.zeros(cap, np.int64)
        weights[:n] = self._weights[slots]
        alive = np.zeros(cap, bool)
        alive[:n] = True
        self._ids, self._weights, self._alive = ids, weights, alive
        self._size = n
        self._n_alive = n
        self._epoch += 1  # slots renumbered: layouts must rebuild, not sync
        self._bump()
        self._notify("compact", np.zeros(0, np.int64), np.zeros(0, np.int64))

    # -- merge (the Mergeable contract, repro.index.mergeable) --------------

    def merge(self, other: "SketchStore") -> "SketchStore":
        """Absorb `other`'s slots (alive AND tombstoned) into this store
        and return self — the device-level half of the Mergeable contract
        (DESIGN.md section 14).  Inputs must share a spec and cover
        disjoint external ids; validation runs BEFORE any mutation, so a
        refused (or faultinject-killed — the ``merge.combine`` crash
        point) merge leaves both stores intact and re-runnable.  `other`
        is never mutated but must be discarded after success: its ids are
        absorbed, and a re-merge raises the disjointness check.

        Two paths, both preserving slot order == id order:

          * append (other's smallest id above self's largest — every
            merge-tree combine, where workers build disjoint ascending id
            ranges): other's used slots ride the SAME jitted
            `_append_rows` graph as `add` — one device concat, no
            recompile, and NO epoch bump, so an existing PartitionSet
            absorbs the merged rows as ordinary shard-routed delta slots.
          * interleave (id ranges overlap without colliding): the merged
            order is the sorted-id merge of the two slot sequences, built
            via one concatenated gather; slot identity changes, so the
            epoch bumps and layouts rebuild (same contract as compact).

        Tombstones reconcile by import: other's dead slots stay dead here
        and `removed_count` advances by their number, so layout syncs see
        the mask work.  Row counters are NOT incremented (merge the obs
        registries to carry other's counts, as `QueryEngine.merge` does);
        `store_merges_total` counts the combines themselves."""
        if other is self:
            raise MergeIncompatible(
                "SketchStore.merge: cannot merge a store with itself")
        if self.spec is not None or other.spec is not None:
            check_spec_compatible(other.spec, self.spec,
                                  what="SketchStore.merge")
        if other.d != self.d:
            raise MergeIncompatible(
                f"SketchStore.merge: sketch dim mismatch "
                f"(d={self.d} vs d={other.d})")
        if other._size == 0:
            # empty input: validated no-op (no version bump — nothing a
            # layout or cache could observe has changed)
            self._next_id = max(self._next_id, other._next_id)
            return self
        check_id_disjoint(self._ids[: self._size], other._ids[: other._size],
                          what="SketchStore.merge")
        with obs.span("store.merge", rows=other._size,
                      alive=len(other)):
            self._merge(other)
        return self

    def _merge(self, other: "SketchStore") -> None:
        faultinject.crash_point(_CP_MERGE)
        size_a, size_b = self._size, other._size
        o_ids = other._ids[:size_b]
        o_alive = other._alive[:size_b]
        alive_ids = o_ids[o_alive]
        if size_a == 0 or o_ids[0] > self._ids[size_a - 1]:
            # append path: other's slots become this store's tail, through
            # the same compiled append graph as `add`
            kpad = pow2_bucket(size_b)
            if size_a + kpad > self.capacity:
                self._grow_to(pow2_bucket(size_a + kpad))
            self._sk_buf, self._wt_buf = _append_rows()(
                self._sk_buf, self._wt_buf, other._sk_buf[:kpad],
                jnp.int32(size_a))
            if self._placement is not None:
                self._sk_buf = self._place(self._sk_buf)
                self._wt_buf = self._place(self._wt_buf)
            sl = slice(size_a, size_a + size_b)
            self._ids[sl] = o_ids
            self._alive[sl] = o_alive
            self._weights[sl] = other._weights[:size_b]
            self._size += size_b
            merged_slots = np.arange(size_a, size_a + size_b,
                                     dtype=np.int64)[o_alive]
        else:
            # interleave path: merged slot order is the sorted-id merge of
            # two already-sorted sequences; one gather from the
            # concatenated buffers rebuilds the tail-to-tail layout
            ids_cat = np.concatenate([self._ids[:size_a], o_ids])
            order = np.argsort(ids_cat, kind="stable")
            take = np.where(order < size_a, order,
                            order - size_a + self.capacity)
            n = size_a + size_b
            cap = pow2_bucket(n)
            sk = packing.padded_take(
                jnp.concatenate([self._sk_buf, other._sk_buf], axis=0),
                take)
            wt = packing.padded_take(
                jnp.concatenate([self._wt_buf, other._wt_buf]), take)
            ids = np.zeros(cap, np.int64)
            ids[:n] = ids_cat[order]
            alive_cat = np.concatenate([self._alive[:size_a], o_alive])
            alive = np.zeros(cap, bool)
            alive[:n] = alive_cat[order]
            w_cat = np.concatenate([self._weights[:size_a],
                                    other._weights[:size_b]])
            weights = np.zeros(cap, np.int64)
            weights[:n] = w_cat[order]
            self._sk_buf = self._place(sk)
            self._wt_buf = self._place(wt)
            self._ids, self._alive, self._weights = ids, alive, weights
            self._size = n
            self._epoch += 1  # slots renumbered: layouts rebuild, not sync
            merged_slots = np.flatnonzero(
                (order >= size_a) & alive_cat[order]).astype(np.int64)
        self._n_alive += len(alive_ids)
        # imported tombstones: dead on arrival here, but they advance the
        # monotone removed counter so layout syncs refresh alive masks
        self._n_removed_total += size_b - len(alive_ids)
        self._next_id = max(self._next_id, other._next_id)
        self._c_merges.inc()
        self._bump()
        self._notify("merge", alive_ids.copy(), merged_slots)

    # -- query-side views ---------------------------------------------------

    def gather_alive(self) -> AliveView:
        """(matrix, n_alive, ids): alive rows gathered in id order into a
        power-of-two padded device matrix.  Rows past n_alive are padding —
        callers mask them via the engines' traced valid counts.

        The result is valid ONLY until the next mutation: the append-only
        fast path returns the live buffer itself, which the next `add`
        DONATES on accelerator backends (the stale matrix then raises
        "Array has been deleted").  Finish (or copy) before mutating —
        every in-repo consumer uses it within a single query call.  The
        returned view is stamped with the store version; pass it to
        `check_fresh` before use if a mutation could have intervened."""
        if self._gather_cache is not None:
            return self._gather_cache
        if self._n_alive == self._size:
            # append-only fast path: no tombstones, so the buffer ITSELF is
            # the id-ordered pow2-padded matrix — no O(N) device gather.
            # Rows past size hold stale append padding, but every consumer
            # masks by the traced valid count, same as the gathered path.
            self._gather_cache = AliveView(
                self._sk_buf, self._size, self._ids[: self._size],
                self.version)
            return self._gather_cache
        slots = self.alive_slots()
        mat = packing.padded_take(self._sk_buf, slots)
        self._gather_cache = AliveView(mat, len(slots), self._ids[slots],
                                       self.version)
        return self._gather_cache

    def check_fresh(self, view: AliveView) -> None:
        """Raise if `view` predates the store's current version — the cheap
        consumer-side guard against the stale-view footgun above.  Views
        without a stamp (plain tuples) are rejected too."""
        version = getattr(view, "version", None)
        if version != self.version:
            raise RuntimeError(
                "stale gather: this view was taken at store version "
                f"{version}, but the store is now at {self.version} — the "
                "matrix may reference a donated buffer.  Re-call "
                "gather_alive() after any add/remove/compact.")

    # -- placement (opt-in sharding) ---------------------------------------

    def place(self, sharding_for_shape) -> None:
        """Install a shape -> jax.sharding.Sharding callback and re-place
        the buffers under it (repro.distributed: rows across the data
        axes).  Subsequent grows/appends/compactions keep the placement."""
        self._placement = sharding_for_shape
        self._sk_buf = self._place(self._sk_buf)
        self._wt_buf = self._place(self._wt_buf)
        self._bump()

    # -- snapshot / restore -------------------------------------------------

    def state_tree(self) -> dict[str, np.ndarray]:
        """Flat tree for checkpoint.Checkpointer: exactly the live slots
        (tombstones included — restore reproduces the store bit-for-bit,
        including pending-compaction state)."""
        return {
            "sk": np.asarray(self._sk_buf[: self._size]),
            "ids": self._ids[: self._size].copy(),
            "alive": self._alive[: self._size].copy(),
            "weights": self._weights[: self._size].copy(),
        }

    def state_meta(self) -> dict:
        return {"d": self.d, "size": self._size, "next_id": self._next_id}

    @classmethod
    def from_state(cls, tree: dict[str, np.ndarray], meta: dict,
                   spec: SketchSpec | None = None) -> "SketchStore":
        store = cls(int(meta["d"]), spec=spec)
        size = int(meta["size"])
        cap = pow2_bucket(size)
        sk = np.zeros((cap, store.w), np.int32)
        sk[:size] = tree["sk"]
        store._sk_buf = jnp.asarray(sk)
        wt = np.zeros(cap, np.int32)
        wt[:size] = tree["weights"]
        store._wt_buf = jnp.asarray(wt)
        store._ids = np.zeros(cap, np.int64)
        store._ids[:size] = tree["ids"]
        store._alive = np.zeros(cap, bool)
        store._alive[:size] = tree["alive"]
        store._weights = np.zeros(cap, np.int64)
        store._weights[:size] = tree["weights"]
        store._size = size
        store._n_alive = int(store._alive.sum())
        store._next_id = int(meta["next_id"])
        store._bump()
        return store
