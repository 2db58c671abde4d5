"""QueryEngine: batched online similarity serving over a SketchStore.

The public boundary of the index subsystem.  Raw categorical rows — dense
(k, n) matrices or padded-COO (indices, values) pairs — go in; external ids
and distances come out.  Sketching happens inside (`core.cabin.sketch_dense`
/ `sketch_sparse`, which auto-dispatch to the fused Pallas kernels on TPU),
so callers never handle packed words, seeds, or layouts.

Serving disciplines (DESIGN.md section 8.3):

  * Micro-batch shape bucketing.  Every ingest and query batch is padded to
    a power-of-two row count (and nnz width for COO) before touching a jit
    boundary; together with the store's traced valid-row counts this keeps
    the number of compiled graphs O(log N + log Q) across arbitrary
    request mixes.  Padding rows are all-zero categorical vectors, whose
    sketches are all-zero and which every reduction masks out — they can
    never contaminate a result.
  * Partitioned serving.  Queries serve through a PartitionSet
    (repro.index.partition, DESIGN.md 8.5/13): per shard, a big
    weight-sorted base partition that SURVIVES mutations, a small
    brute-delta partition of fresh adds, and per-partition alive masks for
    removes.  `_layout()` syncs the set across the version RANGE since it
    was built — a mutation costs the next query O(delta), not the
    O(N log N) rebuild the old version-equality invalidation paid.  All of
    that discipline lives in partition.py; the engine only sketches,
    routes, and caches.
  * Bit-identity.  `topk` serves through each base partition's progressive
    band expansion (allpairs.topk_rows_banded — nearest bands first, stop
    at the exactness certificate, seeded with the cross-partition running
    k-th bound) merged with the deltas by (value, id), and `radius`
    through threshold_pairs per partition; both are bit-identical to
    running the batch engine on a freshly built matrix of the same vectors
    — across any interleaving of add/remove/compact, at every shard count,
    after checkpoint restore, and under both metrics.  Ties in topk
    resolve to the lower id, matching topk_rows' stable merge.
  * LRU result cache.  Results are memoised on (op, args, store version,
    query-sketch bytes); any mutation bumps the version, so stale hits are
    impossible by construction.

Persistence snapshots flow through checkpoint.Checkpointer (flat-tree save
of the store buffers + hash seeds + metadata), and `shard` opt-in re-homes
the serving layout as one PartitionSet per mesh device — rows routed by
``id % n_shards``, per-shard matrices placed per device, answers merged
cross-shard (see `shard`).
"""

from __future__ import annotations

from collections import OrderedDict, deque

import numpy as np
import jax.numpy as jnp

from repro import obs
from repro.core import allpairs, packing, theory
from repro.core.cabin import (CabinParams, sketch_dense_jit,
                              sketch_sparse_jit)
from repro.core.packing import pad_rows_pow2, pow2_bucket
from repro.index import partition
from repro.index.bands import BandedLayout
from repro.index.mergeable import MergeIncompatible, check_spec_compatible
from repro.index.migrate import Migration, RawArchive
from repro.index.partition import PartitionSet
from repro.index.store import SketchSpec, SketchStore

_METRICS = ("cham", "hamming")


def compile_cache_entries() -> int:
    """Total jit-cache entries across the serving stack's compiled
    reductions — the O(log N) graph-count discipline as a LIVE number.
    The engine exports it as a gauge, and tests/test_obs.py pins that the
    REPRO_OBS=0 path adds zero entries to it."""
    from repro.core import cabin as _cabin
    from repro.index import store as _store_mod

    total = 0
    for fn in (allpairs._threshold_pairs_impl, allpairs._banded_pairs_impl,
               allpairs._argmin_rows_impl, allpairs._topk_rows_impl,
               allpairs._rowsum_impl, _cabin.sketch_dense_jit,
               _cabin.sketch_sparse_jit, _store_mod._append_rows()):
        size = getattr(fn, "_cache_size", None)
        if callable(size):
            total += size()
    return total


class QueryEngine:
    """Online k-NN / radius serving over Cabin sketches.

    Parameters
    ----------
    params : CabinParams — hash seeds + dims; all ingested and queried rows
        must share them (they define the sketch space).
    metric : "cham" (estimated categorical HD) or "hamming" (exact sketch
        HD) — fixed per engine so cached results and layouts stay coherent.
    block / mode : tile size and backend forwarded to core.allpairs.
    band_rows : rows per weight band (radius-query pruning granularity).
    cache_entries : LRU result-cache capacity (0 disables caching).
    merge_ratio : tiered-layout merge policy (DESIGN.md 8.5).  Fresh adds
        accumulate in a small unsorted delta tier and fold into the sorted
        base tier once the live delta exceeds `merge_ratio * base_alive`
        rows; until then a mutation costs the next query O(delta) instead
        of a full O(N log N) layout rebuild.  0 merges on every mutation
        (the pre-tiered rebuild-per-version behaviour — the bench baseline);
        None never auto-merges (fold only on `compact()`).
    keep_raw : archive each ingested row's raw COO form (host-side,
        index/migrate.RawArchive) so the index can be re-sketched under a
        new spec.  Default True — without it `migrate()` is impossible and
        the index is frozen at its birth spec.
    auto_migrate : start a lazy spec migration automatically when the
        observed row-density percentile (`drift_pct` over the last
        `drift_window` ingested rows) crosses the density bound
        `theory.max_density_for_dim(d, drift_delta)` for the current sketch
        dim — the Theorem 1/2 accuracy cliff.  The new dim is
        `theory.sketch_dim(percentile, drift_delta)` rounded up to a
        multiple of 128, same hash seeds.
    """

    def __init__(self, params: CabinParams, *, metric: str = "cham",
                 block: int = 2048, mode: str | None = None,
                 band_rows: int = 1024, cache_entries: int = 256,
                 merge_ratio: float | None = 0.125, keep_raw: bool = True,
                 auto_migrate: bool = False, drift_delta: float = 0.1,
                 drift_window: int = 512, drift_pct: float = 95.0,
                 registry=None):
        if metric not in _METRICS:
            raise ValueError(f"metric must be one of {_METRICS}")
        if auto_migrate and not keep_raw:
            raise ValueError("auto_migrate needs keep_raw=True: a drift "
                             "migration re-sketches from the raw archive")
        self.params = params
        self.metric = metric
        self.block = block
        self.mode = mode
        self.band_rows = band_rows
        self.merge_ratio = merge_ratio
        self.spec = SketchSpec(0, params)
        self.raw: RawArchive | None = RawArchive() if keep_raw else None
        self.auto_migrate = auto_migrate
        self.drift_delta = float(drift_delta)
        self.drift_pct = float(drift_pct)
        self.drift_window = int(drift_window)
        self._nnz_window: deque[int] = deque(maxlen=self.drift_window)
        self._mig: Migration | None = None
        self._subs: list = []
        self.store = SketchStore(params.sketch_dim, spec=self.spec)
        self._attach_relay(self.store)
        self._n_shards = 1
        self._devices: list | None = None
        self._tiered: PartitionSet | None = None
        self._cache: OrderedDict[tuple, tuple] = OrderedDict()
        self._cache_entries = cache_entries
        self.cache_hits = 0
        self.cache_misses = 0
        # per-engine flight recorder (repro.obs): NULL_REGISTRY under
        # REPRO_OBS=0, so every instrument below is a shared no-op.  Hot
        # paths cache their instruments HERE, once — queries never pay a
        # registry lookup.
        self.obs = obs.new_registry() if registry is None else registry
        self.store.set_registry(self.obs)
        self._h_lat = {
            op: self.obs.histogram("engine_query_latency_ms", op=op)
            for op in ("topk", "radius", "pairwise")}
        self._c_hits = self.obs.counter("engine_cache_hits_total")
        self._c_misses = self.obs.counter("engine_cache_misses_total")
        self._register_obs_gauges()

    def _register_obs_gauges(self) -> None:
        """Structural state as read-time callbacks: tier depths, cache
        sizes, compile-graph count, density drift, migration progress —
        always live, never a stale sample."""
        reg = self.obs
        reg.gauge_fn("engine_rows_alive", lambda: float(len(self)))
        reg.gauge_fn("engine_store_size",
                     lambda: float(self.store.size))
        reg.gauge_fn("engine_store_capacity",
                     lambda: float(self.store.capacity))
        reg.gauge_fn("engine_lru_entries",
                     lambda: float(len(self._cache)))
        reg.gauge_fn("engine_tier_base_rows",
                     lambda: float(self._tiered.base_alive
                                   if self._tiered else 0))
        reg.gauge_fn("engine_tier_delta_rows",
                     lambda: float(self._tiered.delta_n
                                   if self._tiered else 0))
        reg.gauge_fn("engine_tier_merges",
                     lambda: float(self._tiered.n_merges
                                   if self._tiered else 0))
        reg.gauge_fn("engine_shards",
                     lambda: float(self._n_shards))
        reg.gauge_fn("engine_compile_cache_entries",
                     lambda: float(compile_cache_entries()))
        reg.gauge_fn("engine_sketch_dim", lambda: float(self.d))
        reg.gauge_fn("engine_observed_density_pct", self._observed_density)
        reg.gauge_fn("engine_density_dim_needed", self._density_dim_needed)
        reg.gauge_fn("engine_migration_progress", self._migration_progress)
        reg.gauge_fn("engine_migration_cursor",
                     lambda: float(self._mig.cursor) if self._mig else -1.0)

    def _observed_density(self) -> float:
        """The `drift_pct` percentile of per-row nnz over the drift window
        — the live half of the density-drift gauge pair (the other half is
        `engine_density_dim_needed`; when it exceeds `engine_sketch_dim`
        the Theorem 1/2 accuracy bound no longer covers the data)."""
        if not self._nnz_window:
            return 0.0
        return float(np.percentile(
            np.fromiter(self._nnz_window, np.int64), self.drift_pct))

    def _density_dim_needed(self) -> float:
        if not self._nnz_window:
            return 0.0
        p = max(1, int(np.ceil(self._observed_density())))
        return float(theory.sketch_dim(p, self.drift_delta))

    def _migration_progress(self) -> float:
        """Fraction of old-spec rows re-sketched: 1.0 when no migration is
        in flight (the steady state IS fully migrated), monotone 0 -> 1
        across batches, and exact at every crash/resume point (the
        faultinject matrix in tests/test_obs.py pins this)."""
        if self._mig is None:
            return 1.0
        done = self._mig.rows_migrated
        total = done + len(self._mig.src)
        return done / total if total else 1.0

    # -- mutation observers (engine level) ----------------------------------

    def subscribe(self, callback) -> None:
        """Register `callback(event, ids, slots, store)` — the engine-level
        twin of `SketchStore.subscribe` that per-id sidecars (ClusterIndex)
        should use instead of subscribing to `engine.store` directly: a
        spec migration swaps stores under the engine, and only the engine
        knows which store an event belongs to.  Store events ("add",
        "remove", "compact") relay with the ORIGINATING store; the engine
        adds two of its own: "migrate_start" (a migration just began;
        `store` is the new-spec destination — re-sketch any private packed
        state from raw now) and "migrate" (the migration published;
        `store` is the engine's new serving store)."""
        self._subs.append(callback)

    def unsubscribe(self, callback) -> None:
        self._subs.remove(callback)

    def _attach_relay(self, store: SketchStore) -> None:
        def relay(event, ids, slots, _store=store):
            for cb in list(self._subs):
                cb(event, ids, slots, _store)

        store.subscribe(relay)

    def _emit(self, event: str, store: SketchStore) -> None:
        z = np.zeros(0, np.int64)
        for cb in list(self._subs):
            cb(event, z, z, store)

    # -- basics -------------------------------------------------------------

    def __len__(self) -> int:
        n = len(self.store)
        if self._mig is not None:
            n += len(self._mig.dst) + len(self._mig.fresh)
        return n

    @property
    def d(self) -> int:
        return self.params.sketch_dim

    def ids(self) -> np.ndarray:
        if self._mig is None:
            return self.store.ids()
        return np.sort(np.concatenate([
            self.store.ids(), self._mig.dst.ids(), self._mig.fresh.ids()]))

    def stats(self) -> dict:
        t = self._tiered
        out = {
            "n_alive": len(self),
            "size": self.store.size,
            "capacity": self.store.capacity,
            "version": self.store.version,
            "spec_version": self.spec.version,
            "cache_hits": self.cache_hits,
            "cache_misses": self.cache_misses,
            "n_bands": t.n_bands if t else None,
            "base_rows": t.base_rows if t else None,
            "base_alive": t.base_alive if t else None,
            "delta_rows": t.delta_n if t else None,
            "tier_merges": t.n_merges if t else None,
            "n_shards": self._n_shards,
        }
        if self._mig is not None:
            m = self._mig
            out["migration"] = {
                "phase": m.phase,
                "to_version": m.new_spec.version,
                "to_dim": m.new_spec.d,
                "rows_migrated": m.rows_migrated,
                "rows_remaining": len(m.src),
                "fresh_rows": len(m.fresh),
                "progress": self._migration_progress(),
            }
        lat = {}
        for op, h in self._h_lat.items():
            if h.count:
                lat[op] = {"count": h.count, "p50": h.quantile(50),
                           "p95": h.quantile(95), "p99": h.quantile(99)}
        if lat:
            out["latency_ms"] = lat
        return out

    def render_prom(self) -> str:
        """This engine's registry in Prometheus text exposition format —
        point a scraper (or `curl`) at whatever endpoint serves it."""
        return self.obs.render_prom()

    def obs_snapshot(self) -> dict:
        """Plain-dict snapshot of this engine's registry: every counter,
        gauge (evaluated live), and histogram with p50/p95/p99."""
        return self.obs.snapshot()

    # -- sketching (shape-bucketed) ----------------------------------------

    def _sketch(self, queries, params: CabinParams | None = None
                ) -> tuple[jnp.ndarray, int]:
        """Raw categorical input -> (packed sketches (pow2-padded, w), k).

        `queries` is a dense (k, n_dims) int array, or an (indices, values)
        padded-COO pair.  Both layouts are padded to power-of-two buckets
        (rows, and nnz width for COO) so the sketch jits are reused across
        request sizes; zero padding is inert under psi/pi by construction.
        `params` overrides the engine's CabinParams — the cross-version
        serving and migration paths sketch the same rows under another
        spec's params through exactly this path, which is what makes a
        completed migration bit-identical to a fresh build.
        """
        with obs.span("engine.sketch"):
            if params is None:
                params = self.params
            w = params.packed_width
            if isinstance(queries, (tuple, list)):
                idx_host, val_host = queries
                # validate on host BEFORE the device transfer: no sync on the
                # serving path when (as usual) the input is already numpy
                idx_host = np.asarray(idx_host)
                if idx_host.shape != np.shape(val_host) or idx_host.ndim != 2:
                    raise ValueError("COO input needs matching (k, m) "
                                     "indices/values")
                if idx_host.size and (idx_host.max() >= params.n_dims
                                      or idx_host.min() < 0):
                    raise ValueError(
                        f"COO indices out of range [0, {params.n_dims})")
                indices = jnp.asarray(idx_host, jnp.int32)
                values = jnp.asarray(val_host, jnp.int32)
                k = indices.shape[0]
                if k == 0:
                    return jnp.zeros((0, w), jnp.int32), 0
                mpad = pow2_bucket(indices.shape[1])
                wpad = ((0, pow2_bucket(k) - k), (0, mpad - indices.shape[1]))
                sk = sketch_sparse_jit(params, jnp.pad(indices, wpad),
                                       jnp.pad(values, wpad))
                return sk, k
            x = jnp.asarray(queries, jnp.int32)
            if x.ndim != 2 or x.shape[1] != params.n_dims:
                raise ValueError(
                    f"expected dense (k, {params.n_dims}) rows, "
                    f"got {x.shape}")
            k = x.shape[0]
            if k == 0:
                return jnp.zeros((0, w), jnp.int32), 0
            return sketch_dense_jit(params, pad_rows_pow2(x)), k

    # -- ingestion ----------------------------------------------------------

    def _ingest_target(self) -> tuple[SketchStore, CabinParams]:
        """Where adds land and which spec sketches them: the serving store
        normally, the new-spec fresh store while a migration is in flight —
        acked mutations during migration must never need re-migration."""
        if self._mig is not None:
            return self._mig.fresh, self._mig.new_spec.params
        return self.store, self.params

    def add_dense(self, x) -> np.ndarray:
        """Ingest dense categorical rows (k, n_dims); returns ids (k,)."""
        self._drive()
        store, params = self._ingest_target()
        sk, k = self._sketch(x, params=params)
        ids = store.add(sk, n_valid=k)
        if k:
            x_host = np.asarray(x)
            if self.raw is not None:
                self.raw.put_dense(ids, x_host)
            self._track_drift(np.count_nonzero(x_host, axis=1))
        return ids

    def add_sparse(self, indices, values) -> np.ndarray:
        """Ingest padded-COO categorical rows; returns ids (k,)."""
        with obs.span("engine.add_sparse", rows=len(indices)):
            self._drive()
            store, params = self._ingest_target()
            sk, k = self._sketch((indices, values), params=params)
            ids = store.add(sk, n_valid=k)
            if k:
                if self.raw is not None:
                    self.raw.put(ids, indices, values)
                self._track_drift(
                    np.count_nonzero(np.asarray(values), axis=1))
            return ids

    def add_packed(self, packed, raw=None,
                   spec: SketchSpec | None = None) -> np.ndarray:
        """Ingest pre-sketched packed rows (k, w).  The rows MUST come from
        this engine's CURRENT CabinParams — used by streaming ingest after
        an in-window dedup pass already paid for the sketches.  `spec`
        (optional) names the SketchSpec the rows were sketched under; a
        mismatch raises MergeIncompatible naming both specs, which is the
        only way to catch wrong hash seeds — they are undetectable from
        the bits alone.  `raw` is the rows' (indices, values) COO pair;
        pass it to keep the rows re-sketchable (without it they cannot
        survive a `migrate()`).  While a migration is in flight the packed
        rows are spec-ambiguous: with `raw` the engine re-sketches them
        under the live spec, without it the call raises."""
        self._drive()
        if self._mig is not None:
            if raw is None:
                raise RuntimeError(
                    "add_packed mid-migration needs raw=(indices, values): "
                    "the supplied sketches are under the OLD spec, but new "
                    "rows must land in the new-spec tier")
            return self.add_sparse(*raw)
        packed = jnp.asarray(packed)
        ids = self.store.add_packed(pad_rows_pow2(packed), spec,
                                    n_valid=packed.shape[0])
        if raw is not None and self.raw is not None and len(ids):
            self.raw.put(ids, *raw)
        return ids

    def remove(self, ids) -> int:
        ids = np.atleast_1d(np.asarray(ids, np.int64))
        with obs.span("engine.remove", rows=len(ids)):
            self._drive()
            if self._mig is None:
                n = self.store.remove(ids)
            else:
                if len(np.unique(ids)) != len(ids):
                    raise ValueError("duplicate ids in remove batch")
                # validate membership BEFORE mutating any store, so a bad
                # id cannot leave a partial cross-store remove behind
                groups: dict[int, tuple[SketchStore, list[int]]] = {}
                for id_ in ids.tolist():
                    store = self._mig.store_of(id_)  # KeyError on unknown
                    groups.setdefault(id(store), (store, []))[1].append(id_)
                for store, grp in groups.values():
                    store.remove(np.asarray(grp, np.int64))
                n = len(ids)
            if self.raw is not None:
                self.raw.drop(ids)
            return n

    def compact(self) -> None:
        self._drive()
        self.store.compact()
        if self._mig is not None:
            self._mig.dst.compact()
            self._mig.fresh.compact()

    # -- merge (the Mergeable contract, repro.index.mergeable) --------------

    def merge(self, other: "QueryEngine") -> "QueryEngine":
        """Absorb `other`'s membership into this engine and return self —
        the engine face of the Mergeable contract (DESIGN.md section 14)
        and the combine step of `index.merge_tree.bulk_ingest`.

        Requirements, all validated before anything mutates: same metric,
        same sketch spec (cross-spec merge fails loudly through the same
        compatibility check the spec-migration machinery uses — migrate
        one engine to the other's spec first), matching keep_raw, disjoint
        external ids, and NO migration in flight on either side (a
        mid-migration membership spans two sketch spaces).

        What merges: the store (device buffers, through `SketchStore.merge`
        — the ``merge.combine`` crash point fires there, before any
        mutation), the raw archive, the density-drift window, the serving
        layout (merged rows absorbed as shard-routed delta when the id
        ranges don't interleave), and the obs registries (counters sum,
        histograms union — `MetricsRegistry.merge`).  The LRU clears: its
        keys version a membership that just changed.  Store subscribers
        see ONE "merge" event carrying the absorbed alive rows.  `other`
        is left readable but must be discarded — its ids are absorbed, so
        a re-merge raises the disjointness check."""
        if other is self:
            raise MergeIncompatible(
                "QueryEngine.merge: cannot merge an engine with itself")
        if self._mig is not None or other._mig is not None:
            raise RuntimeError(
                "QueryEngine.merge: a spec migration is in flight; drive "
                "it to completion (migrate_all()) on both engines before "
                "merging — a mid-migration membership spans two sketch "
                "spaces")
        if other.metric != self.metric:
            raise MergeIncompatible(
                f"QueryEngine.merge: metric mismatch ({self.metric!r} vs "
                f"{other.metric!r}) — cached results and layouts would "
                "not be comparable")
        check_spec_compatible(other.spec, self.spec,
                              what="QueryEngine.merge")
        if (self.raw is None) != (other.raw is None):
            raise MergeIncompatible(
                "QueryEngine.merge: keep_raw mismatch — merging a raw-less "
                "engine would leave part of the membership un-migratable")
        with obs.span("engine.merge", rows=len(other)):
            self.store.merge(other.store)
            if self.raw is not None:
                self.raw.merge(other.raw)
            self._nnz_window.extend(other._nnz_window)
            self.cache_hits += other.cache_hits
            self.cache_misses += other.cache_misses
            # counters sum, histograms union; callback gauges freeze to
            # their merge-time values — re-register ours so the live
            # structural windows stay live
            self.obs.merge(other.obs)
            self._register_obs_gauges()
            if self._tiered is not None:
                self._tiered.merge(other._tiered)
            self._cache.clear()
        return self

    # -- spec migration ------------------------------------------------------

    @property
    def migrating(self) -> bool:
        return self._mig is not None

    @property
    def migration(self) -> Migration | None:
        return self._mig

    def migrate(self, new_params: CabinParams | None = None, *,
                d: int | None = None, batch_rows: int = 1024,
                drive: str = "lazy", journal_dir: str | None = None,
                journal_every: int = 1, journal_keep: int = 3) -> Migration:
        """Begin an incremental re-sketch of the index to a new spec.

        `new_params` is the target CabinParams (same n_dims; typically a new
        sketch_dim after density drift), or pass `d` to keep the current
        hash seeds and change only the dim.  Old-spec rows are re-sketched
        from the raw archive in `batch_rows` batches; serving stays live
        throughout, answering across the old- and new-spec tiers.  `drive`:

          * "lazy"  — each engine call (add/remove/query/compact) advances
            the migration one batch before doing its own work; no separate
            driver needed, progress rides the request stream.
          * "manual" — only `migration_step()` / `migrate_all()` advance it.
          * "eager" — run to completion before returning.

        `journal_dir` checkpoints the full engine (both tiers + cursor)
        through checkpoint.Checkpointer every `journal_every` batches —
        `QueryEngine.restore(journal_dir)` after a crash resumes the
        migration without losing any acked mutation.  A completed migration
        is bit-identical to an engine freshly built at the new spec."""
        if self._mig is not None:
            raise RuntimeError("a migration is already in flight")
        if new_params is None:
            if d is None:
                raise ValueError("migrate() needs new_params or d")
            new_params = CabinParams(
                n_dims=self.params.n_dims, sketch_dim=int(d),
                psi_seed=self.params.psi_seed, pi_seed=self.params.pi_seed)
        new_spec = self.spec.successor(new_params)
        mig = Migration(self, new_spec, batch_rows=batch_rows, drive=drive,
                        journal_dir=journal_dir, journal_every=journal_every,
                        journal_keep=journal_keep)
        self._mig = mig
        # fresh holds REAL ingest (acked adds mid-migration) — it shares
        # the engine's counters; dst holds re-sketched copies of existing
        # rows, counted separately by the migration's own instruments so
        # store_rows_added_total keeps meaning "rows ingested".
        mig.fresh.set_registry(self.obs)
        self._attach_relay(mig.dst)
        self._attach_relay(mig.fresh)
        self._emit("migrate_start", mig.dst)
        if drive == "eager":
            mig.run()
        return mig

    def migration_step(self, rows: int | None = None) -> bool:
        """Advance an in-flight migration by one batch (default
        `batch_rows`); returns True while more work remains."""
        if self._mig is None:
            return False
        self._mig.step(rows)
        return self._mig is not None

    def migrate_all(self) -> None:
        """Drive an in-flight migration to completion."""
        while self.migration_step():
            pass

    def _drive(self) -> None:
        """Lazy-mode pacing: one migration batch per engine call."""
        if self._mig is not None and self._mig.drive == "lazy":
            self._mig.step()

    def _publish_migration(self, mig: Migration) -> None:
        """Called by Migration._finish once every row is under the new
        spec: atomically (w.r.t. the Python API) swap the serving store."""
        self.store = mig.dst
        self.store.set_registry(self.obs)
        self.params = mig.new_spec.params
        self.spec = mig.new_spec
        self._tiered = None
        self._cache.clear()
        self._mig = None
        self._emit("migrate", self.store)

    def _track_drift(self, nnz_counts: np.ndarray) -> None:
        """Feed per-row density observations into the drift window; when
        the `drift_pct` percentile needs a bigger sketch dim than we have
        (theory.sketch_dim at `drift_delta`), auto-start a lazy migration
        to that dim rounded up to a multiple of 128, the width the Cabin
        kernels take (the theory's d is a minimum, so rounding up keeps
        its bound).  No-op unless auto_migrate."""
        with obs.span("engine.track_drift", rows=len(nnz_counts)):
            self._nnz_window.extend(int(c) for c in nnz_counts)
            if not self.auto_migrate or self._mig is not None:
                return
            if len(self._nnz_window) < min(64, self.drift_window):
                return  # too few observations to call a drift
            p = max(1, int(np.ceil(np.percentile(
                np.fromiter(self._nnz_window, np.int64), self.drift_pct))))
            need = theory.sketch_dim(p, self.drift_delta)
        if need > self.d:
            self.migrate(d=-(-need // 128) * 128, drive="lazy")

    # -- result cache -------------------------------------------------------

    def _cached(self, key):
        if key is not None and key in self._cache:
            self._cache.move_to_end(key)
            self.cache_hits += 1
            self._c_hits.inc()
            return self._cache[key]
        return None

    def _remember(self, key, value) -> None:
        """Store a PRIVATE copy of `value` (key=None: caching disabled) —
        both hit and miss paths hand callers arrays they may freely
        mutate without corrupting later hits."""
        self.cache_misses += 1
        self._c_misses.inc()
        if key is None:
            return
        if isinstance(value, tuple):
            self._cache[key] = tuple(a.copy() for a in value)
        else:
            self._cache[key] = [a.copy() for a in value]
        if len(self._cache) > self._cache_entries:
            self._cache.popitem(last=False)

    # -- queries ------------------------------------------------------------

    def topk(self, queries, k: int) -> tuple[np.ndarray, np.ndarray]:
        """k nearest stored rows per query: (ids (Q, k'), dists (Q, k')),
        ascending by distance, k' = min(k, len(store)).  Accepts dense rows
        or an (indices, values) COO pair; `topk_packed` skips sketching.
        Raises ValueError for k < 0 (k = 0 is a valid empty query)."""
        if k < 0:
            raise ValueError(f"topk: k must be >= 0, got {k}")
        self._drive()  # migration pacing stays OUTSIDE the query timer
        with self._h_lat["topk"].time(), obs.span("engine.topk", k=k):
            if self._mig is not None:
                return self._topk_migrating(queries, k)
            sk, q = self._sketch(queries)
            return self._topk_packed_impl(sk, k, q)

    def topk_budgeted(self, queries, k: int, deadline=None
                      ) -> tuple[np.ndarray, np.ndarray, dict]:
        """`topk` under a latency budget: (ids, dists, info), where info
        carries {"partial", "cert_gap"}.  `deadline` is any object with an
        `expired` property (repro.serve.Deadline); when it fires before the
        band walk's exactness certificate closes, the walk stops, the best
        candidates seen so far come back with info["partial"]=True, and
        info["cert_gap"] is the residual certificate gap (DESIGN.md 8.4) —
        how far the k-th bound would have to move for the answer to be
        provably exact.  With deadline=None (or when the walk finishes in
        budget) the result is bit-identical to `topk` and partial is False.

        Unfilled slots in a partial answer carry id -1 and distance inf
        (fewer than k candidates were reachable in budget).  Mid-migration,
        queries fall back to the exact dual-version path — a migration
        already bounds its own per-batch work, so budgets do not compound.
        """
        if k < 0:
            raise ValueError(f"topk: k must be >= 0, got {k}")
        self._drive()  # migration pacing stays OUTSIDE the query timer
        info: dict = {"partial": False, "cert_gap": 0.0}
        with self._h_lat["topk"].time(), obs.span("engine.topk", k=k):
            if self._mig is not None:
                ids, dists = self._topk_migrating(queries, k)
                return ids, dists, info
            sk, q = self._sketch(queries)
            ids, dists = self._topk_packed_impl(sk, k, q, deadline=deadline,
                                                info_out=info)
            if info["partial"]:
                ids = np.where(ids == allpairs.KBEST_KEY_PAD, -1, ids)
            return ids, dists, info

    def topk_packed(self, sk, k: int, n_valid: int | None = None
                    ) -> tuple[np.ndarray, np.ndarray]:
        """Served through the partition layer (PartitionSet.topk): each
        shard's base partition runs a progressive band expansion that
        visits bands nearest-first and stops at the exactness certificate
        (seeded with the cross-partition running k-th bound), the delta
        partitions of fresh adds are scanned brute-force, and everything
        merges by (value, id) — so a query touches O(answer neighbourhood
        + delta) rows, not O(N), while returning bit-identical results to
        topk_rows over the alive membership at every shard count.  The LRU
        is consulted on the query-sketch bytes BEFORE the layout or any
        device gather is touched: a cache hit costs O(1) host work
        regardless of store size."""
        if k < 0:
            raise ValueError(f"topk: k must be >= 0, got {k}")
        if self._mig is not None:
            raise RuntimeError(
                "topk_packed is unavailable mid-migration (packed queries "
                "are spec-ambiguous); use topk() with raw rows")
        with self._h_lat["topk"].time(), obs.span("engine.topk", k=k):
            return self._topk_packed_impl(sk, k, n_valid)

    def _topk_packed_impl(self, sk, k: int, n_valid: int | None,
                          deadline=None, info_out: dict | None = None
                          ) -> tuple[np.ndarray, np.ndarray]:
        if info_out is not None:
            info_out.update(partial=False, cert_gap=0.0)
        sk = jnp.asarray(sk)
        q = sk.shape[0] if n_valid is None else n_valid
        if not 0 <= q <= sk.shape[0]:
            raise ValueError(
                f"n_valid={q} outside the {sk.shape[0]} supplied rows")
        kk = min(k, len(self.store))
        if q == 0 or kk == 0:
            return (np.zeros((q, 0), np.int64), np.zeros((q, 0), np.float32))
        q_host, q_weights = self._query_host(sk, q)
        key = None  # caching disabled: skip the device sync for the key
        if self._cache_entries:
            key = ("topk", kk, self.store.version, q_host.tobytes())
            hit = self._cached(key)
            if hit is not None:
                # cached answers are always exact: partial results never
                # enter the LRU (below), so a budgeted call served from
                # cache is a free upgrade to the full answer
                return hit[0].copy(), hit[1].copy()
        layout = self._layout()
        out = layout.topk(pad_rows_pow2(sk), q_weights, kk, q_valid=q,
                          block=self.block, mode=self.mode,
                          deadline=deadline, info_out=info_out)
        if info_out is not None and info_out.get("partial"):
            key = None  # a partial answer must not shadow the exact one
        self._remember(key, out)
        return out

    @staticmethod
    def _query_host(sk, q: int) -> tuple[np.ndarray, np.ndarray]:
        """(host copy of the q real query sketches, their popcounts): the
        band planner's input and the result cache's key, and the query
        path's device sync before the walk."""
        with obs.span("engine.query_sync", rows=q):
            q_host = np.asarray(sk[:q])
            return q_host, packing.np_popcount_rows(q_host)

    def radius(self, queries, r: float) -> list[np.ndarray]:
        """All stored rows within distance < r of each query: a list of Q
        id arrays (ascending).  Weight bands whose score interval is out of
        reach are pruned on host before any tile is computed; the delta
        tier of fresh adds is scanned brute-force.  Accepts dense rows or
        an (indices, values) COO pair; `radius_packed` skips sketching.

        Distances are nonnegative and the test is strict (`dist < r`), so
        r <= 0 returns an empty id array for every query — an explicit
        contract, not an error (negative radii short-circuit before any
        layout or device work)."""
        self._drive()  # migration pacing stays OUTSIDE the query timer
        with self._h_lat["radius"].time(), obs.span("engine.radius", r=r):
            if self._mig is not None:
                return self._radius_migrating(queries, r)
            sk, q = self._sketch(queries)
            return self._radius_packed_impl(sk, r, q)

    def radius_packed(self, sk, r: float, n_valid: int | None = None
                      ) -> list[np.ndarray]:
        """Pre-sketched twin of `radius` (same r <= 0 -> empty contract)."""
        if self._mig is not None:
            raise RuntimeError(
                "radius_packed is unavailable mid-migration (packed queries "
                "are spec-ambiguous); use radius() with raw rows")
        with self._h_lat["radius"].time(), obs.span("engine.radius", r=r):
            return self._radius_packed_impl(sk, r, n_valid)

    def _radius_packed_impl(self, sk, r: float, n_valid: int | None
                            ) -> list[np.ndarray]:
        sk = jnp.asarray(sk)
        q = sk.shape[0] if n_valid is None else n_valid
        if not 0 <= q <= sk.shape[0]:
            raise ValueError(
                f"n_valid={q} outside the {sk.shape[0]} supplied rows")
        if q == 0:
            return []
        if r <= 0:  # dist >= 0 and the test is strict: provably no hits
            return [np.zeros(0, np.int64) for _ in range(q)]
        q_host, q_weights = self._query_host(sk, q)
        key = None
        if self._cache_entries:
            key = ("radius", float(r), self.store.version, q_host.tobytes())
            hit = self._cached(key)
            if hit is not None:
                return [a.copy() for a in hit]
        hits: list[list[np.ndarray]] = [[] for _ in range(q)]
        if len(self.store):
            layout = self._layout()
            # partition memberships partition the alive set: per-partition
            # hits union to exactly the batch engine's answer on the full
            # membership (partition.radius_hits — the one collection pass)
            partition.radius_hits(
                layout, pad_rows_pow2(sk), q_weights, q, r,
                metric=self.metric, block=min(self.block, 256),
                mode=self.mode, hits=hits)
        out = [np.sort(np.concatenate(h)) if h else np.zeros(0, np.int64)
               for h in hits]
        self._remember(key, out)
        return out

    # -- cross-version serving (mid-migration) -------------------------------

    def _sketch_per_spec(self, queries, specs) -> dict:
        """Sketch the same raw queries once under every distinct spec in
        `specs` — the cross-version serving discipline: each tier is
        queried in its OWN sketch space, results merge in id/distance
        space (which both specs estimate for "cham")."""
        out: dict[int, tuple[jnp.ndarray, int]] = {}
        for spec in specs:
            if spec.version not in out:
                out[spec.version] = self._sketch(queries, params=spec.params)
        return out

    def _topk_migrating(self, queries, k: int
                        ) -> tuple[np.ndarray, np.ndarray]:
        """topk across the migration's live tiers (old-spec remainder,
        new-spec migrated rows, new-spec fresh mutations) — each tier a
        whole PartitionSet, sharded or not, under its own spec.  Tier
        memberships partition the alive ids and the cross-set merge is
        partition.topk_across_tiers (the same (value, id)-lex rule, with
        the running k-th bound threaded across sets) — so the result
        equals merging per-store reference answers, each under its own
        spec.  The LRU is bypassed: mid-migration versions span three
        stores and the window is transient."""
        tiers = self._mig.serving_tiers()
        kk = min(k, len(self))
        if not tiers or kk == 0:
            _, q = self._sketch(queries)
            return (np.zeros((q, 0), np.int64), np.zeros((q, 0), np.float32))
        sketched = self._sketch_per_spec(queries, [s for _, s in tiers])
        q = next(iter(sketched.values()))[1]
        if q == 0:
            return (np.zeros((0, 0), np.int64), np.zeros((0, 0), np.float32))
        staged = []
        for layout, spec in tiers:
            sk, _ = sketched[spec.version]
            staged.append((layout, pad_rows_pow2(sk),
                           self._query_host(sk, q)[1]))
        return partition.topk_across_tiers(kk, staged, q_valid=q,
                                           block=self.block, mode=self.mode)

    def _radius_migrating(self, queries, r: float) -> list[np.ndarray]:
        """radius across the migration's live tiers — per-tier hits union
        to the answer over the full alive membership (strict `dist < r`,
        each tier scored in its own sketch space)."""
        tiers = self._mig.serving_tiers()
        if not tiers:
            _, q = self._sketch(queries)
            return [np.zeros(0, np.int64) for _ in range(q)]
        sketched = self._sketch_per_spec(queries, [s for _, s in tiers])
        q = next(iter(sketched.values()))[1]
        if q == 0:
            return []
        if r <= 0:
            return [np.zeros(0, np.int64) for _ in range(q)]
        hits: list[list[np.ndarray]] = [[] for _ in range(q)]
        for layout, spec in tiers:
            sk, _ = sketched[spec.version]
            partition.radius_hits(
                layout, pad_rows_pow2(sk), self._query_host(sk, q)[1],
                q, r, metric=self.metric, block=min(self.block, 256),
                mode=self.mode, hits=hits)
        return [np.sort(np.concatenate(h)) if h else np.zeros(0, np.int64)
                for h in hits]

    def pairwise(self, queries, ids=None) -> tuple[np.ndarray, np.ndarray]:
        """Engine-metric distance matrix (Q, N') between queries and the
        given stored ids (default: all alive rows, id order) — the
        re-ranking path, served by the kernels.hamming query-vs-store tiles.
        Returns (ids (N',), dists (Q, N') f32).  Under "hamming" entries are
        exact integers; under "cham" they agree with topk/radius distances
        to cross-graph libm noise (~1e-7 relative), not bit-for-bit — the
        bit-identity contract belongs to topk/radius, which always go
        through core.allpairs."""
        from repro.kernels.hamming import ops as hamming_ops

        if self._mig is not None:
            raise RuntimeError(
                "pairwise is unavailable mid-migration: rows live under two "
                "specs and a single distance matrix would mix sketch spaces; "
                "drive the migration to completion first (migrate_all())")
        with self._h_lat["pairwise"].time(), obs.span("engine.pairwise"):
            return self._pairwise_impl(hamming_ops, queries, ids)

    def _pairwise_impl(self, hamming_ops, queries, ids
                       ) -> tuple[np.ndarray, np.ndarray]:
        sk, q = self._sketch(queries)
        # empty-traffic fast paths: an empty store or a 0-row query batch
        # answers from host metadata alone — well-typed empty matrices,
        # no device gather and no kernel call on degenerate pow2-padded
        # shapes.  Explicit ids still get full validation (duplicates,
        # membership) so the contract does not weaken at q == 0.
        if q == 0 or (ids is None and len(self.store) == 0):
            all_ids = self.store.ids()
            if ids is None:
                sel_ids = all_ids
            else:
                sel_ids = np.atleast_1d(np.asarray(ids, np.int64))
                if len(np.unique(sel_ids)) != len(sel_ids):
                    raise ValueError("pairwise: duplicate ids in batch")
                m = len(all_ids)
                pos = np.searchsorted(all_ids, sel_ids)
                if m == 0 or (pos >= m).any() or (
                        all_ids[np.minimum(pos, m - 1)] != sel_ids).any():
                    raise KeyError("pairwise: id not in store")
            return sel_ids, np.zeros((q, len(sel_ids)), np.float32)
        view = self.store.gather_alive()
        # cheap stale-view guard BEFORE anything dereferences the matrix
        # (the id-subset padded_take below, then the kernel call): a view
        # predating a mutation (re-entrant callback, another thread) fails
        # here with a clear message instead of jax's opaque "Array has
        # been deleted" after a donated append
        self.store.check_fresh(view)
        mat, m, all_ids = view
        # keep everything pow2-bucketed (sk and mat already are; id subsets
        # go through padded_take) so the kernel's compile cache stays
        # O(log N) across mutations — same discipline as topk/radius
        if ids is None:
            sel_ids = all_ids
            sel, n_sel = mat, m
        else:
            sel_ids = np.atleast_1d(np.asarray(ids, np.int64))
            if len(np.unique(sel_ids)) != len(sel_ids):
                # consistent with SketchStore.remove: duplicate ids are a
                # caller bug, not a request for duplicated columns
                raise ValueError("pairwise: duplicate ids in batch")
            pos = np.searchsorted(all_ids, sel_ids)
            if m == 0 or (pos >= m).any() or (all_ids[np.minimum(pos, m - 1)]
                                              != sel_ids).any():
                raise KeyError("pairwise: id not in store")
            sel = packing.padded_take(mat, pos)
            n_sel = len(pos)
        dists = np.asarray(hamming_ops.dist_matrix(
            sk, sel, self.d, metric=self.metric))[:q, :n_sel]
        return sel_ids, dists

    def cluster(self, k: int, **kwargs) -> "object":
        """Attach a `repro.cluster.ClusterIndex` maintaining k-medoid
        centres and per-row labels over this engine's store: fresh adds are
        assigned to their nearest centre as they arrive (through this
        engine's own serving path), removes update the per-cluster
        bookkeeping, and `refit()` re-clusters the live membership with the
        device k-mode engine.  Keyword args (seed/n_iter/block/refit_every)
        forward to ClusterIndex; see repro/cluster/online.py.  The store
        keeps a strong reference to the attached index — `detach()` an old
        one before attaching a replacement."""
        from repro.cluster import ClusterIndex  # local: repro.cluster
        # imports this module, so the hook resolves the cycle lazily

        return ClusterIndex(self, k, **kwargs)

    def _new_layout(self, store: SketchStore, role: str = "serve"
                    ) -> PartitionSet:
        """Build a PartitionSet over `store` under this engine's serving
        config (band rows, merge policy, registry) AND its shard topology —
        the one layout factory every serving structure goes through, so a
        sharded engine's migration tiers are sharded too."""
        return PartitionSet(store, self.metric, band_rows=self.band_rows,
                            merge_ratio=self.merge_ratio, registry=self.obs,
                            n_shards=self._n_shards, devices=self._devices,
                            role=role)

    def sync_layout(self) -> PartitionSet:
        """Sync the serving layout (a PartitionSet — one (base, delta)
        group per shard) to the store's current version and return it —
        the maintenance the next query would otherwise pay inline.
        Validity is a version RANGE, not version equality: within a slot
        epoch the sync absorbs adds into the per-shard delta partitions
        and removes into the alive masks in O(delta); only compaction
        (epoch bump) or the per-shard merge policy pays a rebuild.
        Calling this after an ingest burst keeps tail latency flat;
        queries call it implicitly."""
        if self._tiered is None:
            self._tiered = self._new_layout(self.store)
        return self._tiered.sync(self.store)

    _layout = sync_layout  # internal alias used by the query paths

    def _banded_layout(self) -> BandedLayout:
        """The synced layout's BASE partition (single-shard introspection +
        tests; serving goes through `_layout`, which also covers the delta
        partitions and all shards)."""
        return self._layout().base

    # -- persistence --------------------------------------------------------

    def _set_store(self, store: SketchStore) -> None:
        """Install a restored serving store: reset the layout and wire the
        engine-level event relay (restore builds stores outside __init__)."""
        self.store = store
        store.set_registry(self.obs)
        self._tiered = None
        self._attach_relay(store)

    def save(self, directory: str, step: int = 0, keep: int = 3) -> None:
        """Snapshot the full index via checkpoint.Checkpointer — same
        atomic-publish layout as model checkpoints, so index snapshots ride
        the existing retention/GC, integrity records, and fault-injection
        crash points.  One step holds the serving store, the raw archive,
        and — mid-migration — BOTH new-spec tiers plus the cursor/spec-pair
        journal record: the unit of atomicity is the whole engine, which is
        what makes crash recovery unable to lose an acked mutation."""
        from repro.checkpoint.checkpointer import Checkpointer

        ckpt = Checkpointer(directory, keep=keep, async_save=False)
        # one snapshot subtree per backing store (partition.snapshot_subtrees
        # — layouts are derived state; a restored engine, sharded or not,
        # rebuilds them from the stores alone)
        tree = partition.snapshot_subtrees(self.store, raw=self.raw,
                                           migration=self._mig)
        meta = {
            "format": "repro.index.v2",
            "metric": self.metric,
            "spec": self.spec.meta(),
            "store_meta": self.store.state_meta(),
            "keep_raw": self.raw is not None,
        }
        if self._mig is not None:
            meta["migration"] = self._mig.meta()
        ckpt.save(step, tree, extra_meta=meta, block=True)

    @classmethod
    def restore(cls, directory: str, step: int | None = None,
                **engine_kwargs) -> "QueryEngine":
        """Rebuild an engine from a snapshot; queries against the restored
        engine are bit-identical to the engine that saved it.  step=None
        restores the NEWEST INTACT step — corrupt or partially-written
        snapshots are verified against their integrity records and skipped
        (checkpoint.CheckpointCorruptError if none survive).  A snapshot
        taken mid-migration resumes the migration exactly where the journal
        left it: already-migrated rows stay migrated, acked mutations stay
        acked, and serving continues cross-version."""
        from repro.checkpoint.checkpointer import Checkpointer

        ckpt = Checkpointer(directory, async_save=False)
        if ckpt.latest_step() is None:
            raise FileNotFoundError(f"no index snapshots in {directory}")
        flat, step = ckpt.restore(step=step)
        meta = ckpt.meta(step)
        fmt = meta.get("format")
        if fmt == "repro.index.v1":
            return cls._restore_v1(flat, meta, engine_kwargs)
        if fmt != "repro.index.v2":
            raise ValueError(f"not an index snapshot: {directory}")
        if "metric" in engine_kwargs:
            raise ValueError("metric is fixed by the snapshot "
                             f"({meta['metric']!r}); it cannot be overridden "
                             "on restore")
        if "keep_raw" in engine_kwargs:
            raise ValueError("keep_raw is fixed by the snapshot "
                             f"({meta['keep_raw']}); it cannot be overridden "
                             "on restore")

        def sub(prefix: str) -> dict:
            return {k[len(prefix):]: v for k, v in flat.items()
                    if k.startswith(prefix)}

        spec = SketchSpec.from_meta(meta["spec"])
        eng = cls(spec.params, metric=meta["metric"],
                  keep_raw=meta["keep_raw"], **engine_kwargs)
        eng.spec = spec
        eng._set_store(SketchStore.from_state(
            sub("store/"), meta["store_meta"], spec=spec))
        if meta["keep_raw"]:
            eng.raw = RawArchive.from_state(sub("raw/"))
        if "migration" in meta:
            mmeta = meta["migration"]
            new_spec = SketchSpec.from_meta(mmeta["new_spec"])
            dst = SketchStore.from_state(
                sub("mig_dst/"), mmeta["dst_meta"], spec=new_spec)
            fresh = SketchStore.from_state(
                sub("mig_fresh/"), mmeta["fresh_meta"], spec=new_spec)
            eng._mig = Migration.resume(eng, mmeta, dst, fresh)
            eng._attach_relay(dst)
            eng._attach_relay(fresh)
        return eng

    @classmethod
    def _restore_v1(cls, flat: dict, meta: dict,
                    engine_kwargs: dict) -> "QueryEngine":
        """Pre-migration snapshot format: one store, no raw archive (the
        restored engine starts an empty one — rows saved under v1 cannot be
        re-sketched until re-ingested)."""
        if "metric" in engine_kwargs:
            raise ValueError("metric is fixed by the snapshot "
                             f"({meta['metric']!r}); it cannot be overridden "
                             "on restore")
        params = CabinParams(
            n_dims=int(meta["n_dims"]), sketch_dim=int(meta["sketch_dim"]),
            psi_seed=int(meta["psi_seed"]), pi_seed=int(meta["pi_seed"]))
        eng = cls(params, metric=meta["metric"], **engine_kwargs)
        eng._set_store(SketchStore.from_state(flat, meta, spec=eng.spec))
        return eng

    # -- placement ----------------------------------------------------------

    def shard(self, mesh=None, *, n_shards: int | None = None) -> None:
        """Opt-in scale-out: re-home the serving layout as one partition
        group per device of `mesh` (default: the ambient mesh), or as
        `n_shards` logical shards on the default device (no mesh needed —
        what single-device tests and CI exercise).  Rows route by
        ``id % n_shards`` — deterministic and stable across compaction —
        each shard keeps its own base+delta partitions with its matrices
        committed to its device, per-shard band walks share the global
        running k-th bound, and answers merge by (value, id) cross-shard.
        Every query stays bit-identical to the unsharded engine on the
        same history (partition.py's exactness argument); ClusterIndex,
        migrations, and the serving front door work unchanged.  Calling
        shard() again (or with a different mesh) re-shards; in-flight
        migrations pick the new topology up on their next layout build."""
        from repro.distributed import sharding as shd

        if n_shards is not None:
            if mesh is not None:
                raise ValueError("shard(): pass a mesh OR n_shards")
            if int(n_shards) < 1:
                raise ValueError(f"n_shards must be >= 1, got {n_shards}")
            devices = None
            n = int(n_shards)
        else:
            mesh = mesh if mesh is not None else shd.current_mesh()
            if mesh is None:
                raise ValueError("shard() needs a mesh (none active)")
            devices = shd.mesh_devices(mesh)
            n = len(devices)
        self._n_shards = n
        self._devices = devices
        # layouts are derived: drop them (serving and migration tiers) and
        # let the next query rebuild under the new topology.  Cached
        # RESULTS stay valid — answers are placement-independent — but the
        # cache is cleared anyway so a re-shard behaves like the fresh
        # engine it is equivalent to.
        self._tiered = None
        if self._mig is not None:
            self._mig.invalidate_serving_tiers()
        self._cache.clear()
