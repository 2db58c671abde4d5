"""Faults planted in the program under the harness, for its self-tests.

Each function breaks the timed path through pytest's `monkeypatch`, so
that a run of the harness must come out not `correct`.  A cell class
names the faults it can have in its `FAULTS` (bench/run.py); a kind that
the program's faults do not reach brings planters of its own.
"""

from __future__ import annotations

import numpy as np


def state_unchanged(monkeypatch) -> None:
    """After set-up, the store hands out ids but stops taking rows."""
    from repro.index import QueryEngine
    from repro.index.store import SketchStore

    real = SketchStore.add

    def add(self, packed, n_valid=None):  # ids handed out, rows dropped
        ids = np.arange(self._next_id, self._next_id
                        + (packed.shape[0] if n_valid is None
                           else n_valid), dtype=np.int64)
        if getattr(self, "_bench_loaded", False):
            self._next_id = int(ids[-1]) + 1
            return ids
        return real(self, packed, n_valid)
    monkeypatch.setattr(SketchStore, "add", add)
    real_sync = QueryEngine.sync_layout

    def sync(self):  # after set-up, the store stops taking rows
        self.store._bench_loaded = True
        return real_sync(self)
    monkeypatch.setattr(QueryEngine, "sync_layout", sync)
    real_remove = QueryEngine.remove

    def remove(self, ids):
        self.store._bench_loaded = True
        return real_remove(self, np.asarray(ids)[
            np.isin(ids, self.store.ids())])
    monkeypatch.setattr(QueryEngine, "remove", remove)


def half_batch(monkeypatch) -> None:
    """Half of each batch is served: queries answered as the first half,
    rows added as ids with no sketch."""
    from repro.index import QueryEngine

    real = QueryEngine.topk

    def topk(self, queries, k):  # the second half answers as the first
        idx, val = queries
        h = max(1, len(idx) // 2)
        ids, d = real(self, (idx[:h], val[:h]), k)
        rep = np.resize(np.arange(h), len(idx))
        return ids[rep], d[rep]
    monkeypatch.setattr(QueryEngine, "topk", topk)
    real_r = QueryEngine.radius

    def radius(self, queries, r):
        idx, val = queries
        h = max(1, len(idx) // 2)
        hits = real_r(self, (idx[:h], val[:h]), r)
        return [hits[i % h] for i in range(len(idx))]
    monkeypatch.setattr(QueryEngine, "radius", radius)
    real_a = QueryEngine.add_sparse

    def add_sparse(self, indices, values):
        h = len(indices) // 2
        ids = real_a(self, indices[:h], values[:h])
        rest = np.arange(ids[-1] + 1, ids[-1] + 1 + len(indices) - h)
        self.store._next_id = int(rest[-1]) + 1
        return np.concatenate([ids, rest])
    monkeypatch.setattr(QueryEngine, "add_sparse", add_sparse)
    real_rm = QueryEngine.remove
    monkeypatch.setattr(QueryEngine, "remove", lambda self, ids: real_rm(
        self, np.asarray(ids)[np.isin(ids, self.store.ids())]))


def answer_altered(monkeypatch) -> None:
    """One answer or one row altered where it is produced."""
    from repro.index import QueryEngine

    real = QueryEngine.topk

    def topk(self, queries, k):
        ids, d = real(self, queries, k)
        ids = ids.copy()
        ids[0, 0] = (ids[0, 0] + 1) % len(self)
        return ids, d
    monkeypatch.setattr(QueryEngine, "topk", topk)
    real_r = QueryEngine.radius

    def radius(self, queries, r):
        hits = real_r(self, queries, r)
        hits[0] = np.union1d(hits[0], [(hits[0][0] + 1) % len(self)
                                       if len(hits[0]) else 0])
        return hits
    monkeypatch.setattr(QueryEngine, "radius", radius)
    real_a = QueryEngine.add_sparse

    def add_sparse(self, indices, values):
        values = np.array(values)
        values[0, :] = 0  # the first row of each batch loses its words
        return real_a(self, indices, values)
    monkeypatch.setattr(QueryEngine, "add_sparse", add_sparse)
