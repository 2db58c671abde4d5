"""The `rolling_ingest` kind: a news archive that takes new articles all the
time and keeps the newest `rows` searchable.

Set-up stores the corpus as the serving cells do (device draw, the
program's sketch kernel, `add_packed`) and draws a pool of host COO
batches from a stream of its own.  Each step of the load is one
`QueryEngine.add_sparse` of a pool batch (cycled; every cycle gets new
ids), then `remove` of the oldest batch's worth of ids, then `compact()`
once tombstones reach `compact_share` of the live rows.  Warm-up runs
steps until one compaction has happened, so every shape the window uses
is compiled.

`check` holds the membership to the retention history (exactly the
newest `rows` ids are alive) and a sample of the stored sketches of rows
added by the load, drawn from the seed, to numpy Cabin.
"""

from __future__ import annotations

import gc
import time

import numpy as np
import jax

import corpus as corpus_mod
import faults
import reference as ref
from cell_open_loop import (load_corpus, make_engine, make_params, one_chip,
                            padded_rows, tiny_config)
from corpus import Stream

SKETCH_SAMPLE = 512
WARMUP_MAX_STEPS = 64


class Cell:
    """bench/run.py's cell protocol for rolling ingest."""

    CONTROLS = ("hash16",)
    FAULTS = {"state_unchanged": faults.state_unchanged,
              "half_batch": faults.half_batch,
              "answer_altered": faults.answer_altered}
    OFFERS_RATE = False

    def __init__(self, workload: dict, cfg: dict, traffic: dict, seed: int,
                 clock, span):
        one_chip(traffic["kind"], workload)
        self.cfg = cfg
        self.traffic = traffic
        self.seed = seed
        self.clock = clock
        self.span = span
        self.corpus = corpus_mod.corpus_of(cfg)
        self.batch_rows = int(traffic["batch_rows"])
        self.keep = int(cfg["corpus"]["rows"])
        self.compact_share = float(traffic["compact_share"])

    @classmethod
    def tiny(cls, cfg: dict, traffic: dict):
        traffic.update(batch_rows=256, pool_batches=3)
        return tiny_config(cfg), traffic

    def setup(self) -> None:
        cfg, seed = self.cfg, self.seed
        self.params = make_params(cfg, seed)
        self.engine = make_engine(cfg, self.params)
        stream = Stream(self.corpus, seed, corpus_mod.CORPUS_STREAM)
        t = time.perf_counter()
        load_corpus(self.engine, self.params, cfg, stream,
                    np.zeros(0, np.int64))
        self.phases = {"corpus_s": time.perf_counter() - t}
        t = time.perf_counter()
        pool = Stream(self.corpus, seed, corpus_mod.POOL_STREAM)
        self.pool = [tuple(np.asarray(a) for a in pool.batch(b,
                                                            self.batch_rows))
                     for b in range(int(self.traffic["pool_batches"]))]
        self.oldest = 0  # ids are assigned in order: alive = [oldest, next)
        self.added: list = []  # (first id, pool batch) of every load step
        self.steps = 0
        self.compactions = 0
        self.phases["pool_s"] = time.perf_counter() - t
        t = time.perf_counter()
        self.warmup()
        self.phases.update(warmup_s=time.perf_counter() - t,
                           warmup_steps=self.steps)

    def step(self) -> None:
        b = self.steps % len(self.pool)
        idx, val = self.pool[b]
        with self.span("bench.add_sparse"):
            ids = self.engine.add_sparse(idx, val)
        self.added.append((int(ids[0]), b))
        gone = np.arange(self.oldest, self.oldest + len(ids))
        with self.span("bench.remove"):
            self.engine.remove(gone)
        self.oldest += len(ids)
        store = self.engine.store
        if store.size - len(store) >= self.compact_share * len(store):
            with self.span("bench.compact"):
                self.engine.compact()
            self.compactions += 1
        self.steps += 1

    def warmup(self) -> None:
        while self.compactions == 0 or self.steps < 2:
            if self.steps >= WARMUP_MAX_STEPS:
                raise RuntimeError("warm-up never compacted")
            self.step()
        self.step()
        jax.block_until_ready(self.engine.store.sk_buf)

    def window(self, ctx, seconds: float):
        """Steps until `seconds` have passed; the readers get the rows
        acknowledged and the seconds they took."""
        self.first_step = self.steps
        self.t0 = time.perf_counter()
        t_end = self.t0 + seconds
        while time.perf_counter() < t_end:
            self.step()
        jax.block_until_ready(self.engine.store.sk_buf)
        self.t1 = time.perf_counter()
        self.window_steps = self.steps - self.first_step
        self.window_rows = self.window_steps * self.batch_rows
        ctx.ingest = (self.window_rows, self.t1 - self.t0)
        return self.t0, self.t1, self.window_steps, 0

    def obs(self):
        return self.engine.obs

    def release(self) -> None:
        view = self.engine.store.gather_alive()
        self.alive_ids = np.asarray(view.ids)
        rng = np.random.default_rng([self.seed, 13])
        firsts = np.array([a for a, _ in self.added])
        loaded = self.alive_ids[self.alive_ids >= firsts.min()]
        self.sample_ids = np.sort(rng.choice(
            loaded, min(SKETCH_SAMPLE, len(loaded)), replace=False))
        pos = np.searchsorted(view.ids, self.sample_ids)
        self.stored = np.asarray(view.matrix[padded_rows(pos)])[:len(pos)]
        del view
        self.engine = None
        gc.collect()

    def check(self, controls: tuple = ()) -> dict:
        """Numbers compared, {name: (value, limit)}, under "program", and
        under the "hash16" control (stored sketches made with a 16-bit
        hash mixer) the number it replaces."""
        next_id = self.added[-1][0] + self.batch_rows
        want = np.arange(next_id - self.keep, next_id)
        alive_wrong = len(np.setxor1d(self.alive_ids, want))
        firsts = np.array([a for a, _ in self.added])
        which = np.searchsorted(firsts, self.sample_ids, side="right") - 1
        idx = np.stack([self.pool[self.added[w][1]][0][i - firsts[w]]
                        for i, w in zip(self.sample_ids, which)])
        val = np.stack([self.pool[self.added[w][1]][1][i - firsts[w]]
                        for i, w in zip(self.sample_ids, which)])
        p = self.params
        want_sk = ref.np_sketch(p.sketch_dim, p.psi_seed, p.pi_seed, idx, val)
        self.n_checked = len(self.sample_ids)
        out = {"program": {
            "alive_wrong": (alive_wrong, 0),
            "sketch_wrong": (int(np.count_nonzero(
                np.any(self.stored != want_sk, axis=1))), 0)}}
        if "hash16" in controls:
            cheap = ref.np_sketch(p.sketch_dim, p.psi_seed, p.pi_seed, idx,
                                  val, hash_bits=16)
            out["hash16"] = {"sketch_wrong": (int(np.count_nonzero(
                np.any(cheap != want_sk, axis=1))), 0)}
        return out
