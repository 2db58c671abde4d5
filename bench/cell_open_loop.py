"""Serving cells, and the `open_loop` kind: requests offered at a fixed
rate through `serve.FrontDoor`.

`ServingCell` is every serving kind's set-up, warm-up and check; a kind
names its load (`make_load`).  Set-up draws the corpus on the device,
sketches it with the program's own `sketch_sparse_jit` (the
`cabin_build_sparse` kernel on a TPU) and stores it with
`QueryEngine.add_packed`, with no host copy; one call builds the serving
layout.  Queries are drawn in set-up too: fresh rows from a stream
disjoint from the corpus's, or stored rows whose entries are dropped with
a fresh mask per request.  Warm-up offers the load on other queries until
two rounds in a row compile nothing.  Here the window then offers requests
at the traffic's fixed `rate_per_s` (bench/sweep.py finds the rate the
system sustains); a request due before the window closes is awaited and
counted.

`check` compares a sample of the window's answers, drawn from the seed,
with the plain reference (bench/reference.py) once the program's state is
freed.
"""

from __future__ import annotations

import gc
import math
import queue
import threading
import time

import numpy as np
import jax
import jax.numpy as jnp

import corpus as corpus_mod
import faults
import reference as ref
from corpus import Stream

RESULT_GRACE_S = 60.0  # an answer may come this long after the window
WARMUP_MAX_S = 120.0  # keeps a run inside its time limit
WARMUP_MIN_ROUND_S = 1.0
CLEAN_ROUNDS = 2
SKETCH_SAMPLE = 256
ARRIVALS_KEY = 7163  # the gap multiset every seed shares


def pow2(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


def padded_rows(rows: np.ndarray) -> jnp.ndarray:
    """Row numbers padded (with row 0) to a power of two of at least 64:
    a gather by them compiles for a few shapes, not one per count."""
    out = np.zeros(max(64, pow2(len(rows))), np.int32)
    out[:len(rows)] = rows
    return jnp.asarray(out)


def make_params(cfg: dict, seed: int):
    from repro.core.cabin import CabinParams
    return CabinParams.create(cfg["corpus"]["n_dims"],
                              cfg["sketch"]["sketch_dim"],
                              seed=seed % (2**31))


def make_engine(cfg: dict, params):
    """The engine with every setting of the configuration's `engine`."""
    from repro.index import QueryEngine
    return QueryEngine(params, metric=cfg["sketch"]["metric"],
                       **cfg["engine"])


def one_chip(kind: str, workload: dict) -> None:
    """Kinds that run one unsharded engine refuse a cell of more chips."""
    if int(workload["chips"]) != 1:
        raise ValueError(f"kind {kind!r} runs one engine on one chip; cell "
                         f"{workload['name']!r} asks for "
                         f"{workload['chips']} chips")


def tiny_config(cfg: dict) -> dict:
    """A configuration shrunk for the CPU tests: a few thousand short rows
    at d=512, flushes of up to 4 rows."""
    cfg["corpus"].update(rows=3000, n_dims=5000, nnz_mean=30.0, nnz_max=100,
                         zipf_draws=512, gen_rows=1024)
    cfg["sketch"]["sketch_dim"] = 512
    cfg["engine"].update(block=256, band_rows=128)
    cfg["frontdoor"]["max_batch_rows"] = 4
    return cfg


def corpus_batches(cfg: dict):
    """[(batch index, first row, valid rows)] of the corpus, in set-up's
    generation batches."""
    rows, gen = cfg["corpus"]["rows"], cfg["corpus"]["gen_rows"]
    return [(b, s, min(gen, rows - s))
            for b, s in enumerate(range(0, rows, gen))]


def load_corpus(engine, params, cfg: dict, stream: Stream,
                keep_rows: np.ndarray):
    """Draw, sketch and store the corpus; returns the host COO of
    `keep_rows` (sorted corpus row numbers), in that order, or None when
    it is empty."""
    from repro.core.cabin import sketch_sparse_jit
    gen = cfg["corpus"]["gen_rows"]
    wpad = pow2(stream.corpus.width)
    kept_idx, kept_val = [], []
    for b, start, n in corpus_batches(cfg):
        idx, val = stream.batch(b, gen)
        pad = ((0, 0), (0, wpad - idx.shape[1]))
        sk = sketch_sparse_jit(params, jnp.pad(idx, pad), jnp.pad(val, pad))
        ids = engine.add_packed(sk[:n] if n < gen else sk)
        if ids[0] != start:
            raise RuntimeError(f"ids start at {ids[0]}, not row {start}")
        local = keep_rows[(keep_rows >= start) & (keep_rows < start + n)]
        if len(local):
            sel = padded_rows(local - start)
            kept_idx.append(np.asarray(idx[sel])[:len(local)])
            kept_val.append(np.asarray(val[sel])[:len(local)])
    jax.block_until_ready(engine.store.sk_buf)
    if not kept_idx:
        return None
    return np.concatenate(kept_idx), np.concatenate(kept_val)


class Queries:
    """Request n -> (indices, values) of one query row, a function of
    (seed, n, warm) alone.  Warm-up requests (warm=True) never repeat a
    window request: fresh ones come from a second batch of the query
    stream, near-duplicates from another drop mask."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, corpus,
                 sources):
        self.kind = traffic["queries"]
        self.seed = seed
        self.drop = float(traffic.get("drop_fraction", 0.0))
        if self.kind == "fresh":
            pool = traffic["fresh_pool"]
            s = Stream(corpus, seed, corpus_mod.QUERY_STREAM)
            self.rows = [tuple(np.asarray(a) for a in s.batch(b, pool))
                         for b in (0, 1)]
        elif self.kind == "near_duplicate":
            self.rows = [sources, sources]
        else:
            raise ValueError(f"unknown query kind {self.kind!r}")

    def __call__(self, n: int, warm: bool = False):
        all_idx, all_val = self.rows[int(warm)]
        i = n % len(all_idx)
        idx, val = all_idx[i:i + 1], all_val[i:i + 1]
        if self.kind == "near_duplicate":
            rng = np.random.default_rng([self.seed, n, int(warm)])
            idx, val = corpus_mod.drop_entries(idx, val, self.drop, rng)
        return idx, val


def submit(door, op: str, param, q):
    if op == "radius":
        return door.submit("radius", q, r=param)
    return door.submit("topk", q, k=param)


class OpenLoad:
    """Open-loop load on one front door: requests due at fixed times,
    `rate` a second, submitted by one thread; a second thread collects the
    answers in submission order.  A request's latency runs from its due
    time, so a late submission counts against it.

    The arrival times of a window of `seconds` are the same multiset of
    exponential gaps for every seed (drawn under ARRIVALS_KEY, scaled to
    sum to `seconds`), in an order drawn from the seed: every seed offers
    the same number of requests at the same rate."""

    def __init__(self, door, op: str, param, queries: Queries, rate: float,
                 seed: int, span):
        self.door = door
        self.op = op
        self.param = param
        self.queries = queries
        self.rate = float(rate)
        self.seed = seed
        self.span = span
        self.late_s: list = []  # submit time past due, of the last run

    def due_offsets(self, seconds: float, warm: bool) -> np.ndarray:
        n = max(1, int(round(self.rate * seconds)))
        gaps = np.random.default_rng(ARRIVALS_KEY).exponential(1.0, n)
        gaps = np.random.default_rng([self.seed, 13, int(warm)]).permutation(
            gaps) * (seconds / gaps.sum())
        return np.cumsum(gaps) - gaps

    def run(self, seconds: float, first_n: int = 0, warm: bool = False):
        """Offer the load for `seconds` on request numbers first_n,
        first_n + 1, ... (warm-up queries when `warm`), and wait for every
        admitted request's answer.  Returns (t0, records): one record per
        request, (n, t_due, t_answer, ok, result, query, admitted)."""
        from repro.serve import RejectedError
        offsets = self.due_offsets(seconds, warm)
        pending: queue.SimpleQueue = queue.SimpleQueue()
        records: list = []
        t_close = [math.inf]

        def collect():
            while True:
                item = pending.get()
                if item is None:
                    return
                n, t_due, req, q = item
                if req is None:  # refused at admission
                    records.append((n, t_due, t_due, False, None, q, False))
                    continue
                res, ok = None, False
                patience = max(1.0, t_close[0] + RESULT_GRACE_S
                               - time.perf_counter())
                try:
                    with self.span("bench.result"):
                        res = req.result(timeout=patience)
                    ok = res.ok and not res.partial
                except TimeoutError:
                    pass
                records.append((n, t_due, time.perf_counter(), ok, res, q,
                                True))

        collector = threading.Thread(target=collect, daemon=True)
        collector.start()
        late = self.late_s = []
        t0 = time.perf_counter()
        t_close[0] = t0 + seconds
        try:
            for i, off in enumerate(offsets):
                q = self.queries(first_n + i, warm)
                t_due = t0 + off
                wait = t_due - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                late.append(time.perf_counter() - t_due)
                try:
                    with self.span("bench.submit"):
                        req = submit(self.door, self.op, self.param, q)
                except RejectedError:
                    req = None
                pending.put((first_n + i, t_due, req, q))
        finally:
            pending.put(None)
            collector.join(seconds + RESULT_GRACE_S + 30.0)
        if collector.is_alive():
            raise RuntimeError("the answer collector did not finish")
        return t0, records


class ServingCell:
    """A serving cell (bench/run.py's cell protocol) short of its load: a
    kind subclasses it as `Cell` and builds the load in `make_load`."""

    CONTROLS = ("bf16", "hash16")
    FAULTS = {"half_batch": faults.half_batch,
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
        self.op = traffic["op"]
        self.param = (float(cfg["serving"]["radius"]) if self.op == "radius"
                      else int(traffic["k"]))

    # -- set-up -------------------------------------------------------------

    def setup(self) -> None:
        from repro.serve import FrontDoor
        cfg, seed = self.cfg, self.seed
        rows = cfg["corpus"]["rows"]
        rng = np.random.default_rng([seed, 7])
        self.sketch_rows = np.sort(rng.choice(rows, min(SKETCH_SAMPLE, rows),
                                              replace=False))
        n_src = min(int(self.traffic.get("sources", 0)), rows)
        self.source_rows = np.sort(rng.choice(rows, n_src, replace=False))
        self.params = make_params(cfg, seed)
        self.engine = make_engine(cfg, self.params)
        self.stream = Stream(self.corpus, seed, corpus_mod.CORPUS_STREAM)
        t = time.perf_counter()
        sources = load_corpus(self.engine, self.params, cfg, self.stream,
                              self.source_rows)
        self.phases = {"corpus_s": time.perf_counter() - t}
        t = time.perf_counter()
        self.engine.sync_layout()
        self.phases["layout_s"] = time.perf_counter() - t
        self.queries = Queries(cfg, self.traffic, seed, self.corpus, sources)
        self.door = FrontDoor(self.engine, **cfg["frontdoor"])
        self.load = self.make_load()
        t = time.perf_counter()
        self.warmup()
        self.phases.update(warmup_s=time.perf_counter() - t,
                           warmup_rounds=self.warmup_rounds)

    def warmup(self) -> None:
        """Compile what the window's flushes use, then offer the load on
        warm-up queries until CLEAN_ROUNDS rounds of it compile nothing.

        A flush of b rows compiles small programs for b itself (the engine
        pads the query batch and slices its sketches eagerly) and the
        walk's programs for b's power-of-two bucket; the window's flushes
        can hold any b up to a full flush.  So every b is sketched once
        (a radius of 0, which answers nothing without a walk, and the same
        slice of b rows), and every bucket is served once."""
        top = int(self.cfg["frontdoor"]["max_batch_rows"])
        parts = [self.queries(n, warm=True) for n in range(top)]
        q_idx = np.concatenate([q[0] for q in parts])
        q_val = np.concatenate([q[1] for q in parts])
        width = self.params.packed_width
        for b in range(1, top + 1):
            self.engine.radius((q_idx[:b], q_val[:b]), 0.0)
            np.asarray(jnp.zeros((pow2(b), width), jnp.int32)[:b])
        b = 1
        while b <= top:
            q = (q_idx[:b], q_val[:b])
            if self.op == "radius":
                self.engine.radius(q, self.param)
            else:
                self.engine.topk(q, self.param)
            b *= 2
        t_stop = time.perf_counter() + WARMUP_MAX_S
        n, round_s, clean = top, WARMUP_MIN_ROUND_S, 0
        self.warmup_rounds = 0
        while time.perf_counter() < t_stop:
            c0 = time.perf_counter()
            _, recs = self.load.run(round_s, n, warm=True)
            c1 = time.perf_counter()
            n += len(recs)
            self.warmup_rounds += 1
            bad = [r for r in recs if r[6] and not r[3]]
            if bad:
                raise RuntimeError(f"warm-up request failed: {bad[0][4]}")
            # the walk's shapes follow how many queries stay open in each
            # round and how many rows its bands hold, so one quiet round
            # can be luck: stop after CLEAN_ROUNDS in a row
            clean = clean + 1 if self.clock.count(c0, c1) == 0 else 0
            if clean == CLEAN_ROUNDS:
                return
            typical = float(np.median([r[2] - r[1] for r in recs]))
            round_s = max(WARMUP_MIN_ROUND_S, 2.5 * typical)
        # still compiling: the window goes ahead, and its compiles show
        # in the run's window_compiles

    # -- window ---------------------------------------------------------------

    @classmethod
    def tiny(cls, cfg: dict, traffic: dict):
        tiny_config(cfg)
        if traffic["op"] == "radius":
            cfg["serving"]["radius"] = 24.45  # inside a cluster of distances
        for key, small in (("sources", 64), ("fresh_pool", 256)):
            if key in traffic:
                traffic[key] = small
        traffic["check_sample"] = 16
        return cfg, traffic

    def make_load(self):
        raise NotImplementedError

    def window(self, ctx, seconds: float):
        self.t0, self.records = self.load.run(seconds)
        self.phases["refused"] = sum(1 for r in self.records if not r[6])
        ctx.requests[self.op] = [(r[1], r[2], r[3]) for r in self.records]
        return (self.t0, max(r[2] for r in self.records), len(self.records),
                sum(1 for r in self.records if not r[3]))

    def obs(self):
        return self.engine.obs

    def release(self) -> None:
        """Close the door and free the program's state before the
        reference runs; keeps the stored sketches the check reads."""
        view = self.engine.store.gather_alive()
        pos = np.searchsorted(view.ids, self.sketch_rows)
        self.stored = np.asarray(view.matrix[padded_rows(pos)])[:len(pos)]
        self.stored_ids = view.ids[np.minimum(pos, len(view.ids) - 1)]
        self.alive_ids = np.asarray(view.ids)
        self.door.close()
        del view
        self.door = self.load = self.engine = None
        gc.collect()

    # -- the check ------------------------------------------------------------

    def check(self, controls: tuple = ()) -> dict:
        """Numbers compared, {name: (value, limit)}, under "program", and
        under each named control the numbers it replaces: "bf16" (the
        reference's answers with the estimator in bfloat16, the precision
        below the configuration's float32) and "hash16" (stored sketches
        made with a 16-bit hash mixer)."""
        t = self.traffic
        answered = [r for r in self.records if r[3]]
        unanswered = sum(1 for r in self.records if r[6] and not r[3])
        rng = np.random.default_rng([self.seed, 11])
        n_check = min(int(t["check_sample"]), len(answered))
        pick = np.sort(rng.choice(len(answered), n_check, replace=False))
        sample = [answered[i] for i in pick]
        q_idx = np.concatenate([r[5][0] for r in sample])
        q_val = np.concatenate([r[5][1] for r in sample])
        p = self.params
        qbits = ref.np_unpack(ref.np_sketch(p.sketch_dim, p.psi_seed,
                                            p.pi_seed, q_idx, q_val),
                              p.sketch_dim)
        bf = ref.BruteForce(qbits, self.cfg["sketch"]["metric"], p.sketch_dim,
                            candidates=(ref.RADIUS_CANDIDATES
                                        if self.op == "radius"
                                        else ref.CANDIDATES))
        gen = self.cfg["corpus"]["gen_rows"]
        sketch_wrong = int(np.count_nonzero(self.stored_ids
                                            != self.sketch_rows))
        hash16_wrong = 0
        wanted = []  # (sample positions, reference sketches, their COO)
        for b, start, n in corpus_batches(self.cfg):
            idx, val = self.stream.batch(b, gen)
            bits = ref.ref_bits(idx, val, d=p.sketch_dim, psi_seed=p.psi_seed,
                                pi_seed=p.pi_seed)
            bf.add_batch(bits, np.arange(start, start + n))
            here = np.flatnonzero((self.sketch_rows >= start)
                                  & (self.sketch_rows < start + n))
            if len(here):
                rows = padded_rows(self.sketch_rows[here] - start)
                wanted.append((here, ref.pack_bits(bits[rows]),
                               (idx[rows], val[rows])))
            del bits
        for here, want, (s_idx, s_val) in wanted:
            want = np.asarray(want)[:len(here)]
            s_idx, s_val = (np.asarray(a)[:len(here)] for a in (s_idx, s_val))
            sketch_wrong += int(np.count_nonzero(
                np.any(self.stored[here] != want, axis=1)))
            if "hash16" in controls:
                cheap = ref.np_sketch(p.sketch_dim, p.psi_seed, p.pi_seed,
                                      s_idx, s_val, hash_bits=16)
                hash16_wrong += int(np.count_nonzero(
                    np.any(cheap != want, axis=1)))
        alive_wrong = len(np.setxor1d(self.alive_ids,
                                      np.arange(self.cfg["corpus"]["rows"])))
        out = {"program": {"unanswered": (unanswered, 0),
                           "sketch_wrong": (sketch_wrong, 0),
                           "alive_wrong": (alive_wrong, 0)}}
        if "hash16" in controls:
            out["hash16"] = {"sketch_wrong": (hash16_wrong, 0)}
        if self.op == "radius":
            hits, edge = bf.radius(self.param)
            served = [np.asarray(r[4].hits[0]) for r in sample]
            out["program"]["radius_wrong"] = (
                ref.compare_radius(served, hits, edge), 0)
            if "bf16" in controls:
                low = bf.lower_precision_radius(self.param, _bf16())
                out["bf16"] = {"radius_wrong": (
                    ref.compare_radius(low, hits, edge), 0)}
            self.hits_per_query = [len(h) for h in hits]
        else:
            k, lim = self.param, float(t["dist_err_limit"])
            ref_ids, ref_d, ref_t, lookup = bf.topk(k)
            s_ids = np.concatenate([r[4].ids for r in sample])
            s_d = np.concatenate([r[4].dists for r in sample])
            wrong, err = ref.compare_topk(s_ids, s_d, ref_ids, ref_d, ref_t,
                                          lookup)
            out["program"].update(topk_wrong=(wrong, 0),
                                  topk_dist_err=(err, lim))
            if "bf16" in controls:
                wrong, err = ref.compare_topk(
                    *bf.lower_precision_topk(k, _bf16()), ref_ids, ref_d,
                    ref_t, lookup)
                out["bf16"] = {"topk_wrong": (wrong, 0),
                               "topk_dist_err": (err, lim)}
        self.n_checked = n_check
        return out


def _bf16():
    import ml_dtypes
    return ml_dtypes.bfloat16


class Cell(ServingCell):
    """Open loop at the traffic's `rate_per_s`."""

    OFFERS_RATE = True

    @classmethod
    def tiny(cls, cfg: dict, traffic: dict):
        cfg, traffic = super().tiny(cfg, traffic)
        # faster than a tiny flush on the CPU, so flushes hold several rows
        traffic["rate_per_s"] = 40.0
        return cfg, traffic

    def make_load(self):
        return OpenLoad(self.door, self.op, self.param, self.queries,
                        float(self.traffic["rate_per_s"]), self.seed,
                        self.span)

    def window(self, ctx, seconds: float):
        out = super().window(ctx, seconds)
        late = np.array(self.load.late_s) * 1e3
        self.phases.update(late_p95_ms=float(np.percentile(late, 95)),
                           late_max_ms=float(late.max()))
        return out
