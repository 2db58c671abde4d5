"""Chip smoke test: the sketch-index serving path, end to end, on TPU.

Runs what a user of `repro.index` runs, once, at a real size, and checks
the answers against plain numpy:

  * corpus   — a synthetic twin of the UCI Bag-of-Words NYTimes corpus
               (102,660 dims, 114 categories, ~871 non-missing entries per
               row in padded COO of width 1,306; Zipf feature popularity as
               in repro.data.synthetic, exact over the 128 most popular ids
               and a closed-form power law beyond), 300,000 rows made on the
               device from --seed;
  * ingest   — `QueryEngine.add_sparse` in 8,192-row batches at d=4096,
               sketched by the fused Pallas kernel; a second engine under
               the other metric takes the same sketches via `add_packed`;
  * sketch   — 256 stored rows checked bit for bit against a numpy
               re-implementation of psi/pi/pack written here;
  * serving  — 64 top-10 and 8 radius queries per engine through
               `serve.FrontDoor`, 32 top-k and all radius answers checked
               against brute-force numpy over the stored sketches
               (hamming: exact; cham: float64 estimator, rtol 1e-5, ids
               equal except across ties within that tolerance);
  * mutation — remove 1% of ids, add 4,096 rows, compact; the membership
               must equal the ids this history leaves, and the sampled
               rows that survived plus 64 added rows are checked bit for
               bit again before serving is checked again;
  * kernels  — the lowered sketch and top-k steps must hold a
               `tpu_custom_call`, and the kernels' compile caches must have
               grown, so a path that quietly took the jnp reference fails.

Every phase prints one `smoke {...}` JSON line (rows, device bytes,
compile and steady seconds, mismatch counts).  The last line of standard
output is the result, `{"ok": true, "device": {...}}`, printed only when
every check passed.  The script exits non-zero, before any phase, unless
JAX's first device is a TPU.

    python chip_smoke.py [--seed N]       # one chip
    python chip_smoke.py --chips 4        # only the sharded phase: the
                                          # same corpus, engine.shard() over
                                          # a 4-device mesh, answers compared
                                          # with the unsharded engine

The persistent compile cache is on (repro.runtime.compile_cache): set
JAX_COMPILATION_CACHE_DIR to choose its directory.
"""

from __future__ import annotations

import argparse
import functools
import json
import math
import sys
import time
from dataclasses import dataclass
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import allpairs  # noqa: E402
from repro.core.cabin import CabinParams, sketch_sparse_jit  # noqa: E402
from repro.core.packing import pow2_bucket  # noqa: E402
from repro.index import QueryEngine  # noqa: E402
from repro.kernels.topk_select import kernel as topk_kernel  # noqa: E402
from repro.kernels.topk_select import ops as topk_ops  # noqa: E402
from repro.runtime.compile_cache import enable_compile_cache  # noqa: E402
from repro.serve import FrontDoor  # noqa: E402


@dataclass(frozen=True)
class Corpus:
    """Shape of the corpus twin (UCI Bag-of-Words NYTimes, paper Table 1)."""

    n_dims: int = 102_660
    n_categories: int = 114
    density: int = 871
    width: int = 1306  # padded COO width: 1.5 x density, as in synthetic.py
    rows: int = 300_000  # the NYTimes document count
    zipf_a: float = 1.1
    draws: int = 4096  # Zipf draws per row; ~1,520 distinct ids > width


SKETCH_DIM = 4096
ZIPF_HEAD = 128  # ids drawn by exact table compare; the rest in closed form
BATCH_ROWS = 8192
K = 10
N_TOPK, N_CHECKED, N_RADIUS, N_SAMPLE = 64, 32, 8, 256
N_ADDED, REMOVE_FRACTION = 4096, 0.01
CHAM_RTOL = 1e-5
METRICS = ("hamming", "cham")
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class SmokeFailure(RuntimeError):
    """A check failed: the run must not report ok."""


def emit(phase: str, **fields) -> None:
    print("smoke " + json.dumps({"phase": phase, **fields}, default=float),
          flush=True)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (or reading the
    persistent cache), from jax.monitoring events.  A jit traced inside
    another reports a span inside its parent's, so the clock measures the
    union of the spans, not their sum."""

    def __init__(self):
        self.spans: list[tuple[float, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event in COMPILE_EVENTS:
            end = time.perf_counter()
            self.spans.append((end - duration, end))

    def seconds(self, t0: float = -math.inf, t1: float = math.inf) -> float:
        """Compile seconds within [t0, t1] on the perf_counter clock."""
        total, reach = 0.0, t0
        for a, b in sorted(self.spans):
            a, b = max(a, reach), min(b, t1)
            if b > a:
                total += b - a
                reach = b
        return total


class Phase:
    """Wall and compile seconds of one phase; steady = wall - compile."""

    def __init__(self, clock: CompileClock):
        self.clock = clock

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self.wall = t1 - self.t0
        self.compile = self.clock.seconds(self.t0, t1)
        return False

    def fields(self) -> dict:
        return {"wall_s": round(self.wall, 3),
                "compile_s": round(self.compile, 3),
                "steady_s": round(self.wall - self.compile, 3)}


def device_bytes() -> list[int]:
    return [int((d.memory_stats() or {}).get("bytes_in_use", -1))
            for d in jax.devices()]


# ---------------------------------------------------------------------------
# corpus, made on the device from the seed
# ---------------------------------------------------------------------------


def zipf_head(corpus: Corpus) -> tuple[jnp.ndarray, int]:
    """uint32 CDF thresholds of Zipf(a) popularity for the ZIPF_HEAD most
    popular ids (id 0 first, as in repro.data.synthetic._zipf_weights), and
    the threshold at which the tail begins."""
    w = 1.0 / np.arange(1, corpus.n_dims + 1, dtype=np.float64) ** corpus.zipf_a
    cdf = np.cumsum(w) / w.sum()
    th = np.minimum(np.floor(cdf * 2.0**32), 2.0**32 - 1).astype(np.uint32)
    return jnp.asarray(th[:ZIPF_HEAD]), int(th[ZIPF_HEAD - 1])


def zipf_ids(u: jnp.ndarray, head: jnp.ndarray, tail_from: int,
             corpus: Corpus) -> jnp.ndarray:
    """Feature ids for uniform uint32 draws `u`, by inverting the Zipf CDF
    without a gather (a table search took ~5 s per 8,192-row batch on a
    TPU v5e).  The head ids come from an exact compare against their
    thresholds; a tail draw inverts the power law x^-a on
    [ZIPF_HEAD + 1, n + 1) in closed form, id i covering [i + 1, i + 2),
    which carries id i's Zipf weight (i + 1)^-a to within a/(2(i + 1))
    < 0.5%."""
    count = jnp.sum((head <= u[..., None]).astype(jnp.int32), axis=-1)
    e = 1.0 - corpus.zipf_a
    lo, hi = (ZIPF_HEAD + 1.0) ** e, (corpus.n_dims + 1.0) ** e
    v = (u - np.uint32(tail_from)).astype(jnp.float32) / (2.0**32 - tail_from)
    x = (lo - v * (lo - hi)) ** (1.0 / e)
    tail = jnp.clip(jnp.floor(x).astype(jnp.int32) - 1, ZIPF_HEAD,
                    corpus.n_dims - 1)
    return jnp.where(count < ZIPF_HEAD, count, tail)


@functools.partial(jax.jit, static_argnames=("rows", "corpus", "tail_from"))
def coo_batch(key, head, *, tail_from: int, rows: int, corpus: Corpus):
    """(indices, values) (rows, width) int32 padded-COO rows.

    Per row: nnz ~ Normal(density, 0.15 density) clipped to [1, width];
    ids are Zipf draws without replacement — the first nnz distinct ids of
    a sequence of with-replacement draws, which is exactly successive
    weighted sampling; categories uniform in [1, n_categories]."""
    k_nnz, k_ids, k_val = jax.random.split(key, 3)
    c = corpus
    nnz = jnp.clip(jnp.round(c.density + 0.15 * c.density
                             * jax.random.normal(k_nnz, (rows,))),
                   1, c.width).astype(jnp.int32)
    u = jax.random.bits(k_ids, (rows, c.draws), jnp.uint32)
    ids = zipf_ids(u, head, tail_from, c)
    pos = jax.lax.broadcasted_iota(jnp.int32, (rows, c.draws), 1)
    by_id, by_id_pos = jax.lax.sort((ids, pos), dimension=1, num_keys=2)
    first = jnp.concatenate([jnp.ones((rows, 1), bool),
                             by_id[:, 1:] != by_id[:, :-1]], axis=1)
    _, first, ids = jax.lax.sort((by_id_pos, first, by_id), dimension=1,
                                 num_keys=1)  # back to draw order
    keep = first & (jnp.cumsum(first, axis=1) <= nnz[:, None])
    _, ids, keep = jax.lax.sort((jnp.where(keep, pos, c.draws), ids, keep),
                                dimension=1, num_keys=1)  # kept ids first
    keep = keep[:, :c.width]
    idx = jnp.where(keep, ids[:, :c.width], 0)
    val = jax.random.randint(k_val, (rows, c.width), 1, c.n_categories + 1)
    return idx, jnp.where(keep, val, 0).astype(jnp.int32)


class CorpusStream:
    """Batches of the corpus, host copies (the engine validates on host)."""

    def __init__(self, corpus: Corpus, seed: int):
        self.corpus = corpus
        self.key = jax.random.PRNGKey(seed)
        self.head, self.tail_from = zipf_head(corpus)
        self.n_batches = 0

    def batch(self, rows: int) -> tuple[np.ndarray, np.ndarray]:
        key = jax.random.fold_in(self.key, self.n_batches)
        self.n_batches += 1
        idx, val = coo_batch(key, self.head, tail_from=self.tail_from,
                             rows=rows, corpus=self.corpus)
        return np.asarray(idx), np.asarray(val)


def perturb(idx: np.ndarray, val: np.ndarray, rng: np.random.Generator
            ) -> tuple[np.ndarray, np.ndarray]:
    """Near-duplicate queries: drop ~10% of each row's entries."""
    return idx, np.where(rng.random(val.shape) < 0.1, 0, val).astype(np.int32)


# ---------------------------------------------------------------------------
# numpy reference: Cabin sketch (psi / pi / pack) and brute-force serving
# ---------------------------------------------------------------------------

_M1, _M2, _M3 = 0x85EBCA6B, 0xC2B2AE35, 0x9E3779B9


def _u32(x) -> np.ndarray:
    return np.asarray(x, np.int64).astype(np.uint32).reshape(-1)


def _mix32(x: np.ndarray) -> np.ndarray:
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(_M1)
    x = x ^ (x >> np.uint32(13))
    x = x * np.uint32(_M2)
    return x ^ (x >> np.uint32(16))


def _hash(x: np.ndarray, seed: int) -> np.ndarray:
    return _mix32(x + _mix32(_u32([seed]) * np.uint32(_M3)))


def np_sketch(params: CabinParams, idx: np.ndarray, val: np.ndarray
              ) -> np.ndarray:
    """Cabin on padded-COO rows -> packed (rows, d/32) int32, LSB-first."""
    rows, width = idx.shape
    attr = _u32(idx)
    cat = _u32(val)
    hx = _hash(attr, params.psi_seed)
    psi = _mix32(hx ^ (cat * np.uint32(_M3) + (hx >> np.uint32(7))))
    on = ((psi & np.uint32(1)) == 1) & (cat != 0)
    bucket = _hash(attr, params.pi_seed) % np.uint32(params.sketch_dim)
    bits = np.zeros((rows, params.sketch_dim), np.uint8)
    row = np.repeat(np.arange(rows), width)
    bits[row[on], bucket[on].astype(np.int64)] = 1
    packed = np.packbits(bits, axis=1, bitorder="little")
    return packed.view("<u4").view(np.int32)


def ref_distances(q: np.ndarray, base: np.ndarray, metric: str, d: int
                  ) -> tuple[np.ndarray, np.ndarray]:
    """(Q, N) distances and their tolerances, brute force over packed rows:
    exact int64 under hamming; the float64 Cham estimator under cham, with
    tolerance CHAM_RTOL times the estimator's operands."""
    b64 = np.ascontiguousarray(base).view(np.uint64)
    wb = np.bitwise_count(b64).sum(axis=1, dtype=np.int64)
    q64 = np.ascontiguousarray(q).view(np.uint64)
    wq = np.bitwise_count(q64).sum(axis=1, dtype=np.int64)
    inner = np.stack([np.bitwise_count(b64 & row).sum(axis=1, dtype=np.int64)
                      for row in q64])
    if metric == "hamming":
        dist = wq[:, None] + wb[None, :] - 2 * inner
        return dist.astype(np.float64), np.zeros(dist.shape)

    def est(w):
        return np.log(np.clip(1.0 - w / d, 1e-9, 1.0)) / np.log1p(-1.0 / d)

    a, b = est(wq)[:, None], est(wb)[None, :]
    u = est(wq[:, None] + wb[None, :] - inner)
    dist = 2.0 * np.maximum(2.0 * u - a - b, 0.0)
    return dist, CHAM_RTOL * 2.0 * (a + b + 2.0 * u)


def check_topk(ids: np.ndarray, dists: np.ndarray, ref: np.ndarray,
               tol: np.ndarray, alive_ids: np.ndarray, k: int) -> int:
    """Queries whose served (ids, dists) disagree with the reference."""
    bad = 0
    for qi in range(ids.shape[0]):
        order = np.lexsort((alive_ids, ref[qi]))[:k]
        pos = np.searchsorted(alive_ids, ids[qi])
        ok = (ids.shape[1] == len(order) and len(set(ids[qi])) == len(order)
              and np.all(pos < len(alive_ids))
              and np.array_equal(alive_ids[np.minimum(pos, len(alive_ids) - 1)],
                                 ids[qi]))
        if ok:
            got = ref[qi, pos]  # the reference distance of each served id
            # a differing id is allowed only across a tie within tolerance
            # (never under hamming, whose tolerance is 0: ties go to the
            # lower id)
            slack = tol[qi, pos] + tol[qi, order]
            tie = (np.abs(got - ref[qi, order]) <= slack) & (slack > 0)
            ok = (np.all(np.abs(dists[qi] - got) <= tol[qi, pos])
                  and np.all((ids[qi] == alive_ids[order]) | tie))
        bad += not ok
    return bad


def check_radius(hits: list, ref: np.ndarray, tol: np.ndarray, r: float,
                 alive_ids: np.ndarray) -> int:
    """Queries whose served hit set differs from {dist < r}, apart from
    rows within tolerance of the boundary."""
    bad = 0
    for qi, got in enumerate(hits):
        want = alive_ids[ref[qi] < r]
        diff = np.setxor1d(np.asarray(got, np.int64), want)
        pos = np.searchsorted(alive_ids, diff)
        edge = np.abs(ref[qi, pos] - r) <= tol[qi, pos]
        bad += not (np.all(pos < len(alive_ids)) and np.all(edge))
    return bad


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def require_kernel(op: str, hlo_text: str) -> None:
    """Fail unless the lowered step calls a compiled Pallas kernel."""
    if "tpu_custom_call" not in hlo_text:
        raise SmokeFailure(f"{op}: no tpu_custom_call in the lowered step — "
                           "it did not run the Pallas kernel")


def kernel_paths(engine: QueryEngine, q_idx: np.ndarray, q_val: np.ndarray,
                 cache_before: dict) -> dict:
    """Which implementation each serving op ran, checked from the lowered
    steps and the kernels' compile caches."""
    params = engine.params
    rows = pow2_bucket(q_idx.shape[0])
    width = pow2_bucket(q_idx.shape[1])
    pad = ((0, rows - q_idx.shape[0]), (0, width - q_idx.shape[1]))
    sketch_hlo = sketch_sparse_jit.lower(
        params, jnp.asarray(np.pad(q_idx, pad)),
        jnp.asarray(np.pad(q_val, pad))).as_text()
    require_kernel("sketch", sketch_hlo)
    w = params.packed_width
    q = jax.ShapeDtypeStruct((rows, w), jnp.int32)
    b = jax.ShapeDtypeStruct((engine.block, w), jnp.int32)
    topk_hlo = jax.jit(lambda q, b: topk_ops.topk_select(
        q, b, K, d=params.sketch_dim, metric=engine.metric,
        bn=engine.block)).lower(q, b).as_text()
    require_kernel("topk", topk_hlo)
    mode = allpairs._auto_mode(engine.mode)
    grown = {name: fn._cache_size() - cache_before[name]
             for name, fn in compiled_steps().items()}
    if mode != "pallas" or min(grown.values()) <= 0:
        raise SmokeFailure(f"serving did not run the kernels: tile mode "
                           f"{mode!r}, new compiled variants {grown}")
    return {"sketch": "sketch_sparse_jit -> pallas cabin_build_sparse",
            "topk": "topk_rows mode=pallas -> pallas topk_select",
            "radius": f"threshold_pairs mode={mode} -> pallas pair_stats",
            "compiled_variants": grown}


def compiled_steps() -> dict:
    """Jitted entry points the serving path calls directly, so their
    compile caches grow exactly when that path runs."""
    return {"sketch_sparse_jit": sketch_sparse_jit,
            "topk_select": topk_kernel.topk_select}


def serve_and_check(engine: QueryEngine, queries, radius_queries,
                    clock: CompileClock, label: str) -> None:
    """64 top-k + 8 radius requests through the front door; check the
    first 32 top-k answers and every radius answer with numpy."""
    d = engine.d
    view = engine.store.gather_alive()
    base = np.asarray(view.matrix[: view.n_alive])
    alive_ids = np.asarray(view.ids)
    qi, qv = queries
    ri, rv = radius_queries
    q_sk = np_sketch(engine.params, qi[:N_CHECKED], qv[:N_CHECKED])
    r_sk = np_sketch(engine.params, ri, rv)
    ref_q, tol_q = ref_distances(q_sk, base, engine.metric, d)
    ref_r, tol_r = ref_distances(r_sk, base, engine.metric, d)
    # a radius that leaves a handful of hits per query
    r = float(np.max(np.sort(ref_r, axis=1)[:, 2]))
    with Phase(clock) as ph:
        with FrontDoor(engine, max_batch_rows=N_TOPK,
                       max_wait_ms=200.0) as door:
            topk_reqs = [door.submit("topk", (qi[i:i + 1], qv[i:i + 1]), k=K)
                         for i in range(N_TOPK)]
            topk_res = [req.result(timeout=900) for req in topk_reqs]
            rad_reqs = [door.submit("radius", (ri[i:i + 1], rv[i:i + 1]),
                                    r=r) for i in range(N_RADIUS)]
            rad_res = [req.result(timeout=900) for req in rad_reqs]
    errors = [res.error for res in topk_res + rad_res if not res.ok]
    if errors:
        raise SmokeFailure(f"{label}: front door answered with errors: "
                           f"{errors[:3]!r}")
    ids = np.concatenate([res.ids for res in topk_res[:N_CHECKED]])
    dists = np.concatenate([res.dists for res in topk_res[:N_CHECKED]])
    bad_topk = check_topk(ids, dists, ref_q, tol_q, alive_ids, K)
    pos = np.minimum(np.searchsorted(alive_ids, ids), len(alive_ids) - 1)
    err = np.abs(dists - np.take_along_axis(ref_q, pos, axis=1))
    bad_radius = check_radius([res.hits[0] for res in rad_res], ref_r,
                              tol_r, r, alive_ids)
    hits = [len(res.hits[0]) for res in rad_res]
    emit(f"serve_{label}", metric=engine.metric, rows_alive=len(alive_ids),
         topk_queries=N_TOPK, topk_checked=N_CHECKED,
         topk_mismatches=bad_topk, topk_max_abs_dist_err=float(err.max()),
         topk_max_rel_dist_err=float(np.max(err / np.maximum(
             np.take_along_axis(ref_q, pos, axis=1), 1.0))),
         radius_queries=N_RADIUS, radius=r,
         radius_hits=hits, radius_mismatches=bad_radius,
         topk_latency_ms_max=round(max(r.latency_ms for r in topk_res), 3),
         radius_latency_ms_max=round(max(r.latency_ms for r in rad_res), 3),
         **ph.fields())
    if bad_topk or bad_radius:
        raise SmokeFailure(f"{label} {engine.metric}: {bad_topk} top-k and "
                           f"{bad_radius} radius answers disagree with numpy")


def build_engines(corpus: Corpus, seed: int, clock: CompileClock,
                  d: int = SKETCH_DIM, batch_rows: int = BATCH_ROWS):
    """Ingest the corpus: `add_sparse` into the first engine, its sketches
    into the second through `add_packed`.  Returns the engines, the stream,
    a sample of (id, idx, val) rows and the query sources."""
    params = CabinParams.create(corpus.n_dims, d, seed=seed)
    engines = [QueryEngine(params, metric=m, keep_raw=False)
               for m in METRICS]
    stream = CorpusStream(corpus, seed)
    rng = np.random.default_rng(seed)
    sample_ids = np.sort(rng.choice(corpus.rows, N_SAMPLE, replace=False))
    source_ids = rng.choice(corpus.rows, N_TOPK + N_RADIUS, replace=False)
    keep = np.union1d(sample_ids, source_ids)
    kept = {}
    split = {"make_s": 0.0, "add_sparse_s": 0.0, "add_packed_s": 0.0}
    with Phase(clock) as ph:
        for start in range(0, corpus.rows, batch_rows):
            rows = min(batch_rows, corpus.rows - start)
            t0 = time.perf_counter()
            idx, val = stream.batch(rows)
            t1 = time.perf_counter()
            ids = engines[0].add_sparse(idx, val)
            t2 = time.perf_counter()
            packed = engines[0].store.sk_buf[start: start + rows]
            engines[1].add_packed(packed)
            t3 = time.perf_counter()
            split["make_s"] += t1 - t0
            split["add_sparse_s"] += t2 - t1
            split["add_packed_s"] += t3 - t2
            for i in keep[(keep >= start) & (keep < start + rows)]:
                kept[int(i)] = (idx[i - start], val[i - start])
            if ids[0] != start:
                raise SmokeFailure(f"ids start at {ids[0]}, not {start}")
        jax.block_until_ready(engines[1].store.sk_buf)
    emit("ingest", rows=corpus.rows, batch_rows=batch_rows, sketch_dim=d,
         n_dims=corpus.n_dims, coo_width=corpus.width,
         rows_per_s=round(corpus.rows / ph.wall, 1),
         device_bytes_in_use=device_bytes(),
         **{k: round(v, 3) for k, v in split.items()}, **ph.fields())
    samples = (sample_ids, np.stack([kept[int(i)][0] for i in sample_ids]),
               np.stack([kept[int(i)][1] for i in sample_ids]))
    sources = (np.stack([kept[int(i)][0] for i in source_ids]),
               np.stack([kept[int(i)][1] for i in source_ids]))
    return engines, stream, samples, sources, source_ids


def check_sketches(engine: QueryEngine, samples, label: str) -> None:
    """Stored sketches of sampled rows, read by id from the rows the engine
    serves, against the numpy Cabin of their COO."""
    ids, idx, val = samples
    view = engine.store.gather_alive()
    alive_ids = np.asarray(view.ids)
    pos = np.minimum(np.searchsorted(alive_ids, ids), len(alive_ids) - 1)
    missing = int(np.count_nonzero(alive_ids[pos] != ids))
    stored = np.asarray(view.matrix[jnp.asarray(pos)])
    want = np_sketch(engine.params, idx, val)
    bad = int(np.count_nonzero(np.any(stored != want, axis=1)))
    emit(f"sketch_check_{label}", metric=engine.metric, rows_checked=len(ids),
         mismatches=bad, missing_ids=missing,
         ones_per_row=float(np.bitwise_count(want.view(np.uint32))
                            .sum(axis=1).mean()))
    if bad or missing:
        raise SmokeFailure(f"{label}: {bad} of {len(ids)} stored sketches "
                           f"differ from the numpy reference, {missing} ids "
                           "missing")


def mutate(engines, stream: CorpusStream, source_ids: np.ndarray,
           samples, seed: int, clock: CompileClock):
    """Remove 1% of the ids (a few query sources among them), add 4,096
    fresh rows, compact — the same history on every engine.  Checks the
    membership against the ids this history must leave, and returns the
    samples that survived plus a sample of the added rows."""
    rng = np.random.default_rng(seed + 1)
    before = engines[0].ids()
    n_remove = int(len(before) * REMOVE_FRACTION)
    gone = np.union1d(rng.choice(before, n_remove, replace=False),
                      source_ids[::16])
    with Phase(clock) as ph:
        for eng in engines:
            eng.remove(gone)
        idx, val = stream.batch(N_ADDED)
        start = engines[0].store.size
        added = engines[0].add_sparse(idx, val)
        engines[1].add_packed(engines[0].store.sk_buf[start: start + N_ADDED])
        for eng in engines:
            eng.compact()
        jax.block_until_ready([e.store.sk_buf for e in engines])
    want = np.union1d(np.setdiff1d(before, gone),
                      np.arange(before[-1] + 1, before[-1] + 1 + N_ADDED))
    wrong = [e.metric for e in engines if not np.array_equal(e.ids(), want)]
    emit("mutate", removed=len(gone), added=N_ADDED,
         rows_alive=len(engines[0]), rows_expected=len(want),
         membership_mismatches=len(wrong),
         device_bytes_in_use=device_bytes(), **ph.fields())
    if wrong or not np.array_equal(added, want[-N_ADDED:]):
        raise SmokeFailure(f"membership after mutations differs from the "
                           f"history's under {wrong or 'the add ids'}")
    ids, s_idx, s_val = samples
    live = ~np.isin(ids, gone)
    pick = np.sort(rng.choice(N_ADDED, N_SAMPLE // 4, replace=False))
    return (np.concatenate([ids[live], added[pick]]),
            np.concatenate([s_idx[live], idx[pick]]),
            np.concatenate([s_val[live], val[pick]]))


def query_rows(sources, rng: np.random.Generator):
    idx, val = perturb(*sources, rng)
    return (idx[:N_TOPK], val[:N_TOPK]), (idx[N_TOPK:], val[N_TOPK:])


def run_single(seed: int, corpus: Corpus = Corpus(), d: int = SKETCH_DIM,
               batch_rows: int = BATCH_ROWS) -> dict:
    clock = CompileClock()
    caches = {name: fn._cache_size() for name, fn in compiled_steps().items()}
    engines, stream, samples, sources, source_ids = build_engines(
        corpus, seed, clock, d, batch_rows)
    check_sketches(engines[0], samples, "initial")
    queries, radius_queries = query_rows(sources, np.random.default_rng(seed))
    for eng in engines:
        serve_and_check(eng, queries, radius_queries, clock, "initial")
    samples = mutate(engines, stream, source_ids, samples, seed, clock)
    for eng in engines:
        check_sketches(eng, samples, "mutated")
        serve_and_check(eng, queries, radius_queries, clock, "mutated")
    paths = kernel_paths(engines[0], *queries, caches)
    emit("kernels", **paths)
    emit("totals", compile_s=round(clock.seconds(), 3),
         device_bytes_in_use=device_bytes(),
         peak_bytes=[int((dv.memory_stats() or {}).get("peak_bytes_in_use",
                                                       -1))
                     for dv in jax.devices()])
    return paths


def run_sharded(seed: int, n_chips: int, corpus: Corpus = Corpus(),
                d: int = SKETCH_DIM, batch_rows: int = BATCH_ROWS) -> None:
    """The same corpus, served unsharded and then after engine.shard() over
    an n_chips mesh: answers must agree (hamming exactly; cham within the
    estimator tolerance, as for the numpy check)."""
    clock = CompileClock()
    devices = jax.devices()[:n_chips]
    engines, _, _, sources, _ = build_engines(corpus, seed, clock, d,
                                              batch_rows)
    queries, radius_queries = query_rows(sources, np.random.default_rng(seed))
    mesh = jax.sharding.Mesh(np.array(devices), ("shard",))
    for eng in engines:
        serve_and_check(eng, queries, radius_queries, clock, "unsharded")
        before = eng.topk(queries, K)
        eng.shard(mesh)
        serve_and_check(eng, queries, radius_queries, clock, "sharded")
        after = eng.topk(queries, K)
        same_ids = int(np.count_nonzero(np.all(before[0] == after[0], axis=1)))
        max_diff = float(np.max(np.abs(before[1] - after[1])))
        emit("shard_compare", metric=eng.metric, shards=n_chips,
             queries=N_TOPK, identical_id_rows=same_ids,
             max_abs_dist_diff=max_diff,
             device_bytes_in_use=device_bytes())
        if eng.metric == "hamming" and (same_ids != N_TOPK or max_diff):
            raise SmokeFailure("sharded hamming answers differ from the "
                               "unsharded engine's")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    cache_dir = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}
    emit("device", compile_cache=cache_dir, **info)
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU: JAX's first device is {dev.platform} "
              f"({dev.device_kind})", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devices)} "
              "devices", file=sys.stderr)
        return 1
    try:
        if args.chips == 1:
            run_single(args.seed)
        else:
            run_sharded(args.seed, args.chips)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
