"""The plain reference the benchmark's `correct` is decided against.

It imports nothing of the program and takes nothing the program made: it
re-draws the corpus from the seed (bench/corpus.py), sketches every row
itself — on the host in numpy (`np_sketch`, copied from the repository's
chip smoke) or, for whole corpora, on the device in plain jnp (`ref_bits`:
a scatter into a (rows, d) bit matrix, no kernel) — and brute-forces the
answers over all alive rows in float64.

The brute force streams the corpus in the set-up's own batches.  Per batch
it takes each sampled query's exact integer statistics (the row's sketch
weight and its inner product with the query, an exact 0/1 matmul) on the
device, keeps the CANDIDATES best rows by a float32 distance built from a
float64 table, and finishes on the host in float64.  A batch whose last
kept candidate is not clearly past the final answer's boundary makes the
reference inconclusive (an error, never a pass).
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

# Relative slack of the float32 estimator against the float64 one, as the
# smoke set it: served cham distances are float32 (the configuration's
# precision), compared with float64.
CHAM_RTOL = 1e-5
CANDIDATES = 128  # rows kept per query and batch before the float64 pass
# Radius answers have no size bound: a short near-duplicate query finds up
# to a few thousand rows of the whole corpus, some hundreds per batch.
RADIUS_CANDIDATES = 2048
_SELECT_SLACK = 1e-4  # float32 table distance vs float64, relative
_IN_FLIGHT = 2  # batches dispatched ahead of the host's fetch

_M1, _M2, _M3 = 0x85EBCA6B, 0xC2B2AE35, 0x9E3779B9


class ReferenceInconclusive(RuntimeError):
    """The candidate pre-selection could not certify the reference."""


# ---------------------------------------------------------------------------
# Cabin, in numpy (host) — copied from the chip smoke
# ---------------------------------------------------------------------------


def _u32(x) -> np.ndarray:
    return np.asarray(x, np.int64).astype(np.uint32).reshape(-1)


def _mix32(x: np.ndarray, mask: int = 0xFFFFFFFF) -> np.ndarray:
    m = np.uint32(mask)
    x = x ^ (x >> np.uint32(16))
    x = (x * np.uint32(_M1)) & m
    x = x ^ (x >> np.uint32(13))
    x = (x * np.uint32(_M2)) & m
    return x ^ (x >> np.uint32(16))


def _hash(x: np.ndarray, seed: int, mask: int = 0xFFFFFFFF) -> np.ndarray:
    return _mix32(x + _mix32(_u32([seed]) * np.uint32(_M3), mask), mask)


def np_sketch(d: int, psi_seed: int, pi_seed: int, idx: np.ndarray,
              val: np.ndarray, hash_bits: int = 32) -> np.ndarray:
    """Cabin on padded-COO rows -> packed (rows, d/32) int32, LSB-first.

    `hash_bits` below 32 keeps only that many bits of every product in the
    attribute mixer: the cheaper-hash control, never the reference."""
    mask = (1 << hash_bits) - 1
    rows, width = idx.shape
    attr = _u32(idx)
    cat = _u32(val)
    hx = _hash(attr, psi_seed)
    psi = _mix32(hx ^ (cat * np.uint32(_M3) + (hx >> np.uint32(7))))
    on = ((psi & np.uint32(1)) == 1) & (cat != 0)
    bucket = _hash(attr, pi_seed, mask) % np.uint32(d)
    bits = np.zeros((rows, d), np.uint8)
    row = np.repeat(np.arange(rows), width)
    bits[row[on], bucket[on].astype(np.int64)] = 1
    packed = np.packbits(bits, axis=1, bitorder="little")
    return packed.view("<u4").view(np.int32)


def np_unpack(packed: np.ndarray, d: int) -> np.ndarray:
    """(rows, d/32) int32 LSB-first -> (rows, d) uint8 bits."""
    b = np.ascontiguousarray(packed).view(np.uint8)
    return np.unpackbits(b, axis=1, bitorder="little")[:, :d]


# ---------------------------------------------------------------------------
# Cabin, in plain jnp (device) — for whole corpora
# ---------------------------------------------------------------------------


def _jmix32(x):
    x = x ^ (x >> 16)
    x = x * jnp.uint32(_M1)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(_M2)
    return x ^ (x >> 16)


def _jhash(x, seed: int):
    s = _jmix32(jnp.uint32(seed & 0xFFFFFFFF) * jnp.uint32(_M3))
    return _jmix32(x + s)


@functools.partial(jax.jit, static_argnames=("d", "psi_seed", "pi_seed"))
def ref_bits(idx, val, *, d: int, psi_seed: int, pi_seed: int):
    """Cabin bits of padded-COO rows as a (rows, d) int8 0/1 matrix."""
    rows, width = idx.shape
    attr = idx.astype(jnp.uint32)
    cat = val.astype(jnp.uint32)
    hx = _jhash(attr, psi_seed)
    psi = _jmix32(hx ^ (cat * jnp.uint32(_M3) + (hx >> 7)))
    on = ((psi & 1) == 1) & (cat != 0)
    bucket = (_jhash(attr, pi_seed) % jnp.uint32(d)).astype(jnp.int32)
    flat = jnp.arange(rows, dtype=jnp.int32)[:, None] * d + bucket
    flat = jnp.where(on, flat, rows * d)  # off entries fall outside: dropped
    bits = jnp.zeros((rows * d,), jnp.int8).at[flat.reshape(-1)].set(
        1, mode="drop")
    return bits.reshape(rows, d)


@jax.jit
def pack_bits(bits):
    """(rows, d) 0/1 -> (rows, d/32) int32, LSB-first."""
    rows, d = bits.shape
    b = bits.reshape(rows, d // 32, 32).astype(jnp.uint32)
    words = jnp.sum(b << jnp.arange(32, dtype=jnp.uint32), axis=-1,
                    dtype=jnp.uint32)
    return jax.lax.bitcast_convert_type(words, jnp.int32)


# ---------------------------------------------------------------------------
# distances
# ---------------------------------------------------------------------------


def cham_table(d: int) -> np.ndarray:
    """Cham's density estimate of every sketch weight 0..2d, float64."""
    w = np.arange(2 * d + 1, dtype=np.float64)
    return np.log(np.clip(1.0 - w / d, 1e-9, 1.0)) / np.log1p(-1.0 / d)


def distances64(wq, wb, inner, metric: str, d: int):
    """Reference distances and their tolerances from exact integer stats:
    exact under hamming; the float64 Cham estimator under cham, with
    tolerance CHAM_RTOL times the estimator's operands."""
    wq = np.asarray(wq, np.int64)
    wb = np.asarray(wb, np.int64)
    inner = np.asarray(inner, np.int64)
    if metric == "hamming":
        dist = (wq + wb - 2 * inner).astype(np.float64)
        return dist, np.zeros(dist.shape)
    t = cham_table(d)
    a, b, u = t[wq], t[wb], t[np.minimum(wq + wb - inner, 2 * d)]
    dist = 2.0 * np.maximum(2.0 * u - a - b, 0.0)
    return dist, CHAM_RTOL * 2.0 * (a + b + 2.0 * u)


@functools.partial(jax.jit, static_argnames=("metric", "c"))
def _batch_candidates(bits, qbits, wq, table, n_valid, *, metric: str,
                      c: int):
    """Per query, the c rows of this batch nearest by a float32 distance
    from exact integer stats: (rows (Q, c), wb (Q, c), inner (Q, c),
    dist32 (Q, c))."""
    wb = jnp.sum(bits.astype(jnp.int32), axis=1)  # (R,)
    inner = jnp.dot(qbits.astype(jnp.bfloat16), bits.T.astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32).astype(jnp.int32)
    if metric == "hamming":
        dist = (wq[:, None] + wb[None, :] - 2 * inner).astype(jnp.float32)
    else:
        u = table[wq[:, None] + wb[None, :] - inner]
        dist = 2.0 * jnp.maximum(2.0 * u - table[wq][:, None]
                                 - table[wb][None, :], 0.0)
    col = jnp.arange(bits.shape[0])[None, :]
    dist = jnp.where(col < n_valid, dist, jnp.inf)
    neg, rows = jax.lax.top_k(-dist, c)
    return rows, wb[rows], jnp.take_along_axis(inner, rows, axis=1), -neg


class BruteForce:
    """Streams corpus batches; keeps each query's candidate rows."""

    def __init__(self, qbits: np.ndarray, metric: str, d: int,
                 keep_all: bool = False, candidates: int = CANDIDATES):
        """Keeps `candidates` rows per query and batch, or with `keep_all`
        every row's statistics (no pre-selection)."""
        self.keep_all = keep_all
        self.candidates = candidates
        self.metric = metric
        self.d = d
        self.q = qbits.shape[0]
        self.qbits = jnp.asarray(qbits, jnp.int8)
        self.wq_host = qbits.sum(axis=1).astype(np.int64)
        self.wq = jnp.asarray(self.wq_host, jnp.int32)
        self.table = jnp.asarray(cham_table(d), jnp.float32)
        self.parts: list[tuple] = []  # (ids, wb, inner, dist32, last)
        self._pending: list[tuple] = []

    def add_batch(self, bits, ids: np.ndarray) -> None:
        """`bits` (R, d) reference bits on the device, of which the first
        len(ids) rows are real, with external ids `ids`."""
        n = len(ids)
        c = n if self.keep_all else min(self.candidates, bits.shape[0])
        self._pending.append((ids, n, c, _batch_candidates(
            bits, self.qbits, self.wq, self.table, jnp.int32(n),
            metric=self.metric, c=c)))
        while len(self._pending) > _IN_FLIGHT:
            self._collect()

    def _collect(self) -> None:
        """Fetch the oldest dispatched batch: the device keeps at most
        _IN_FLIGHT batches ahead of the host."""
        ids, n, c, out = self._pending.pop(0)
        rows, wb, inner, d32 = jax.device_get(out)
        rows = np.minimum(rows, max(n - 1, 0))  # past n: dist32 is inf
        last = d32[:, -1] if c <= n else np.full(self.q, np.inf)
        self.parts.append((ids[rows], wb, inner, d32, last))

    def _merged(self):
        while self._pending:
            self._collect()
        ids = np.concatenate([p[0] for p in self.parts], axis=1)
        wb = np.concatenate([p[1] for p in self.parts], axis=1)
        inner = np.concatenate([p[2] for p in self.parts], axis=1)
        d32 = np.concatenate([p[3] for p in self.parts], axis=1)
        last = np.stack([p[4] for p in self.parts], axis=1)  # (Q, batches)
        dist, tol = distances64(self.wq_host[:, None], wb, inner,
                                self.metric, self.d)
        dist = np.where(np.isfinite(d32), dist, np.inf)
        return ids, dist, tol, last, wb, inner

    def _certify(self, bound: np.ndarray, last: np.ndarray) -> None:
        """Every batch's last kept candidate must lie clearly past
        `bound` (per query); otherwise rows past it might belong."""
        slack = _SELECT_SLACK * np.abs(bound) + 1e-3
        if np.any(last <= (bound + slack)[:, None]):
            raise ReferenceInconclusive(
                "a batch kept too few candidates to certify the answer")

    def topk(self, k: int):
        """(ids (Q, k), dist (Q, k), tol (Q, k)) float64, ascending by
        (distance, id), plus per-query dicts id -> (dist, tol) of every
        candidate (to look up served ids)."""
        ids, dist, tol, last, _, _ = self._merged()
        order = np.lexsort((ids, dist), axis=1)[:, :k]
        top_ids = np.take_along_axis(ids, order, axis=1)
        top_d = np.take_along_axis(dist, order, axis=1)
        top_t = np.take_along_axis(tol, order, axis=1)
        self._certify(top_d[:, -1] + top_t[:, -1], last)
        lookup = []
        for i in range(self.q):
            real = np.isfinite(dist[i])
            lookup.append(dict(zip(ids[i][real].tolist(),
                                   zip(dist[i][real].tolist(),
                                       tol[i][real].tolist()))))
        return top_ids, top_d, top_t, lookup

    def radius(self, r: float):
        """Per query: (ids with dist < r, ids within tolerance of r)."""
        ids, dist, tol, last, _, _ = self._merged()
        self._certify(np.full(self.q, r) + tol.max(initial=0.0), last)
        hits, edge = [], []
        for i in range(self.q):
            ok = np.isfinite(dist[i])
            hits.append(np.unique(ids[i][ok & (dist[i] < r)]))
            edge.append(np.unique(ids[i][ok & (np.abs(dist[i] - r)
                                               <= tol[i])]))
        return hits, edge

    def lower_precision_topk(self, k: int, dtype) -> tuple:
        """The control: the reference's top-k with the estimator computed in
        `dtype` (the precision below the configuration's float32)."""
        ids, _, _, _, wb, inner = self._merged()
        t = cham_table(self.d).astype(dtype)
        wq = self.wq_host[:, None]
        if self.metric == "hamming":
            dist = (wq + wb - 2 * inner).astype(dtype)
        else:
            two = np.asarray(2.0, dtype)
            u = t[np.minimum(wq + wb - inner, 2 * self.d)]
            dist = two * np.maximum(two * u - t[wq] - t[wb],
                                    np.asarray(0.0, dtype))
        dist = dist.astype(np.float64)
        order = np.lexsort((ids, dist), axis=1)[:, :k]
        return (np.take_along_axis(ids, order, axis=1),
                np.take_along_axis(dist, order, axis=1))

    def lower_precision_radius(self, r: float, dtype) -> list:
        ids, _, _, _, wb, inner = self._merged()
        t = cham_table(self.d).astype(dtype)
        wq = self.wq_host[:, None]
        u = t[np.minimum(wq + wb - inner, 2 * self.d)]
        two = np.asarray(2.0, dtype)
        dist = (two * np.maximum(two * u - t[wq] - t[wb],
                                 np.asarray(0.0, dtype))).astype(np.float64)
        return [np.unique(ids[i][dist[i] < r]) for i in range(self.q)]


# ---------------------------------------------------------------------------
# comparisons
# ---------------------------------------------------------------------------


def compare_topk(served_ids: np.ndarray, served_d: np.ndarray,
                 ref_ids: np.ndarray, ref_d: np.ndarray, ref_t: np.ndarray,
                 lookup: list) -> tuple[int, float]:
    """(answers whose ids disagree, largest relative distance error).

    A served id may differ from the reference's at the same rank only
    across a tie within tolerance (never under hamming, whose tolerance is
    0: ties go to the lower id).  The distance error of a served id is
    measured against the reference distance of THAT id, relative to
    max(reference distance, 1)."""
    wrong, err = 0, 0.0
    for i in range(served_ids.shape[0]):
        got_ids, got_d = served_ids[i], served_d[i]
        ok = (len(got_ids) == ref_ids.shape[1]
              and len(set(got_ids.tolist())) == len(got_ids)
              and all(int(g) in lookup[i] for g in got_ids))
        if ok:
            rd = np.array([lookup[i][int(g)][0] for g in got_ids])
            rt = np.array([lookup[i][int(g)][1] for g in got_ids])
            slack = rt + ref_t[i]
            tie = (np.abs(rd - ref_d[i]) <= slack) & (slack > 0)
            ok = bool(np.all((got_ids == ref_ids[i]) | tie))
            e = np.abs(got_d.astype(np.float64) - rd) / np.maximum(rd, 1.0)
            err = max(err, float(e.max(initial=0.0)))
        else:
            err = max(err, float("inf"))
        wrong += not ok
    return wrong, err


def compare_radius(served: list, ref_hits: list, ref_edge: list) -> int:
    """Answers whose hit set differs from the reference's, apart from rows
    within tolerance of the boundary."""
    wrong = 0
    for got, want, edge in zip(served, ref_hits, ref_edge):
        diff = np.setxor1d(np.asarray(got, np.int64), want)
        wrong += not np.all(np.isin(diff, edge))
    return wrong
