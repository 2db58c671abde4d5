"""Corpus twins of the UCI Bag-of-Words collections, made on the device.

A configuration file gives the shape of its source: documents, vocabulary,
categories, the mean number of distinct words per document (the `docword`
header's NNZ over its document count) and the largest (the paper's Table 1
sparsity s, which is also the padded COO width).  Every row is drawn from
the seed alone, batch by batch, so the reference can draw the same rows
again after the measured window.

Per row: nnz ~ round(lognormal), clipped to [1, nnz_max], with the
lognormal's location solved so that the clipped mean equals the source's
mean (`assumed` in the configuration names the shape); word ids are Zipf
draws without replacement (the first nnz distinct ids of a sequence of
with-replacement draws, which is successive weighted sampling) — exact over
the ZIPF_HEAD most popular ids, a closed-form power law beyond; categories
are uniform in [1, n_categories].

The generator is copied from the repository's chip smoke and reshaped to
the mean/max of each source.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import numpy as np
import jax
import jax.numpy as jnp

ZIPF_HEAD = 128  # ids drawn by exact table compare; the rest in closed form


@dataclass(frozen=True)
class Corpus:
    """Shape of one corpus twin (hashable: a static jit argument)."""

    n_dims: int
    n_categories: int
    nnz_mean: float
    nnz_max: int
    nnz_sigma: float
    zipf_a: float
    draws: int  # Zipf draws per row; must give > nnz_max distinct ids

    @property
    def width(self) -> int:
        """Padded COO width: the largest row."""
        return self.nnz_max

    @functools.cached_property
    def nnz_mu(self) -> float:
        """Location of the lognormal whose rounded, clipped mean is nnz_mean."""
        z = _normal_quantiles()

        def mean(mu):
            x = np.clip(np.round(np.exp(mu + self.nnz_sigma * z)), 1,
                        self.nnz_max)
            return float(x.mean())

        lo, hi = 0.0, math.log(self.nnz_max) + 1.0
        for _ in range(60):
            mid = 0.5 * (lo + hi)
            lo, hi = (mid, hi) if mean(mid) < self.nnz_mean else (lo, mid)
        return 0.5 * (lo + hi)


@functools.cache
def _normal_quantiles(n: int = 200_001) -> np.ndarray:
    from statistics import NormalDist
    nd = NormalDist()
    return np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])


def corpus_of(config: dict) -> Corpus:
    c = config["corpus"]
    return Corpus(n_dims=int(c["n_dims"]), n_categories=int(c["n_categories"]),
                  nnz_mean=float(c["nnz_mean"]), nnz_max=int(c["nnz_max"]),
                  nnz_sigma=float(c["nnz_sigma"]), zipf_a=float(c["zipf_a"]),
                  draws=int(c["zipf_draws"]))


def root_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, including ones past 32 bits."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


# streams under one seed: disjoint keys, so queries never repeat corpus rows
CORPUS_STREAM, QUERY_STREAM, POOL_STREAM = 0, 1, 2


def stream_key(seed: int, stream: int) -> jax.Array:
    return jax.random.fold_in(root_key(seed), stream)


def zipf_head(corpus: Corpus) -> tuple[np.ndarray, int]:
    """uint32 CDF thresholds of Zipf(a) popularity for the ZIPF_HEAD most
    popular ids (id 0 first), and the threshold at which the tail begins."""
    w = 1.0 / np.arange(1, corpus.n_dims + 1, dtype=np.float64) ** corpus.zipf_a
    cdf = np.cumsum(w) / w.sum()
    th = np.minimum(np.floor(cdf * 2.0**32), 2.0**32 - 1).astype(np.uint32)
    return th[:ZIPF_HEAD], int(th[ZIPF_HEAD - 1])


def zipf_ids(u, head, tail_from: int, corpus: Corpus):
    """Feature ids for uniform uint32 draws `u`, by inverting the Zipf CDF
    without a gather.  Head ids come from an exact compare against their
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


@functools.partial(jax.jit, static_argnames=("rows", "corpus", "tail_from",
                                             "mu"))
def coo_batch(key, head, *, tail_from: int, rows: int, corpus: Corpus,
              mu: float):
    """(indices, values), each (rows, corpus.width) int32 padded COO;
    padding slots hold index 0 and category 0."""
    k_nnz, k_ids, k_val = jax.random.split(key, 3)
    c = corpus
    z = jax.random.normal(k_nnz, (rows,))
    nnz = jnp.clip(jnp.round(jnp.exp(mu + c.nnz_sigma * z)), 1,
                   c.width).astype(jnp.int32)
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


class Stream:
    """Batches of rows from one keyed stream, on the device.  Batch b is a
    function of (seed, stream, b, rows) alone."""

    def __init__(self, corpus: Corpus, seed: int, stream: int):
        self.corpus = corpus
        self.key = stream_key(seed, stream)
        head, self.tail_from = zipf_head(corpus)
        self.head = jnp.asarray(head)

    def batch(self, b: int, rows: int):
        return coo_batch(jax.random.fold_in(self.key, b), self.head,
                         tail_from=self.tail_from, rows=rows,
                         corpus=self.corpus, mu=self.corpus.nnz_mu)


def drop_entries(idx: np.ndarray, val: np.ndarray, frac: float,
                 rng: np.random.Generator) -> tuple[np.ndarray, np.ndarray]:
    """Near-duplicate of rows: each entry dropped (category set to 0, which
    is padding) with probability `frac`."""
    drop = rng.random(val.shape) < frac
    return idx, np.where(drop, 0, val).astype(np.int32)
