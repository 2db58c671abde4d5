"""System-behaviour tests for Cabin + Cham: the paper's Lemmas 1, 2, 4 and
Theorem 2, plus estimator internals, on controlled synthetic data."""

import numpy as np
import jax.numpy as jnp
import pytest
from _hyp import given, settings, st  # hypothesis when installed, fallback otherwise

from repro.core import CabinParams, packing
from repro.core.cabin import binem, binsketch, sketch_dense, sketch_sparse
from repro.core.cham import (
    binhamming_from_stats,
    cham,
    cham_matrix,
    density_estimate,
    inner_estimate,
    log_f32,
)
from repro.core.theory import sketch_dim, theorem2_bound


def make_categorical(rng, n_rows, n, c, density):
    x = np.zeros((n_rows, n), dtype=np.int32)
    for i in range(n_rows):
        idx = rng.choice(n, size=density, replace=False)
        x[i, idx] = rng.integers(1, c + 1, size=density)
    return x


# ---------------------------------------------------------------------------
# Lemma 1: BinEm density a' satisfies a' <= a, E[a'] = a/2, concentrated.
# ---------------------------------------------------------------------------


def test_lemma1_binem_density():
    rng = np.random.default_rng(0)
    n, c, density, trials = 2000, 20, 200, 64
    x = make_categorical(rng, 1, n, c, density)
    densities = []
    for seed in range(trials):
        p = CabinParams.create(n, 512, seed=seed)
        u1 = np.asarray(binem(p, jnp.asarray(x[0])))
        a_prime = int(u1.sum())
        assert a_prime <= density  # claim (a)
        densities.append(a_prime)
    mean = np.mean(densities)
    # claim (b): E[a'] = a/2; 64 trials of Binomial(200, .5) -> se ~ 0.9
    assert abs(mean - density / 2) < 5.0
    # claim (c): concentration — all samples within 5 sigma
    assert np.max(np.abs(np.asarray(densities) - density / 2)) < 5 * np.sqrt(density / 4) + 1


# ---------------------------------------------------------------------------
# Lemma 2: HD(u, v) = 2 E[HD(u', v')].
# ---------------------------------------------------------------------------


def test_lemma2_binem_preserves_hamming():
    rng = np.random.default_rng(1)
    n, c, density = 2000, 20, 250
    x = make_categorical(rng, 2, n, c, density)
    hd = int((x[0] != x[1]).sum())
    ests = []
    for seed in range(64):
        p = CabinParams.create(n, 512, seed=seed)
        u1 = np.asarray(binem(p, jnp.asarray(x)))
        ests.append(2 * int((u1[0] != u1[1]).sum()))
    mean = np.mean(ests)
    # var of one estimate = 4 * hd/4 = hd; se of mean over 64 trials
    se = np.sqrt(hd / 64)
    assert abs(mean - hd) < 6 * se + 2


# ---------------------------------------------------------------------------
# Lemma 4: sketch retains (improves) sparsity: E[ones(Cabin(u))] <= T/2.
# ---------------------------------------------------------------------------


def test_lemma4_sketch_sparsity():
    rng = np.random.default_rng(2)
    n, c, density = 3000, 30, 400
    x = make_categorical(rng, 1, n, c, density)
    d = sketch_dim(density, 0.1)
    ones = []
    for seed in range(32):
        p = CabinParams.create(n, d, seed=seed)
        sk = sketch_dense(p, jnp.asarray(x[0]))
        ones.append(int(packing.popcount_rows(sk)))
    # mean within sampling noise of <= T/2 (se of Binomial(400,.5)/sqrt 32 ~ 1.8)
    assert np.mean(ones) <= density / 2 + 6.0


# ---------------------------------------------------------------------------
# Theorem 2: |Cham - HD| <= 11 sqrt(s ln(7/delta)) w.p. >= 1 - delta.
# ---------------------------------------------------------------------------


def test_theorem2_error_bound():
    rng = np.random.default_rng(3)
    n, c, density, rows = 4000, 25, 300, 48
    delta = 0.1
    x = make_categorical(rng, rows, n, c, density)
    d = sketch_dim(density, delta)
    p = CabinParams.create(n, d, seed=11)
    sk = sketch_dense(p, jnp.asarray(x))
    hd = (x[:, None, :] != x[None, :, :]).sum(-1)
    est = np.asarray(cham_matrix(sk, sk, d))
    iu = np.triu_indices(rows, 1)
    errors = np.abs(est - hd)[iu]
    bound = theorem2_bound(density, delta)
    frac_within = float((errors <= bound).mean())
    assert frac_within >= 1 - delta  # empirically ~1.0 (bound is loose)
    # and the estimator is far better than the bound in practice:
    assert errors.mean() < bound / 3


def test_cham_identical_vectors_is_zero():
    rng = np.random.default_rng(4)
    x = make_categorical(rng, 1, 1000, 10, 100)
    p = CabinParams.create(1000, 512, seed=0)
    sk = sketch_dense(p, jnp.asarray(x[0]))
    assert float(cham(sk, sk, 512)) == pytest.approx(0.0, abs=1e-3)


# ---------------------------------------------------------------------------
# Estimator internals
# ---------------------------------------------------------------------------


def test_log_f32_is_accurate_to_an_ulp():
    """The estimator's TPU log: within about an ulp of float64 log over the
    clamped range (1e-9, 1], exactly 0 at 1."""
    rng = np.random.default_rng(0)
    y = np.concatenate([rng.uniform(1e-9, 1.0, 50_000),
                        rng.uniform(0.8, 1.0, 50_000),
                        1.0 - np.arange(1, 4097) * 2.0**-24,
                        [1.0, 1e-9, 0.5, np.sqrt(0.5), np.sqrt(2.0) / 2]]
                       ).astype(np.float32)
    got = np.asarray(log_f32(jnp.asarray(y))).astype(np.float64)
    ref = np.log(y.astype(np.float64))
    ulp = np.spacing(np.abs(ref).astype(np.float32)).astype(np.float64)
    assert np.all(np.abs(got - ref) <= ulp)
    assert got[y == 1.0].tolist() == [0.0] * int(np.sum(y == 1.0))


@given(st.integers(16, 4096), st.integers(0, 2**31 - 1))
@settings(max_examples=15, deadline=None)
def test_density_estimate_inverts_expectation(d, seed):
    # For a known pre-sketch density a << d, sketch weight w concentrates at
    # d(1 - (1-1/d)^a) and density_estimate(w) recovers ~a.
    rng = np.random.default_rng(seed)
    a = max(1, d // 8)
    buckets = rng.integers(0, d, size=a)
    w = len(np.unique(buckets))
    a_hat = float(density_estimate(jnp.asarray(w), d))
    assert abs(a_hat - a) < 6 * np.sqrt(a) + 2


def test_binhamming_from_stats_matches_expectation_regime():
    # Closed-form check: if sketches don't collide (w == density), the
    # estimator reduces to (approximately) the raw Hamming distance.
    d = 1 << 14
    wu = wv = jnp.asarray(64.0)
    inner = jnp.asarray(32.0)
    est = float(binhamming_from_stats(wu, wv, inner, d))
    assert est == pytest.approx(64.0, rel=0.02)  # |u|+|v|-2<uv> = 64


def test_inner_estimate_accuracy():
    rng = np.random.default_rng(5)
    n, density = 3000, 200
    bits = np.zeros((2, n), np.int32)
    common = rng.choice(n, size=density // 2, replace=False)
    bits[:, common] = 1
    for r in range(2):
        extra = rng.choice(n, size=density // 2, replace=False)
        bits[r, extra] = 1
    true_inner = int((bits[0] & bits[1]).sum())
    d = sketch_dim(density, 0.1)
    p = CabinParams.create(n, d, seed=3)
    sk = binsketch(p, jnp.asarray(bits))
    est = float(inner_estimate(sk[0], sk[1], d))
    assert abs(est - true_inner) < 3 * np.sqrt(density * np.log(10)) + 2


# ---------------------------------------------------------------------------
# Layout invariances
# ---------------------------------------------------------------------------


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=10, deadline=None)
def test_sparse_dense_equivalence(seed):
    rng = np.random.default_rng(seed)
    n, c, density, rows = 800, 12, 60, 4
    x = make_categorical(rng, rows, n, c, density)
    p = CabinParams.create(n, 256, seed=seed & 0xFFFF)
    dense_sk = sketch_dense(p, jnp.asarray(x))
    idxs = np.zeros((rows, density), np.int32)
    vals = np.zeros((rows, density), np.int32)
    for i in range(rows):
        nz = np.nonzero(x[i])[0]
        idxs[i], vals[i] = nz, x[i, nz]
    sparse_sk = sketch_sparse(p, jnp.asarray(idxs), jnp.asarray(vals))
    np.testing.assert_array_equal(np.asarray(dense_sk), np.asarray(sparse_sk))


def test_sketch_deterministic_across_calls():
    rng = np.random.default_rng(6)
    x = make_categorical(rng, 3, 500, 8, 40)
    p = CabinParams.create(500, 128, seed=9)
    a = np.asarray(sketch_dense(p, jnp.asarray(x)))
    b = np.asarray(sketch_dense(p, jnp.asarray(x)))
    np.testing.assert_array_equal(a, b)
