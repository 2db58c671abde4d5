"""Cham: Hamming-distance estimation from Cabin sketches (Algorithm 2).

Implements the BinSketch estimator the paper defers to ([33, Alg. 2]); the
formula printed in the provided text is PDF-garbled (see DESIGN.md 1.1).

Derivation, with d bins, D = 1 - 1/d, sketch weights wu = |u~|, wv = |v~| and
sketch inner product st = <u~, v~>:

  E[wu]           = d (1 - D^a)            a = |u'| (pre-sketch density)
  E[wu + wv - st] = d (1 - D^(a+b-ip))     bins hit by the support UNION
so
  a_hat  = log(1 - wu/d) / log D
  U_hat  = log(1 - (wu + wv - st)/d) / log D
  ip_hat = a_hat + b_hat - U_hat
  h_hat  = a_hat + b_hat - 2 ip_hat = 2 U_hat - a_hat - b_hat

and Cham(u~, v~) = 2 h_hat (Lemma 2: HD(u,v) = 2 E[HD(u',v')]).

Also provides the BinSketch bonus estimators (inner product / cosine /
Jaccard on the pre-sketch binary vectors) and all-pairs matrix forms used by
heatmap / clustering / dedup workloads.  The all-pairs packed popcount matmul
has a Pallas TPU kernel twin in repro.kernels.hamming.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import packing

_EPS = 1e-9

# Cephes logf: log(1 + x) = x - x^2/2 + x^3 P(x) on [sqrt(1/2) - 1, sqrt(2) - 1]
_LOG_POLY = (7.0376836292e-2, -1.1514610310e-1, 1.1676998740e-1,
             -1.2420140846e-1, 1.4249322787e-1, -1.6668057665e-1,
             2.0000714765e-1, -2.4999993993e-1, 3.3333331174e-1)
_LN2_HI, _LN2_LO = 0.693359375, -2.12194440e-4


def log_f32(y: jnp.ndarray) -> jnp.ndarray:
    """Natural log of positive normal f32 values to about an ulp, from bit
    operations, multiplies and adds only (Cephes logf), so XLA and Pallas
    kernels compute it alike on every backend.  The TPU's native f32 log is
    a fast approximation (relative error up to ~2e-4 for arguments near 1,
    measured on a v5e) that the 2u - a - b cancellation below turns into
    distance errors of ~1e-3."""
    bits = jax.lax.bitcast_convert_type(y.astype(jnp.float32), jnp.int32)
    mant = jax.lax.bitcast_convert_type((bits & 0x007FFFFF) | 0x3F800000,
                                        jnp.float32)  # [1, 2)
    big = mant >= np.float32(math.sqrt(2.0))
    x = jnp.where(big, 0.5 * mant, mant) - 1.0  # exact
    e = ((bits >> 23) - 127 + big.astype(jnp.int32)).astype(jnp.float32)
    z = x * x
    p = jnp.full_like(x, _LOG_POLY[0])
    for c in _LOG_POLY[1:]:
        p = p * x + c
    r = p * x * z + _LN2_LO * e - 0.5 * z
    return (x + r) + _LN2_HI * e


def _safe_log1m(x: jnp.ndarray) -> jnp.ndarray:
    """log(1 - x), clamped: saturated sketches (x -> 1) clip to a full bin."""
    return log_f32(jnp.clip(1.0 - x, _EPS, 1.0))


def density_estimate(weight: jnp.ndarray, d: int) -> jnp.ndarray:
    """Estimate pre-sketch Hamming weight from sketch weight (BinSketch)."""
    log_d = math.log1p(-1.0 / d)
    return _safe_log1m(weight.astype(jnp.float32) / d) / log_d


def binhamming_from_stats(
    wu: jnp.ndarray, wv: jnp.ndarray, inner: jnp.ndarray, d: int,
    *, obs_u: jnp.ndarray | None = None, obs_v: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """h_hat = estimated HD(u', v') from sketch statistics (broadcasting).

    obs_u / obs_v (keyword-only, broadcasting like wu / wv) are per-row
    OBSERVED-dimension counts under the miss model of Shen et al. (online
    categorical sketching with misses): a row whose record dropped some
    categories can have at most obs set bits, so the density and union
    estimates are clamped into the feasible polytope
        a_hat <= obs_u,  b_hat <= obs_v,  max(a,b) <= u_hat <= a_hat + b_hat
    before the distance is formed.  With both None (the default) the
    arithmetic is bit-identical to the unmasked estimator — serving paths
    that never see misses pay nothing.  A saturated sketch of a heavily
    truncated row otherwise explodes a_hat through the log and corrupts
    every distance against it; clamping degrades it gracefully to "as far
    as its observed support allows".
    """
    log_d = math.log1p(-1.0 / d)  # d is static: a host constant
    wu = wu.astype(jnp.float32)
    wv = wv.astype(jnp.float32)
    st = inner.astype(jnp.float32)
    a_hat = _safe_log1m(wu / d) / log_d
    b_hat = _safe_log1m(wv / d) / log_d
    u_hat = _safe_log1m((wu + wv - st) / d) / log_d
    if obs_u is not None:
        a_hat = jnp.minimum(a_hat, obs_u.astype(jnp.float32))
    if obs_v is not None:
        b_hat = jnp.minimum(b_hat, obs_v.astype(jnp.float32))
    if obs_u is not None or obs_v is not None:
        u_hat = jnp.clip(u_hat, jnp.maximum(a_hat, b_hat), a_hat + b_hat)
    return jnp.maximum(2.0 * u_hat - a_hat - b_hat, 0.0)


def binhamming(u: jnp.ndarray, v: jnp.ndarray, d: int,
               *, obs_u: jnp.ndarray | None = None,
               obs_v: jnp.ndarray | None = None) -> jnp.ndarray:
    """BinHamming on packed sketches (..., w) -> estimated HD(u', v')."""
    wu = packing.popcount_rows(u)
    wv = packing.popcount_rows(v)
    inner = packing.packed_inner(u, v)
    return binhamming_from_stats(wu, wv, inner, d, obs_u=obs_u, obs_v=obs_v)


def cham(u: jnp.ndarray, v: jnp.ndarray, d: int,
         *, obs_u: jnp.ndarray | None = None,
         obs_v: jnp.ndarray | None = None) -> jnp.ndarray:
    """Cham(u~, v~) = 2 * BinHamming — estimates HD of the ORIGINAL vectors."""
    return 2.0 * binhamming(u, v, d, obs_u=obs_u, obs_v=obs_v)


def inner_estimate(u: jnp.ndarray, v: jnp.ndarray, d: int) -> jnp.ndarray:
    """Estimated <u', v'> (BinSketch Theorem 1 quantity)."""
    wu = packing.popcount_rows(u)
    wv = packing.popcount_rows(v)
    st = packing.packed_inner(u, v)
    log_d = math.log1p(-1.0 / d)
    a_hat = _safe_log1m(wu.astype(jnp.float32) / d) / log_d
    b_hat = _safe_log1m(wv.astype(jnp.float32) / d) / log_d
    u_hat = _safe_log1m((wu + wv - st).astype(jnp.float32) / d) / log_d
    return jnp.maximum(a_hat + b_hat - u_hat, 0.0)


def cosine_estimate(u: jnp.ndarray, v: jnp.ndarray, d: int) -> jnp.ndarray:
    wu = density_estimate(packing.popcount_rows(u), d)
    wv = density_estimate(packing.popcount_rows(v), d)
    ip = inner_estimate(u, v, d)
    return ip / jnp.maximum(jnp.sqrt(wu * wv), _EPS)


def jaccard_estimate(u: jnp.ndarray, v: jnp.ndarray, d: int) -> jnp.ndarray:
    wu = density_estimate(packing.popcount_rows(u), d)
    wv = density_estimate(packing.popcount_rows(v), d)
    ip = inner_estimate(u, v, d)
    return ip / jnp.maximum(wu + wv - ip, _EPS)


# ---------------------------------------------------------------------------
# All-pairs (matrix) forms — heatmaps, RMSE, k-mode, dedup.
# ---------------------------------------------------------------------------


def sketch_stats_matrix(
    a: jnp.ndarray, b: jnp.ndarray
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Pairwise (wa, wb, inner) between packed rows a (N, w) and b (M, w).

    jnp reference path: O(N*M*w) popcounts.  The Pallas kernel in
    repro.kernels.hamming computes the same tiled in VMEM.
    """
    wa = packing.popcount_rows(a)
    wb = packing.popcount_rows(b)
    inner = jnp.sum(
        packing.popcount32(a[:, None, :] & b[None, :, :]), axis=-1
    )
    return wa, wb, inner


def cham_matrix(a: jnp.ndarray, b: jnp.ndarray, d: int) -> jnp.ndarray:
    """All-pairs Cham estimates: (N, w), (M, w) packed -> (N, M) float32."""
    wa, wb, inner = sketch_stats_matrix(a, b)
    return 2.0 * binhamming_from_stats(wa[:, None], wb[None, :], inner, d)


def hamming_matrix_exact(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Exact pairwise HD between packed BINARY rows (used on u'/full data)."""
    return jnp.sum(packing.popcount32(a[:, None, :] ^ b[None, :, :]), axis=-1)
