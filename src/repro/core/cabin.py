"""Cabin: the paper's sketching algorithm (Algorithm 1).

Two stages:
  1. BinEm   — category mapping psi(i, a) -> {0,1} turns a categorical vector
               u in {0..c}^n into a binary vector u' in {0,1}^n (same dim).
  2. BinSketch — attribute mapping pi(i) -> {0..d-1} ORs bits into d buckets.

Both stages are one-pass and stateless (hash-derived mappings, DESIGN.md 1.1).
Sketches are produced directly in packed int32 form; the n-dimensional binary
intermediate is only materialised by the explicit `binem` API (used by the
paper's Figure-4 analysis) — the fused paths never allocate it at full width
per batch beyond the input itself.

Two input layouts are supported:
  * dense:  x (N, n) int32, 0 = missing feature.
  * sparse: (indices (N, m), values (N, m)) padded COO rows; value 0 = pad.
    This is the layout for the million-dimension datasets (Table 1).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.core import hashing, packing


def _derive_seeds(seed: int) -> tuple[int, int]:
    s = int(hashing.mix32(jnp.uint32(seed * 2 + 1)))
    return s & 0x7FFFFFFF, int(hashing.mix32(jnp.uint32(s + 17))) & 0x7FFFFFFF


@dataclass(frozen=True)
class CabinParams:
    """Static description of a Cabin sketcher: dims + hash seeds."""

    n_dims: int  # original dimension n
    sketch_dim: int  # d
    psi_seed: int
    pi_seed: int

    @classmethod
    def create(cls, n_dims: int, sketch_dim: int, seed: int = 0) -> "CabinParams":
        psi, pi = _derive_seeds(seed)
        return cls(n_dims=n_dims, sketch_dim=sketch_dim, psi_seed=psi, pi_seed=pi)

    @property
    def packed_width(self) -> int:
        return packing.packed_width(self.sketch_dim)


# ---------------------------------------------------------------------------
# Stage 1: BinEm
# ---------------------------------------------------------------------------


def binem(params: CabinParams, x: jnp.ndarray) -> jnp.ndarray:
    """BinEm on dense categorical rows: (..., n) {0..c} -> (..., n) {0,1}."""
    n = x.shape[-1]
    idx = jnp.arange(n, dtype=jnp.uint32)
    return hashing.psi_bits(idx, x, params.psi_seed)


# ---------------------------------------------------------------------------
# Stage 2: BinSketch (+ fused Cabin)
# ---------------------------------------------------------------------------


def binsketch(params: CabinParams, bits: jnp.ndarray) -> jnp.ndarray:
    """BinSketch on dense binary rows: (..., n) {0,1} -> packed (..., w) int32."""
    n = bits.shape[-1]
    buckets = hashing.pi_buckets(jnp.arange(n, dtype=jnp.uint32),
                                 params.sketch_dim, params.pi_seed)
    d = params.sketch_dim
    # OR-aggregation == max-aggregation on {0,1}: scatter-max into d buckets.
    flat = bits.reshape(-1, n)
    out = jnp.zeros((flat.shape[0], d), dtype=flat.dtype)
    out = out.at[:, buckets].max(flat, mode="drop")
    out = out.reshape(*bits.shape[:-1], d)
    return packing.pack_bits(out)


def sketch_dense(params: CabinParams, x: jnp.ndarray) -> jnp.ndarray:
    """Cabin on dense categorical rows -> packed sketches (..., w) int32."""
    return binsketch(params, binem(params, x))


def sketch_sparse_jnp(
    params: CabinParams, indices: jnp.ndarray, values: jnp.ndarray
) -> jnp.ndarray:
    """jnp reference path for Cabin on padded-COO rows: per-row scatter-max.

    This is the oracle the fused Pallas kernel
    (repro.kernels.cabin_build_sparse) is tested against bit-for-bit, and
    the path `sketch_sparse` takes off-TPU.
    """
    bits = hashing.psi_bits(indices.astype(jnp.uint32), values, params.psi_seed)
    buckets = hashing.pi_buckets(indices.astype(jnp.uint32),
                                 params.sketch_dim, params.pi_seed)
    bits = jnp.where(values != 0, bits, 0)
    m = indices.shape[-1]
    flat_bits = bits.reshape(-1, m)
    flat_buckets = buckets.reshape(-1, m)
    out = jnp.zeros((flat_bits.shape[0], params.sketch_dim), dtype=jnp.int32)
    out = jax.vmap(lambda o, b, v: o.at[b].max(v, mode="drop"))(
        out, flat_buckets, flat_bits
    )
    out = out.reshape(*indices.shape[:-1], params.sketch_dim)
    return packing.pack_bits(out)


def kernel_dispatch(sketch_dim: int, use_pallas: bool | None) -> bool:
    """Whether Cabin sketching runs the fused Pallas kernels.

    use_pallas=None picks the kernel exactly when JAX runs on a TPU.  The
    kernels need sketch_dim % 128 == 0; a request for them — explicit, or
    implied by running on a TPU — raises for any other dim instead of
    quietly taking the jnp path.  use_pallas=False always takes it.
    """
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if use_pallas and sketch_dim % 128:
        raise ValueError(
            f"sketch_dim={sketch_dim} is not a multiple of 128, which the "
            "Cabin kernels need; round d up (the theory gives a minimum d) "
            "or pass use_pallas=False for the jnp path")
    return bool(use_pallas)


def sketch_sparse(
    params: CabinParams,
    indices: jnp.ndarray,
    values: jnp.ndarray,
    *,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Cabin on padded-COO rows -> packed sketches (..., w) int32.

    indices: (..., m) int32 feature positions; values: (..., m) categories,
    0 = padding / missing (psi maps it to 0, so padded entries can share
    index 0 safely).

    Dispatch (`kernel_dispatch`): on a TPU the fused Pallas kernel
    repro.kernels.cabin_build_sparse builds the packed sketch in one pass;
    elsewhere the jnp scatter-max reference path runs, unless the kernel is
    requested explicitly via use_pallas=True (tests run it with
    interpret=True).  Both produce bit-identical output.
    """
    if kernel_dispatch(params.sketch_dim, use_pallas):
        # lazy import: repro.kernels.* imports this module for CabinParams
        from repro.kernels.cabin_build_sparse import kernel as _sparse_kernel

        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        m = indices.shape[-1]
        lead = indices.shape[:-1]
        out = _sparse_kernel.cabin_build_sparse(
            indices.reshape(-1, m),
            values.reshape(-1, m),
            d=params.sketch_dim,
            psi_seed=params.psi_seed,
            pi_seed=params.pi_seed,
            interpret=bool(interpret),
        )
        return out.reshape(*lead, params.packed_width)
    return sketch_sparse_jnp(params, indices, values)


@functools.partial(jax.jit, static_argnums=0)
def sketch_dense_jit(params: CabinParams, x: jnp.ndarray) -> jnp.ndarray:
    return sketch_dense(params, x)


@functools.partial(jax.jit, static_argnums=0)
def sketch_sparse_jit(
    params: CabinParams, indices: jnp.ndarray, values: jnp.ndarray
) -> jnp.ndarray:
    return sketch_sparse(params, indices, values)
