"""Pallas TPU kernel: fused Cabin sketch construction (BinEm + BinSketch).

A GPU port of the paper's algorithm would scatter bits through global memory
atomics.  TPUs have no scatter/atomics in the kernel programming model, so we
re-derive the OR-aggregation as MXU work (DESIGN.md section 2):

    out[i, t] = OR_j ( psi(j, x[i,j]) AND pi(j) == t )
              = ( sum_j bits[i, j] * onehot[j, t] ) > 0

i.e. a {0,1} matmul against an on-the-fly one-hot bucket matrix followed by a
`> 0`.  Both psi (category mapping) and pi (attribute mapping) are evaluated
INSIDE the kernel with the same stateless mixers as repro.core.hashing, so
the kernel reads the raw categorical tile from HBM exactly once and never
materialises the n-dimensional binary intermediate u'.

Grid: (N/BM, d/DO, n/BK) with the contraction (k over attribute slabs)
innermost.  DO is the sketch span of one output block (4096 bits when d is a
multiple of 4096, else all of d), so the output block is lane-legal on the
chip; inside a step the one-hot matmul walks DO in BD-bit chunks.  A
(BM, DO) f32 collision-count accumulator lives in VMEM scratch and is packed
to int32 words on the last k step by the sparse kernel's `pack_hits`.

Alignment contract: d % 128 == 0 (callers round the sketch dimension up to
a multiple of 128 — the theory gives a MINIMUM d, so rounding up only
tightens the estimate).  The same d % 128 contract is shared by the
padded-COO twin, repro.kernels.cabin_build_sparse.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import hashing
from repro.kernels.cabin_build_sparse.kernel import (chunk_bits,
                                                     out_block_bits, pack_hits)


def _cabin_kernel(x_ref, out_ref, acc_ref, *, psi_seed, pi_seed, d, bk,
                  span, chunk, k_steps):
    dblk = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]  # (BM, BK) int32 categorical slab
    # attribute positions of this slab, as a row (psi, per element) and as
    # a column (pi, per one-hot row)
    j_row = (k * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
             ).astype(jnp.uint32)
    j_col = (k * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
             ).astype(jnp.uint32)
    # Stage 1 (BinEm): psi(j, x) in {0,1}; padding columns (j >= n) carry
    # x == 0 and thus bit == 0, contributing nothing.
    bits = hashing.psi_bits(j_row, x, psi_seed).astype(jnp.float32)
    # Stage 2 (BinSketch): pi(j) buckets; restrict to this output block.
    local = hashing.pi_buckets(j_col, d, pi_seed) - dblk * span  # (BK, 1)
    t_iota = jax.lax.broadcasted_iota(jnp.int32, (bk, chunk), 1)

    def body(c, carry):
        off = pl.multiple_of(c * chunk, 128)
        onehot = ((local - off) == t_iota).astype(jnp.float32)  # (BK, BD)
        acc_ref[:, pl.ds(off, chunk)] += jnp.dot(
            bits, onehot, preferred_element_type=jnp.float32)
        return carry

    jax.lax.fori_loop(0, span // chunk, body, 0)

    @pl.when(k == k_steps - 1)
    def _finalize():
        pack_hits(acc_ref, out_ref, chunk=chunk)


@functools.partial(
    jax.jit, static_argnames=("d", "psi_seed", "pi_seed", "bm", "bd", "bk",
                              "interpret")
)
def cabin_build(
    x: jnp.ndarray,
    *,
    d: int,
    psi_seed: int,
    pi_seed: int,
    bm: int = 128,
    bd: int = 512,
    bk: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused Cabin on dense categorical rows: (N, n) int32 -> (N, d/32) int32.

    `bd` bounds the bits one one-hot matmul covers.  Requires d % 128 == 0
    (see module docstring).
    """
    n_rows, n = x.shape
    if d % 128:
        raise ValueError("cabin_build kernel requires d % 128 == 0")
    span = out_block_bits(d)
    chunk = chunk_bits(span, bd)
    bm_ = min(bm, max(8, n_rows))
    bk_ = min(bk, n)

    pad_rows = (-n_rows) % bm_
    pad_cols = (-n) % bk_
    x_p = jnp.pad(x, ((0, pad_rows), (0, pad_cols)))
    mp, np_ = x_p.shape
    k_steps = np_ // bk_
    grid = (mp // bm_, d // span, k_steps)

    out = pl.pallas_call(
        functools.partial(
            _cabin_kernel,
            psi_seed=psi_seed,
            pi_seed=pi_seed,
            d=d,
            bk=bk_,
            span=span,
            chunk=chunk,
            k_steps=k_steps,
        ),
        grid=grid,
        in_specs=[pl.BlockSpec((bm_, bk_), lambda i, t, k: (i, k))],
        out_specs=pl.BlockSpec((bm_, span // 32), lambda i, t, k: (i, t)),
        out_shape=jax.ShapeDtypeStruct((mp, d // 32), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm_, span), jnp.float32)],
        interpret=interpret,
    )(x_p)
    return out[:n_rows]
