"""Pallas TPU kernel: fused Cabin sketch construction on padded-COO rows.

This is the sparse twin of repro.kernels.cabin_build — the path that matters
for the paper's Table-1 datasets, where n runs to millions of dimensions but
each row carries only a few hundred nonzeros.  The dense kernel's contraction
runs over ALL n attribute columns; here it runs over the m <= few-hundred
padded-COO slots, so the kernel is O(N * m * d) instead of O(N * n * d) with
the same output.

Derivation (DESIGN.md section 2 applied to the COO layout): the dense kernel
exploits that pi(j) is shared by every row in a column slab, turning the
OR-aggregation into one (BK, BD) one-hot matmul on the MXU.  In COO layout
the attribute index — and therefore the bucket — varies PER ELEMENT, so no
shared one-hot matrix exists.  We instead evaluate the OR-aggregation as a
VPU compare-reduce over a (BM, BK, BD) broadcast:

    hit[i, t] = OR_k ( psi(idx[i,k], val[i,k]) AND pi(idx[i,k]) == t )
    acc[i, t] += sum_k bits[i, k] * (local_bucket[i, k] == t)

with psi and pi evaluated INSIDE the kernel by the same stateless mixers as
repro.core.hashing (no tables, no gathers, no scatter/atomics).  Padding
slots carry value 0 and psi(., 0) = 0 by construction, so they contribute
nothing even though they alias attribute index 0.

Grid: (N/BM, d/DO, m/BK), contraction innermost.  DO (`out_block_bits`) is
the sketch span of one output block: 4096 bits (128 int32 words, one full
lane tile) when d is a multiple of 4096, else all of d — either way the
output block is lane-legal on the chip.  Inside a step the compare-reduce
walks DO in BD-bit chunks, so the (BM, BK, BD) broadcast stays small.  An
int32 (BM, DO) collision-count accumulator lives in VMEM scratch and is
packed to int32 words on the last k step by `pack_hits` — identical packing
(LSB-first, bit j -> word j//32) to the dense kernel and repro.core.packing.

Alignment contract (shared with cabin_build): d % 128 == 0; callers round
the sketch dimension up to a multiple of 128 (the theory gives a MINIMUM d,
so rounding up only tightens the estimate).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import hashing

_WORD_TILE_BITS = 4096  # 128 int32 words: one full lane tile of output


def out_block_bits(d: int) -> int:
    """Sketch bits per output block: a whole lane tile of words when d
    allows it, else the full sketch (a full-width block is always legal)."""
    return _WORD_TILE_BITS if d % _WORD_TILE_BITS == 0 else d


def chunk_bits(span: int, bd: int) -> int:
    """Largest 128-multiple <= bd that divides `span` (a 128-multiple)."""
    c = max(128, min(bd, span) // 128 * 128)
    while span % c:
        c -= 128
    return c


def pack_hits(acc_ref, out_ref, *, chunk: int) -> None:
    """Pack the (BM, DO) hit-count accumulator into (BM, DO/32) int32 words.

    Word w, bit b <- column 32w + b.  Done on the MXU so no lane reshape or
    unsigned reduction is needed: each BD-bit chunk of hits multiplies a
    (BD, DO/32) place-value matrix whose entries are powers of two below
    2^16, once for bits 0-15 and once for bits 16-31.  Every product and sum
    is an integer below 2^16, exact even at the MXU's bf16 input precision,
    and the two 16-bit halves recombine in int32."""
    bm, span = acc_ref.shape
    words = span // 32
    t = jax.lax.broadcasted_iota(jnp.int32, (chunk, words), 0)
    w = jax.lax.broadcasted_iota(jnp.int32, (chunk, words), 1)

    def body(c, carry):
        lo, hi = carry
        off = pl.multiple_of(c * chunk, 128)
        hit = (acc_ref[:, pl.ds(off, chunk)] > 0).astype(jnp.float32)
        col = t + off
        place = jnp.where((col >> 5) == w,
                          jnp.left_shift(1, col & 15), 0).astype(jnp.float32)
        low_half = (col & 16) == 0
        p_lo = jnp.where(low_half, place, 0.0)
        p_hi = jnp.where(low_half, 0.0, place)
        lo = lo + jnp.dot(hit, p_lo, preferred_element_type=jnp.float32)
        hi = hi + jnp.dot(hit, p_hi, preferred_element_type=jnp.float32)
        return lo, hi

    zero = jnp.zeros((bm, words), jnp.float32)
    lo, hi = jax.lax.fori_loop(0, span // chunk, body, (zero, zero))
    out_ref[...] = lo.astype(jnp.int32) | (hi.astype(jnp.int32) << 16)


def _cabin_sparse_kernel(idx_ref, val_ref, out_ref, acc_ref, *, psi_seed,
                         pi_seed, d, span, chunk, k_steps):
    dblk = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    idx = idx_ref[...]  # (BM, BK) int32 attribute positions
    val = val_ref[...]  # (BM, BK) int32 categories, 0 = padding
    # Stage 1 (BinEm): psi(idx, val) in {0,1}; psi(., 0) == 0 masks padding.
    bits = hashing.psi_bits(idx.astype(jnp.uint32), val, psi_seed)  # (BM, BK)
    # Stage 2 (BinSketch): per-ELEMENT buckets, restricted to this block;
    # elements whose bit is 0 get bucket -1 and never match.
    buckets = hashing.pi_buckets(idx.astype(jnp.uint32), d, pi_seed)
    local = jnp.where(bits > 0, buckets - dblk * span, -1)  # (BM, BK)
    t_iota = jax.lax.broadcasted_iota(jnp.int32, (1, 1, chunk), 2)

    def body(c, carry):
        off = pl.multiple_of(c * chunk, 128)
        # (BM, BK, BD) compare-reduce: no shared one-hot exists in COO layout
        hit = (local - off)[:, :, None] == t_iota
        acc_ref[:, pl.ds(off, chunk)] += jnp.sum(hit.astype(jnp.int32),
                                                 axis=1)
        return carry

    jax.lax.fori_loop(0, span // chunk, body, 0)

    @pl.when(k == k_steps - 1)
    def _finalize():
        pack_hits(acc_ref, out_ref, chunk=chunk)


@functools.partial(
    jax.jit, static_argnames=("d", "psi_seed", "pi_seed", "bm", "bd", "bk",
                              "interpret")
)
def cabin_build_sparse(
    indices: jnp.ndarray,
    values: jnp.ndarray,
    *,
    d: int,
    psi_seed: int,
    pi_seed: int,
    bm: int = 8,
    bd: int = 512,
    bk: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused Cabin on padded-COO rows: (N, m) x2 int32 -> (N, d/32) int32.

    indices[i, k] is the attribute position of slot k of row i; values[i, k]
    its category, with 0 meaning padding/missing.  `bd` bounds the bits one
    compare-reduce step covers.  Requires d % 128 == 0 (see module
    docstring).
    """
    if indices.shape != values.shape or indices.ndim != 2:
        raise ValueError("indices/values must be identically-shaped (N, m)")
    n_rows, m = indices.shape
    if d % 128:
        raise ValueError("cabin_build_sparse kernel requires d % 128 == 0")
    span = out_block_bits(d)
    chunk = chunk_bits(span, bd)
    bm_ = min(bm, max(1, n_rows))
    bk_ = min(bk, m)

    pad_rows = (-n_rows) % bm_
    pad_cols = (-m) % bk_
    # zero padding is safe: value 0 => psi bit 0 => no contribution
    idx_p = jnp.pad(indices, ((0, pad_rows), (0, pad_cols)))
    val_p = jnp.pad(values, ((0, pad_rows), (0, pad_cols)))
    mp, m_p = idx_p.shape
    k_steps = m_p // bk_
    grid = (mp // bm_, d // span, k_steps)

    out = pl.pallas_call(
        functools.partial(
            _cabin_sparse_kernel,
            psi_seed=psi_seed,
            pi_seed=pi_seed,
            d=d,
            span=span,
            chunk=chunk,
            k_steps=k_steps,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm_, bk_), lambda i, t, k: (i, k)),
            pl.BlockSpec((bm_, bk_), lambda i, t, k: (i, k)),
        ],
        out_specs=pl.BlockSpec((bm_, span // 32), lambda i, t, k: (i, t)),
        out_shape=jax.ShapeDtypeStruct((mp, d // 32), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm_, span), jnp.int32)],
        interpret=interpret,
    )(idx_p, val_p)
    return out[:n_rows]
