"""Jit'd wrapper for fused sparse-Cabin sketch construction.

Mirrors repro.kernels.cabin_build.ops: `use_pallas=None` auto-selects the
compiled kernel on TPU (which needs a 128-aligned sketch dim — an unaligned
one raises there), the jnp scatter-max reference otherwise; tests run the
kernel with interpret=True on CPU.
"""

from __future__ import annotations

import jax

from repro.core.cabin import CabinParams, kernel_dispatch, sketch_sparse_jnp
from repro.kernels.cabin_build_sparse import kernel


def cabin_sketch_sparse(params: CabinParams, indices, values, *,
                        use_pallas: bool | None = None,
                        interpret: bool | None = None):
    """Cabin sketches for padded-COO rows (N, m) x2 -> packed (N, w).

    Uses the fused Pallas kernel on TPU or when explicitly requested (tests
    run it with interpret=True), the jnp reference path otherwise — the
    `core.cabin.kernel_dispatch` rule.  Output is bit-identical either way.
    """
    if kernel_dispatch(params.sketch_dim, use_pallas):
        return kernel.cabin_build_sparse(
            indices,
            values,
            d=params.sketch_dim,
            psi_seed=params.psi_seed,
            pi_seed=params.pi_seed,
            interpret=bool(interpret if interpret is not None
                           else jax.default_backend() != "tpu"),
        )
    return sketch_sparse_jnp(params, indices, values)
