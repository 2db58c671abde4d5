"""Jit'd wrapper for fused Cabin sketch construction."""

from __future__ import annotations

import jax

from repro.core.cabin import CabinParams, kernel_dispatch
from repro.kernels.cabin_build import kernel, ref


def cabin_sketch(params: CabinParams, x, *, use_pallas: bool | None = None,
                 interpret: bool | None = None):
    """Cabin sketches for dense categorical rows (N, n) -> packed (N, w).

    Uses the fused Pallas kernel on TPU or when explicitly requested (tests
    run it with interpret=True), the jnp reference path otherwise — the
    `core.cabin.kernel_dispatch` rule, which refuses an unaligned sketch dim
    where the kernel is due.
    """
    if kernel_dispatch(params.sketch_dim, use_pallas):
        return kernel.cabin_build(
            x,
            d=params.sketch_dim,
            psi_seed=params.psi_seed,
            pi_seed=params.pi_seed,
            interpret=bool(interpret if interpret is not None
                           else jax.default_backend() != "tpu"),
        )
    return ref.cabin_build_ref(
        x, d=params.sketch_dim, psi_seed=params.psi_seed, pi_seed=params.pi_seed
    )
