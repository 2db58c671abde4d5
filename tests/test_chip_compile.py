"""The main path's Pallas kernels compile for a TPU v5e chip.

Each test lowers a kernel at the serving path's real widths and compiles
it with the TPU compiler for a described `v5e:2x2` topology — no chip
attached.  This catches what interpret mode cannot: block shapes that are
not lane-legal, reductions Mosaic does not implement, kernels that blow the
VMEM budget or take minutes to compile.  A compile that passes is not a
chip run; the results are checked by the interpret-mode parity tests.

The topology is described inside a module fixture (never at import):
only one process at a time may load the TPU library, and the test workers
must all collect the same tests.
"""

import pytest

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import SingleDeviceSharding

from repro.core.cham import log_f32
from repro.kernels.cabin_build import kernel as dense_kernel
from repro.kernels.cabin_build_sparse import kernel as sparse_kernel
from repro.kernels.hamming import kernel as hamming_kernel
from repro.kernels.topk_select import kernel as topk_kernel

COO_WIDTH = 2048  # NYTimes rows (1,306 entries) at their pow2 width bucket


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    """ShapeDtypeStruct factory placed on the described chip's device 0,
    with the persistent compile cache off: its entries for a described
    chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    one_chip = SingleDeviceSharding(topo.devices[0])
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield lambda dims, dtype=jnp.int32: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile_for_chip(fn, *args) -> str:
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text  # the Pallas kernel, not a fallback
    return text


@pytest.mark.parametrize("d", [512, 1024, 4096])
def test_cabin_build_sparse_compiles(shape, d):
    coo = shape((256, COO_WIDTH))
    _compile_for_chip(
        lambda i, v: sparse_kernel.cabin_build_sparse(
            i, v, d=d, psi_seed=1, pi_seed=2), coo, coo)


def test_cabin_build_dense_compiles(shape):
    _compile_for_chip(
        lambda x: dense_kernel.cabin_build(x, d=4096, psi_seed=1, pi_seed=2),
        shape((256, 1024)))


def test_hamming_kernels_compile(shape):
    w = 128  # d = 4096
    _compile_for_chip(lambda a, b: hamming_kernel.pair_stats(a, b),
                      shape((256, w)), shape((256, w)))
    _compile_for_chip(hamming_kernel.row_popcount, shape((2048, w)))


@pytest.mark.parametrize("metric", ["cham", "hamming"])
def test_topk_select_compiles(shape, metric):
    """The engine's tile sizes (bq=128, bn=block=2048) at W=128."""
    w = 128
    _compile_for_chip(
        lambda q, b, m: topk_kernel.topk_select(
            q, b, m, 10, metric=metric, d=32 * w, bq=128, bn=2048),
        shape((128, w)), shape((8192, w)), shape((), jnp.int32))


@pytest.mark.parametrize("q", [8, 64])
def test_topk_select_in_place_compiles(shape, q):
    """The band walk's in-place entry at the PubMed layout's size: 2^23
    rows, 1,024-row tiles, the tile list in SMEM."""
    w, p, bn = 128, 2**23, 1024
    _compile_for_chip(
        lambda q_, b, wt, r, t, n: topk_kernel.topk_select_tiles(
            q_, b, wt, r, t, n, 10, metric="cham", d=32 * w, bn=bn),
        shape((q, w)), shape((p, w)), shape((1, p)), shape((1, p)),
        shape((p // bn,)), shape((), jnp.int32))


def test_estimator_log_compiles_in_a_kernel(shape):
    """On TPU the Cham estimator takes its log from `log_f32` (bit ops,
    multiplies and adds), inside the top-k kernel as well."""
    def kernel(x_ref, o_ref):
        o_ref[...] = log_f32(x_ref[...])

    _compile_for_chip(
        lambda x: pl.pallas_call(
            kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x),
        shape((256, 512), jnp.float32))
