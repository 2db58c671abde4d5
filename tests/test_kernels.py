"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs pure-jnp oracle."""

import numpy as np
import jax.numpy as jnp
import pytest
from _hyp import given, settings, st  # hypothesis when installed, fallback otherwise

from repro.kernels.cabin_build.kernel import cabin_build
from repro.kernels.cabin_build.ops import cabin_sketch
from repro.kernels.cabin_build.ref import cabin_build_ref
from repro.kernels.cabin_build_sparse.kernel import cabin_build_sparse
from repro.kernels.cabin_build_sparse.ops import cabin_sketch_sparse
from repro.kernels.cabin_build_sparse.ref import cabin_build_sparse_ref
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ops import attention, chunked_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.hamming.kernel import pair_stats, row_popcount
from repro.kernels.hamming.ops import cham_matrix_fast
from repro.kernels.hamming.ref import pair_stats_ref, row_popcount_ref
from repro.core.cabin import CabinParams
from repro.core.cham import cham_matrix

RNG = np.random.default_rng(1234)


# ---------------------------------------------------------------------------
# hamming / pair_stats
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "m,n,w,bm,bn,bk",
    [
        (1, 1, 1, 8, 8, 4),
        (16, 16, 8, 8, 8, 4),
        (37, 29, 9, 16, 16, 4),   # ragged: padding on every axis
        (64, 33, 17, 32, 16, 8),
        (128, 128, 32, 128, 128, 32),  # exact tiling
    ],
)
def test_pair_stats_shapes(m, n, w, bm, bn, bk):
    a = jnp.asarray(RNG.integers(-(2**31), 2**31, size=(m, w)).astype(np.int32))
    b = jnp.asarray(RNG.integers(-(2**31), 2**31, size=(n, w)).astype(np.int32))
    i1, h1 = pair_stats(a, b, interpret=True, bm=bm, bn=bn, bk=bk)
    i2, h2 = pair_stats_ref(a, b)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2))


def test_pair_stats_single_op_modes():
    a = jnp.asarray(RNG.integers(-(2**31), 2**31, size=(9, 5)).astype(np.int32))
    inner, ham = pair_stats(a, a, op_ham=False, interpret=True, bm=8, bn=8, bk=4)
    assert ham is None
    inner2, ham2 = pair_stats(a, a, op_inner=False, interpret=True, bm=8, bn=8, bk=4)
    assert inner2 is None
    ri, rh = pair_stats_ref(a, a)
    np.testing.assert_array_equal(np.asarray(inner), np.asarray(ri))
    np.testing.assert_array_equal(np.asarray(ham2), np.asarray(rh))


@given(st.integers(1, 80), st.integers(1, 12), st.integers(0, 2**31 - 1))
@settings(max_examples=8, deadline=None)
def test_row_popcount_property(m, w, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.integers(-(2**31), 2**31, size=(m, w)).astype(np.int32))
    got = row_popcount(x, interpret=True, bm=16)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(row_popcount_ref(x)))


def test_cham_matrix_fast_matches_core():
    d = 512
    p = CabinParams.create(1000, d, seed=0)
    from repro.core.cabin import sketch_dense

    x = jnp.asarray(RNG.integers(0, 5, size=(24, 1000)).astype(np.int32))
    sk = sketch_dense(p, x)
    fast = cham_matrix_fast(sk, sk, d, use_pallas=True)
    slow = cham_matrix(sk, sk, d)
    np.testing.assert_allclose(np.asarray(fast), np.asarray(slow), rtol=1e-5, atol=1e-3)


# ---------------------------------------------------------------------------
# cabin_build
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "rows,n,d,bm,bd,bk",
    [
        (1, 50, 128, 8, 128, 64),
        (19, 700, 256, 8, 128, 128),
        (8, 1000, 512, 8, 512, 256),
        (33, 333, 384, 16, 128, 128),  # d with non-power-of-two block count
    ],
)
def test_cabin_build_shapes(rows, n, d, bm, bd, bk):
    x = jnp.asarray(RNG.integers(0, 9, size=(rows, n)).astype(np.int32))
    got = cabin_build(x, d=d, psi_seed=7, pi_seed=13, bm=bm, bd=bd, bk=bk,
                      interpret=True)
    want = cabin_build_ref(x, d=d, psi_seed=7, pi_seed=13)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_cabin_build_all_missing():
    x = jnp.zeros((4, 100), jnp.int32)
    got = cabin_build(x, d=128, psi_seed=1, pi_seed=2, bm=8, bd=128, bk=64,
                      interpret=True)
    assert int(jnp.abs(got).sum()) == 0


def test_cabin_ops_wrapper_dispatch():
    p = CabinParams.create(200, 128, seed=5)
    x = jnp.asarray(RNG.integers(0, 4, size=(6, 200)).astype(np.int32))
    a = cabin_sketch(p, x, use_pallas=True, interpret=True)
    b = cabin_sketch(p, x, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # off-TPU an unaligned d takes the reference path; a kernel request
    # for it raises instead of quietly taking that path
    p2 = CabinParams.create(200, 100, seed=5)
    c = cabin_sketch(p2, x)
    assert c.shape == (6, 4)  # ceil(100/32)
    with pytest.raises(ValueError, match="multiple of 128"):
        cabin_sketch(p2, x, use_pallas=True, interpret=True)


# ---------------------------------------------------------------------------
# cabin_build_sparse
# ---------------------------------------------------------------------------


def _coo_rows(rng, rows, n, m, c=12):
    """Padded-COO rows with per-row random support (value 0 = padding)."""
    idx = np.zeros((rows, m), np.int32)
    val = np.zeros((rows, m), np.int32)
    for i in range(rows):
        nnz = int(rng.integers(0, m + 1))
        if nnz:
            idx[i, :nnz] = rng.choice(n, size=nnz, replace=False)
            val[i, :nnz] = rng.integers(1, c + 1, size=nnz)
    return idx, val


@pytest.mark.parametrize(
    "rows,n,m,d,bm,bd,bk",
    [
        (1, 500, 7, 128, 8, 128, 64),
        (19, 5000, 60, 256, 8, 128, 32),
        (33, 100000, 130, 384, 16, 128, 128),  # non-power-of-two block count
        (8, 1000, 200, 512, 8, 512, 128),
    ],
)
def test_cabin_build_sparse_shapes(rows, n, m, d, bm, bd, bk):
    idx, val = _coo_rows(RNG, rows, n, m)
    got = cabin_build_sparse(jnp.asarray(idx), jnp.asarray(val), d=d,
                             psi_seed=7, pi_seed=13, bm=bm, bd=bd, bk=bk,
                             interpret=True)
    want = cabin_build_sparse_ref(jnp.asarray(idx), jnp.asarray(val),
                                  n_dims=n, d=d, psi_seed=7, pi_seed=13)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_cabin_build_sparse_all_padding():
    idx = jnp.zeros((4, 50), jnp.int32)
    val = jnp.zeros((4, 50), jnp.int32)
    got = cabin_build_sparse(idx, val, d=128, psi_seed=1, pi_seed=2,
                             interpret=True)
    assert int(jnp.abs(got).sum()) == 0


def test_cabin_build_sparse_matches_dense_kernel():
    """Sparse and dense fused kernels agree on the same logical rows."""
    rng = np.random.default_rng(77)
    rows, n, density, d = 6, 700, 40, 256
    x = np.zeros((rows, n), np.int32)
    idx = np.zeros((rows, density), np.int32)
    val = np.zeros((rows, density), np.int32)
    for i in range(rows):
        pos = rng.choice(n, size=density, replace=False)
        cats = rng.integers(1, 9, size=density)
        x[i, pos] = cats
        idx[i], val[i] = pos, cats
    dense = cabin_build(jnp.asarray(x), d=d, psi_seed=3, pi_seed=5,
                        bm=8, bd=128, bk=128, interpret=True)
    sparse = cabin_build_sparse(jnp.asarray(idx), jnp.asarray(val), d=d,
                                psi_seed=3, pi_seed=5, interpret=True)
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(sparse))


def test_cabin_sparse_ops_wrapper_dispatch():
    p = CabinParams.create(3000, 128, seed=5)
    idx, val = _coo_rows(RNG, 6, 3000, 40)
    a = cabin_sketch_sparse(p, jnp.asarray(idx), jnp.asarray(val),
                            use_pallas=True, interpret=True)
    b = cabin_sketch_sparse(p, jnp.asarray(idx), jnp.asarray(val),
                            use_pallas=False)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # off-TPU an unaligned d takes the jnp reference; a kernel request for
    # it raises
    p2 = CabinParams.create(3000, 100, seed=5)
    c = cabin_sketch_sparse(p2, jnp.asarray(idx), jnp.asarray(val))
    assert c.shape == (6, 4)  # ceil(100/32)
    with pytest.raises(ValueError, match="multiple of 128"):
        cabin_sketch_sparse(p2, jnp.asarray(idx), jnp.asarray(val),
                            use_pallas=True, interpret=True)


def test_kernel_dispatch_on_tpu_refuses_unaligned_dims(monkeypatch):
    """On a TPU the kernels are due: an unaligned sketch dim raises there
    rather than taking the jnp path unnoticed."""
    from repro.core import cabin

    monkeypatch.setattr(cabin.jax, "default_backend", lambda: "tpu")
    assert cabin.kernel_dispatch(4096, None)
    with pytest.raises(ValueError, match="multiple of 128"):
        cabin.kernel_dispatch(100, None)
    assert not cabin.kernel_dispatch(100, False)


def test_sketch_sparse_core_dispatch_bit_identical():
    """core.cabin.sketch_sparse: kernel dispatch == jnp fallback, bit for bit."""
    from repro.core.cabin import sketch_sparse, sketch_sparse_jnp

    p = CabinParams.create(5000, 256, seed=9)
    idx, val = _coo_rows(RNG, 11, 5000, 70)
    via_kernel = sketch_sparse(p, jnp.asarray(idx), jnp.asarray(val),
                               use_pallas=True, interpret=True)
    via_jnp = sketch_sparse_jnp(p, jnp.asarray(idx), jnp.asarray(val))
    np.testing.assert_array_equal(np.asarray(via_kernel), np.asarray(via_jnp))


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "b,hq,hkv,s,dh,bq,bk,causal",
    [
        (1, 2, 2, 128, 64, 64, 64, True),
        (2, 4, 2, 256, 64, 64, 64, True),    # GQA 2:1
        (1, 8, 1, 128, 32, 64, 32, True),    # MQA
        (1, 2, 2, 128, 64, 64, 64, False),   # bidirectional (encoder)
        (2, 4, 4, 128, 128, 128, 128, True), # single block
    ],
)
def test_flash_attention_shapes(b, hq, hkv, s, dh, bq, bk, causal):
    q = jnp.asarray(RNG.standard_normal((b, hq, s, dh)).astype(np.float32))
    k = jnp.asarray(RNG.standard_normal((b, hkv, s, dh)).astype(np.float32))
    v = jnp.asarray(RNG.standard_normal((b, hkv, s, dh)).astype(np.float32))
    got = flash_attention(q, k, v, causal=causal, bq=bq, bk=bk, interpret=True)
    want = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_bf16():
    q = jnp.asarray(RNG.standard_normal((1, 2, 128, 64))).astype(jnp.bfloat16)
    k = jnp.asarray(RNG.standard_normal((1, 2, 128, 64))).astype(jnp.bfloat16)
    v = jnp.asarray(RNG.standard_normal((1, 2, 128, 64))).astype(jnp.bfloat16)
    got = flash_attention(q, k, v, causal=True, bq=64, bk=64, interpret=True)
    want = attention_ref(q, k, v, causal=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, dtype=np.float32), np.asarray(want, dtype=np.float32),
        rtol=0.05, atol=0.05,
    )


def test_chunked_attention_matches_ref_cross_lengths():
    # decode-like: q shorter than kv
    q = jnp.asarray(RNG.standard_normal((1, 4, 64, 32)).astype(np.float32))
    k = jnp.asarray(RNG.standard_normal((1, 2, 256, 32)).astype(np.float32))
    v = jnp.asarray(RNG.standard_normal((1, 2, 256, 32)).astype(np.float32))
    got = chunked_attention(q, k, v, causal=False, block=64)
    want = attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_attention_dispatcher():
    q = jnp.asarray(RNG.standard_normal((1, 2, 128, 32)).astype(np.float32))
    k = jnp.asarray(RNG.standard_normal((1, 2, 128, 32)).astype(np.float32))
    v = jnp.asarray(RNG.standard_normal((1, 2, 128, 32)).astype(np.float32))
    for impl in ("pallas", "chunked", "ref"):
        out = attention(q, k, v, causal=True, impl=impl, interpret=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(attention_ref(q, k, v, causal=True)),
            rtol=2e-5, atol=2e-5,
        )
