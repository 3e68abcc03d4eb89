"""Per-kernel allclose sweeps vs the pure-jnp oracles (interpret=True)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # property tests degrade to skips
from hypothesis import given, settings, strategies as st

import repro.kernels as K
from repro.kernels import ref as R


def _rand(key, shape, dtype):
    x = jax.random.normal(jax.random.key(key), shape, jnp.float32)
    return x.astype(dtype)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


# -- gemm ----------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("mnk", [(32, 64, 32), (64, 64, 64), (128, 128, 64)])
def test_gemm_variants(dtype, mnk):
    m, n, k = mnk
    a, b = _rand(0, (m, k), dtype), _rand(1, (k, n), dtype)
    want = R.gemm_ref(a, b).astype(jnp.float32)
    tol = TOL[dtype] * k
    got = K.gemm.gemm_v00(a, b, interpret=True).astype(jnp.float32)
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)
    got = K.gemm.gemm_v01(a, b, bm=8, interpret=True).astype(jnp.float32)
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)
    got = K.gemm.gemm_v02(a, b, bm=32, bn=32, bk=32, interpret=True).astype(jnp.float32)
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)


# -- flash attention -------------------------------------------------------------


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("s,d,bq,bkv", [(128, 32, 64, 64), (256, 64, 128, 64)])
def test_flash_kernel(causal, s, d, bq, bkv):
    q = _rand(0, (4, s, d), jnp.float32)
    k = _rand(1, (4, s, d), jnp.float32)
    v = _rand(2, (4, s, d), jnp.float32)
    got = K.flash.flash_attention(q, k, v, causal=causal, bq=bq, bkv=bkv, interpret=True)
    want = R.flash_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-4)


def test_flash_kernel_bf16():
    q = _rand(0, (2, 128, 32), jnp.bfloat16)
    k = _rand(1, (2, 128, 32), jnp.bfloat16)
    v = _rand(2, (2, 128, 32), jnp.bfloat16)
    got = K.flash.flash_attention(q, k, v, causal=True, bq=64, bkv=64, interpret=True)
    want = R.flash_ref(q, k, v, causal=True)
    np.testing.assert_allclose(
        got.astype(jnp.float32), want.astype(jnp.float32), atol=3e-2, rtol=3e-2
    )


# -- ssd ------------------------------------------------------------------------


@pytest.mark.parametrize("l,p,n", [(16, 8, 4), (32, 16, 8), (64, 64, 16)])
def test_ssd_chunk_kernel(l, p, n):
    bh, c = 3, 4
    x = _rand(0, (bh, c, l, p), jnp.float32)
    a = -jnp.abs(_rand(1, (bh, c, l), jnp.float32)) * 0.4
    bm = _rand(2, (bh, c, l, n), jnp.float32)
    cm = _rand(3, (bh, c, l, n), jnp.float32)
    y, s = K.ssd.ssd_chunk(x, a, bm, cm, interpret=True)
    y2, s2 = R.ssd_chunk_ref(x, a, bm, cm)
    np.testing.assert_allclose(y, y2, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(s, s2, atol=1e-4, rtol=1e-4)


# -- spmv -------------------------------------------------------------------------


@given(
    r=st.sampled_from([8, 32, 64]),
    k=st.sampled_from([4, 16, 33]),
)
@settings(max_examples=10, deadline=None)
def test_spmv_sweep(r, k):
    vals = _rand(0, (r, k), jnp.float32)
    xg = _rand(1, (r, k), jnp.float32)
    got = K.spmv.spmv_ell(vals, xg, br=8, interpret=True)
    np.testing.assert_allclose(got, R.spmv_ref(vals, xg), atol=1e-5, rtol=1e-4)


def test_spmv_csr_end_to_end(rng):
    """ELL kernel vs a scipy-style CSR oracle on a random sparse matrix."""
    n, nnz_per_row = 64, 6
    row_offsets = np.arange(0, (n + 1) * nnz_per_row, nnz_per_row).astype(np.int32)
    col_indices = rng.integers(0, n, size=n * nnz_per_row).astype(np.int32)
    values = rng.normal(size=n * nnz_per_row).astype(np.float32)
    x = rng.normal(size=n).astype(np.float32)
    idx, val = K.spmv.csr_to_ell(row_offsets, col_indices, values, n)
    xg = x[idx]
    got = K.spmv.spmv_ell(jnp.asarray(val), jnp.asarray(xg), br=8, interpret=True)
    want = R.spmv_csr_ref(row_offsets, col_indices, values, x)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


# -- ttm ---------------------------------------------------------------------------


@pytest.mark.parametrize("use_scratch", [False, True])
@pytest.mark.parametrize("f,nf,r", [(16, 8, 32), (32, 4, 64)])
def test_ttm(use_scratch, f, nf, r):
    vals = _rand(0, (f, nf), jnp.float32)
    ur = _rand(1, (f, nf, r), jnp.float32)
    got = K.ttm.ttm(vals, ur, use_scratch=use_scratch, interpret=True)
    np.testing.assert_allclose(got, R.ttm_ref(vals, ur), atol=1e-5, rtol=1e-4)


# -- gramschm ---------------------------------------------------------------------


@pytest.mark.parametrize("k", [0, 3, 31])
def test_gramschm_k3(k):
    q = _rand(0, (64, 32), jnp.float32)
    a = _rand(1, (64, 256), jnp.float32)
    want = R.gramschm_k3_ref(q, a, k)
    got_naive = K.gramschm.gramschm_k3_naive(q, a, k, interpret=True)
    got_opt = K.gramschm.gramschm_k3_opt(q.T, a, k, interpret=True)
    np.testing.assert_allclose(got_naive, want, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(got_opt, want, atol=1e-4, rtol=1e-4)


# -- histogram ---------------------------------------------------------------------


@pytest.mark.parametrize("variant", ["naive", "opt", "opt2"])
def test_histogram(variant):
    cells = jax.random.randint(jax.random.key(0), (4096,), 0, 64)
    fn = {"naive": K.histogram.hist_naive, "opt": K.histogram.hist_opt,
          "opt2": K.histogram.hist_opt2}[variant]
    got = fn(cells, 64, interpret=True)
    np.testing.assert_allclose(got, R.hist_ref(cells, 64), atol=0, rtol=0)


# -- gmm -----------------------------------------------------------------------------


@pytest.mark.parametrize("groups", [[100, 28, 0, 130], [64, 64, 64, 64], [0, 0, 5, 1]])
def test_gmm_vs_plan(groups):
    gs = np.asarray(groups)
    row_map, tile_ids, mp = K.gmm.plan_groups(gs, bm=32)
    x = _rand(0, (mp, 64), jnp.float32)
    w = _rand(1, (len(gs), 64, 48), jnp.float32)
    got = K.gmm.gmm(x, w, jnp.asarray(tile_ids), bm=32, interpret=True)
    want = K.gmm.gmm_ref(x, w, tile_ids, bm=32)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-4)


def test_gmm_matches_ragged_dot():
    gs = np.asarray([32, 64, 32])
    row_map, tile_ids, mp = K.gmm.plan_groups(gs, bm=32)
    assert mp == 128  # already tile multiples
    x = _rand(0, (128, 32), jnp.float32)
    w = _rand(1, (3, 32, 16), jnp.float32)
    got = K.gmm.gmm(x, w, jnp.asarray(tile_ids), bm=32, interpret=True)
    want = R.gmm_ragged_ref(x, w, jnp.asarray(gs, np.int32))
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-4)
