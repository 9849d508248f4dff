"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (interpret
mode executes the kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.spmm import dense_to_ell

RNG = np.random.default_rng(7)


# ---------------------------------------------------------------------------
# modmatmul: ring matmul sweeps
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype,bits", [(np.uint32, 32), (np.uint64, 64)])
@pytest.mark.parametrize("shape", [(128, 128, 128), (256, 384, 128),
                                   (100, 50, 30), (1, 200, 7), (129, 129, 129)])
def test_ring_matmul_sweep(dtype, bits, shape):
    n, d, k = shape
    a = RNG.integers(0, 1 << bits, (n, d), dtype=dtype)
    b = RNG.integers(0, 1 << bits, (d, k), dtype=dtype)
    got = np.asarray(ops.ring_matmul(jnp.asarray(a), jnp.asarray(b)))
    fn = ref.modmatmul_u32 if bits == 32 else ref.modmatmul_u64
    want = np.asarray(fn(jnp.asarray(a), jnp.asarray(b)))
    np.testing.assert_array_equal(got, want)


@given(st.integers(1, 300), st.integers(1, 300), st.integers(1, 100))
@settings(deadline=None, max_examples=8)
def test_ring_matmul_property(n, d, k):
    a = RNG.integers(0, 1 << 64, (n, d), dtype=np.uint64)
    b = RNG.integers(0, 1 << 64, (d, k), dtype=np.uint64)
    got = np.asarray(ops.ring_matmul(jnp.asarray(a), jnp.asarray(b)))
    want = np.asarray(ref.modmatmul_u64(jnp.asarray(a), jnp.asarray(b)))
    np.testing.assert_array_equal(got, want)


def test_ring_matmul_matches_beaver_semantics():
    """The kernel must be a drop-in for the protocol's jnp ring matmul."""
    from repro.core import ring
    a = RNG.integers(0, 1 << 64, (64, 32), dtype=np.uint64)
    b = RNG.integers(0, 1 << 64, (32, 16), dtype=np.uint64)
    got = np.asarray(ops.ring_matmul(jnp.asarray(a), jnp.asarray(b)))
    want = np.asarray(jnp.matmul(jnp.asarray(a), jnp.asarray(b)))
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# fused ESD
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(128, 128, 128), (300, 40, 5),
                                   (1000, 2, 2), (57, 129, 17)])
def test_esd_sweep(shape):
    n, d, k = shape
    x = RNG.normal(0, 3, (n, d)).astype(np.float32)
    mu = RNG.normal(0, 3, (k, d)).astype(np.float32)
    got = np.asarray(ops.esd(jnp.asarray(x), jnp.asarray(mu)))
    want = np.asarray(ref.esd(jnp.asarray(x), jnp.asarray(mu)))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-3)


def test_esd_argmin_matches_full_euclidean():
    """Dropping ||x||^2 must not change the argmin (paper Eq. 2)."""
    x = RNG.normal(0, 2, (200, 8)).astype(np.float32)
    mu = RNG.normal(0, 2, (5, 8)).astype(np.float32)
    dprime = np.asarray(ops.esd(jnp.asarray(x), jnp.asarray(mu)))
    full = ((x[:, None, :] - mu[None]) ** 2).sum(-1)
    np.testing.assert_array_equal(dprime.argmin(1), full.argmin(1))


# ---------------------------------------------------------------------------
# argmin one-hot
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(128, 4), (1000, 7), (33, 2), (128, 256)])
def test_argmin_onehot_sweep(shape):
    d = RNG.normal(0, 10, shape).astype(np.float32)
    got = np.asarray(ops.argmin_onehot(jnp.asarray(d)))
    want = np.asarray(ref.argmin_onehot(jnp.asarray(d)))
    np.testing.assert_array_equal(got, want)


def test_argmin_onehot_ties_first_wins():
    d = np.zeros((8, 5), np.float32)  # all ties -> column 0
    got = np.asarray(ops.argmin_onehot(jnp.asarray(d)))
    assert (got[:, 0] == 1).all() and (got[:, 1:] == 0).all()


# ---------------------------------------------------------------------------
# blocked-ELL spmm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sparsity", [0.0, 0.5, 0.9, 0.99])
@pytest.mark.parametrize("dtype", [np.float32, np.uint32])
def test_spmm_sweep(sparsity, dtype):
    n, d, k = 64, 512, 8
    mask = RNG.random((n, d)) >= sparsity
    if dtype == np.float32:
        x = (RNG.normal(0, 2, (n, d)) * mask).astype(np.float32)
        y = RNG.normal(0, 2, (d, k)).astype(np.float32)
    else:
        x = (RNG.integers(0, 1 << 32, (n, d), dtype=np.uint32) * mask)
        y = RNG.integers(0, 1 << 32, (d, k), dtype=np.uint32)
    got = np.asarray(ops.spmm_from_dense(x, jnp.asarray(y)))
    if dtype == np.float32:
        want = x @ y
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-3)
    else:
        want = np.einsum("ij,jk->ik", x.astype(np.uint32), y,
                         dtype=np.uint32, casting="unsafe")
        np.testing.assert_array_equal(got, want)


def test_spmm_ell_oracle_agrees():
    n, d, k = 40, 384, 4
    x = (RNG.normal(0, 1, (n, d)) * (RNG.random((n, d)) > 0.8)).astype(np.float32)
    y = RNG.normal(0, 1, (d, k)).astype(np.float32)
    blocks, idx, counts = dense_to_ell(x)
    want = np.asarray(ref.spmm_ell(jnp.asarray(blocks), jnp.asarray(idx),
                                   jnp.asarray(counts), jnp.asarray(y), n))
    got = np.asarray(ops.spmm(jnp.asarray(blocks), jnp.asarray(idx),
                              jnp.asarray(counts), jnp.asarray(y)))[:n]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


def test_ell_packing_is_nnz_proportional():
    """The storage/compute win: blocks scale with density, not with n*d."""
    n, d = 256, 2048
    dense_blocks = dense_to_ell(np.ones((n, d), np.float32))[0]
    x = np.zeros((n, d), np.float32)
    x[:, :128] = 1.0  # one non-empty block column
    sparse_blocks = dense_to_ell(x)[0]
    assert sparse_blocks.shape[1] * sparse_blocks.shape[0] \
        < dense_blocks.shape[1] * dense_blocks.shape[0] / 8
