"""Backend-parity tests: every ring-compute implementation must be
BIT-EXACT in Z_{2^64} — the dispatch layer may change where the arithmetic
runs, never what it computes. Covers the three primitive ops across all
backend pairs (including wraparound-heavy inputs) and full SecureKMeans.fit
under xla vs pallas for all four partition x sparsity combinations."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import protocol as P
from repro.core import ring
from repro.core.backend import (KS_LEVELS, MAX_CONTRACTION, NumpyBackend,
                                PallasBackend, XlaBackend, _digits,
                                get_backend, ring_matmul)
from repro.core.kmeans import KMeansConfig, SecureKMeans
from repro.core.sharing import rec
from repro.core.sparse import CSRMatrix

RNG = np.random.default_rng(42)
BACKENDS = {"xla": XlaBackend(), "pallas": PallasBackend(),
            "numpy": NumpyBackend()}
PAIRS = [("xla", "pallas"), ("xla", "numpy"), ("pallas", "numpy")]


def _wraparound_heavy(shape):
    """Values clustered at the top of the ring so partial products and
    accumulations overflow constantly — the regime where a sloppy
    implementation (float detour, signed overflow) diverges."""
    top = np.uint64(0xFFFFFFFFFFFFFFFF)
    x = RNG.integers(0, 1 << 20, shape, dtype=np.uint64)
    return top - x


# ---------------------------------------------------------------------------
# ring_mm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pair", PAIRS)
@pytest.mark.parametrize("shape", [(64, 32, 16), (100, 37, 9), (1, 5, 1),
                                   (129, 130, 3)])
def test_ring_mm_parity(pair, shape):
    n, d, k = shape
    a = RNG.integers(0, 1 << 64, (n, d), dtype=np.uint64)
    b = RNG.integers(0, 1 << 64, (d, k), dtype=np.uint64)
    b1, b2 = BACKENDS[pair[0]], BACKENDS[pair[1]]
    np.testing.assert_array_equal(np.asarray(b1.ring_mm(a, b), np.uint64),
                                  np.asarray(b2.ring_mm(a, b), np.uint64))


@pytest.mark.parametrize("pair", PAIRS)
def test_ring_mm_parity_wraparound_heavy(pair):
    a = _wraparound_heavy((40, 33))
    b = _wraparound_heavy((33, 7))
    b1, b2 = BACKENDS[pair[0]], BACKENDS[pair[1]]
    got1 = np.asarray(b1.ring_mm(a, b), np.uint64)
    got2 = np.asarray(b2.ring_mm(a, b), np.uint64)
    np.testing.assert_array_equal(got1, got2)
    # sanity vs an exact big-int oracle: every partial product here exceeds
    # 2^64, so a non-wrapping implementation could not land on this value
    i, j = 3, 2
    want = sum(int(a[i, t]) * int(b[t, j]) for t in range(a.shape[1]))
    assert want >= 1 << 64
    assert int(got1[i, j]) == want % (1 << 64)


# The word whose signed base-256 digits are all -128: every digit product
# of it with itself is +2^14, the largest one, so a contraction of K such
# words sums to K * 2^14 — the int32 bound the cascade splits at.
_ALL_MINUS_128 = np.uint64(0x7F7F7F7F7F7F7F80)
_ALL_ONES = np.uint64(0xFFFFFFFFFFFFFFFF)


@pytest.mark.parametrize("word,digits", [(_ALL_ONES, [-1] + [0] * 7),
                                         (_ALL_MINUS_128, [-128] * 8)],
                         ids=["all_ones", "all_digits_-128"])
@pytest.mark.parametrize("kdim", [MAX_CONTRACTION, MAX_CONTRACTION + 1,
                                  2 * MAX_CONTRACTION + 3])
def test_limb_cascade_exact_at_contraction_bound(word, digits, kdim):
    """The cascade against the host reference at the contraction bound and
    past it, where the split must take over: without the split the
    all -128 words overflow the int32 partial product at K = bound + 1."""
    a = np.full((2, kdim), word, np.uint64)
    a[1, ::7] = RNG.integers(0, 1 << 64, a[1, ::7].shape, dtype=np.uint64)
    b = np.full((kdim, 3), word, np.uint64)
    b[::5, 2] = RNG.integers(0, 1 << 64, b[::5, 2].shape, dtype=np.uint64)
    np.testing.assert_array_equal(
        np.asarray(ring_matmul(a, b), np.uint64),
        BACKENDS["numpy"].ring_mm(a, b))
    assert np.asarray(_digits(np.array([word])))[:, 0].tolist() == digits


def test_limb_cascade_batched_matches_numpy():
    """Leading batch dims (the ELL spmm's (nrb, maxb) tiles) contract per
    batch entry, like np.matmul."""
    a = _wraparound_heavy((3, 2, 5, 17))
    b = _wraparound_heavy((3, 2, 17, 4))
    want = np.einsum("rbmi,rbik->rbmk", a, b, dtype=np.uint64,
                     casting="unsafe")
    np.testing.assert_array_equal(np.asarray(ring_matmul(a, b), np.uint64),
                                  want)


# ---------------------------------------------------------------------------
# ring_spmm (blocked-ELL and CSR entry points)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pair", PAIRS)
@pytest.mark.parametrize("sparsity", [0.0, 0.7, 0.97])
def test_ring_spmm_parity(pair, sparsity):
    n, d, k = 52, 300, 5
    mask = RNG.random((n, d)) >= sparsity
    x = _wraparound_heavy((n, d)) * mask
    csr = CSRMatrix.from_dense(x.astype(np.uint64))
    y = _wraparound_heavy((d, k))
    b1, b2 = BACKENDS[pair[0]], BACKENDS[pair[1]]
    got1 = np.asarray(b1.ring_spmm_csr(csr, y), np.uint64)
    got2 = np.asarray(b2.ring_spmm_csr(csr, y), np.uint64)
    np.testing.assert_array_equal(got1, got2)
    want = np.einsum("ij,jk->ik", x.astype(np.uint64), y,
                     dtype=np.uint64, casting="unsafe")
    np.testing.assert_array_equal(got1, want)


@pytest.mark.parametrize("pair", PAIRS)
def test_ring_spmm_ell_op_parity(pair):
    """The blocked-ELL op itself (xla/numpy use it as the pallas kernel's
    oracle; ring_spmm_csr on host backends takes the chunked CSR path)."""
    from repro.kernels.spmm import csr_to_ell
    n, d, k = 24, 300, 4
    x = _wraparound_heavy((n, d)) * (RNG.random((n, d)) >= 0.8)
    csr = CSRMatrix.from_dense(x.astype(np.uint64))
    blocks, idx, counts = csr_to_ell(csr.indptr, csr.indices, csr.data,
                                     csr.shape)
    y = np.pad(_wraparound_heavy((d, k)), ((0, (-d) % 128), (0, 0)))
    b1, b2 = BACKENDS[pair[0]], BACKENDS[pair[1]]
    got1 = np.asarray(b1.ring_spmm(blocks, idx, counts, y), np.uint64)[:n]
    got2 = np.asarray(b2.ring_spmm(blocks, idx, counts, y), np.uint64)[:n]
    np.testing.assert_array_equal(got1, got2)
    want = np.einsum("ij,jk->ik", x.astype(np.uint64), y[:d],
                     dtype=np.uint64, casting="unsafe")
    np.testing.assert_array_equal(got1, want)


def test_ring_spmm_empty_matrix():
    csr = CSRMatrix.from_dense(np.zeros((10, 40), np.uint64))
    y = RNG.integers(0, 1 << 64, (40, 3), dtype=np.uint64)
    for bk in BACKENDS.values():
        got = np.asarray(bk.ring_spmm_csr(csr, y), np.uint64)
        np.testing.assert_array_equal(got, np.zeros((10, 3), np.uint64))


# ---------------------------------------------------------------------------
# ks_fused
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pair", PAIRS)
@pytest.mark.parametrize("shape", [(16, 8), (3,), (1, 1), ()])
def test_ks_fused_parity(pair, shape):
    def draw(s):
        return jnp.asarray(RNG.integers(0, 1 << 64, s, dtype=np.uint64))

    flat = [draw(shape) for _ in range(6)]
    lvls = [draw((len(KS_LEVELS), 2) + shape) for _ in range(5)]
    b1, b2 = BACKENDS[pair[0]], BACKENDS[pair[1]]
    for party0 in (True, False):
        got1 = np.asarray(b1.ks_fused(*flat, *lvls, party0=party0), np.uint64)
        got2 = np.asarray(b2.ks_fused(*flat, *lvls, party0=party0), np.uint64)
        np.testing.assert_array_equal(got1, got2)


# ---------------------------------------------------------------------------
# backend resolution
# ---------------------------------------------------------------------------

def test_get_backend_resolution():
    assert get_backend("xla").name == "xla"
    assert get_backend("pallas").name == "pallas"
    assert get_backend("numpy").name == "numpy"
    assert get_backend(None).name == "xla"     # auto, on every platform
    assert get_backend("auto").name == "xla"
    inst = XlaBackend()
    assert get_backend(inst) is inst
    with pytest.raises(ValueError):
        get_backend("cuda")


def test_ctx_carries_backend():
    ctx = P.make_ctx(0, backend="pallas")
    assert ctx.backend.name == "pallas"
    assert P.make_ctx(0).backend.name == "xla"


# ---------------------------------------------------------------------------
# end-to-end: SecureKMeans.fit bit-exact across backends
# ---------------------------------------------------------------------------

def _blobs(n, d, k, seed, sparse_frac=0.0):
    rng = np.random.default_rng(seed)
    centers = rng.uniform(-4, 4, (k, d))
    lab = rng.integers(0, k, n)
    x = centers[lab] + rng.normal(0, 0.3, (n, d))
    if sparse_frac:
        x = x * (rng.random((n, d)) >= sparse_frac)
    return x


@pytest.mark.parametrize("partition", ["vertical", "horizontal"])
@pytest.mark.parametrize("sparse", [False, True])
def test_fit_bit_exact_xla_vs_pallas(partition, sparse):
    """The whole secure pipeline — distances, tournament argmin, centroid
    update — must produce IDENTICAL shares under either compute backend:
    same seed means same dealer randomness, and the local ring algebra is
    exact, so even the final uint64 share words must agree bit for bit."""
    n, d, k = 48, 4, 2
    x = _blobs(n, d, k, seed=11, sparse_frac=0.5 if sparse else 0.0)
    if partition == "vertical":
        a, b = x[:, :2], x[:, 2:]
    else:
        a, b = x[:24], x[24:]
    results = {}
    for backend in ("xla", "pallas"):
        cfg = KMeansConfig(k=k, iters=2, partition=partition, sparse=sparse,
                           seed=5, backend=backend)
        results[backend] = SecureKMeans(cfg).fit(a, b)
    rx, rp = results["xla"], results["pallas"]
    np.testing.assert_array_equal(np.asarray(rec(rx.centroids), np.uint64),
                                  np.asarray(rec(rp.centroids), np.uint64))
    np.testing.assert_array_equal(np.asarray(rec(rx.assignment), np.uint64),
                                  np.asarray(rec(rp.assignment), np.uint64))
    np.testing.assert_array_equal(rx.labels_plain(), rp.labels_plain())
    # traffic accounting must be backend-independent
    assert rx.log.total_bytes("online") == rp.log.total_bytes("online")
    assert rx.log.total_rounds("online") == rp.log.total_rounds("online")


# ---------------------------------------------------------------------------
# KMeansConfig validation (regression: iters=0 used to crash fit with an
# UnboundLocalError deep in the loop)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("iters", [0, -3])
def test_config_rejects_nonpositive_iters(iters):
    with pytest.raises(ValueError, match="iters"):
        KMeansConfig(k=3, iters=iters)
