"""System tests: secure K-means vs plaintext oracle; Protocol 2; HE; fraud."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import protocol as P
from repro.core import ring
from repro.core.he import Paillier, SimulatedPHE
from repro.core.kmeans import (KMeansConfig, SecureKMeans, plaintext_kmeans)
from repro.core.fraud import (FraudDataset, jaccard, run_plaintext_fraud,
                              run_secure_fraud)
from repro.core.sharing import AShare, rec, share
from repro.core.sparse import (CSRMatrix, dense_ss_matmul_comm_bytes,
                               secure_sparse_matmul, sparse_matmul_comm_bytes)


def make_blobs(n, d, k, seed=0, sparse_frac=0.0):
    rng = np.random.default_rng(seed)
    centers = rng.uniform(-4, 4, (k, d))
    lab = rng.integers(0, k, n)
    x = centers[lab] + rng.normal(0, 0.4, (n, d))
    if sparse_frac:
        x = x * (rng.random((n, d)) >= sparse_frac)
    return x


def _match_labels(sec, ref, k):
    """Accuracy up to cluster permutation (greedy matching)."""
    best = 0.0
    from itertools import permutations
    for perm in permutations(range(k)):
        best = max(best, (np.asarray(perm)[sec] == ref).mean())
    return best


# ---------------------------------------------------------------------------
# secure == plaintext (both partitions, dense + sparse)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("partition", ["vertical", "horizontal"])
def test_secure_matches_plaintext(partition):
    n, d, k = 240, 6, 3
    x = make_blobs(n, d, k, seed=1)
    if partition == "vertical":
        a, b = x[:, :3], x[:, 3:]
    else:
        a, b = x[:120], x[120:]
    res = SecureKMeans(KMeansConfig(k=k, iters=8, partition=partition,
                                    seed=3)).fit(a, b)
    _, lab_ref = plaintext_kmeans(x, k, 8, seed=3)
    assert (res.labels_plain() == lab_ref).mean() > 0.99


def test_sparse_path_matches_dense_path():
    x = make_blobs(150, 8, 3, seed=2, sparse_frac=0.6)
    a, b = x[:, :4], x[:, 4:]
    dense = SecureKMeans(KMeansConfig(k=3, iters=6, seed=5)).fit(a, b)
    sparse = SecureKMeans(KMeansConfig(k=3, iters=6, seed=5,
                                       sparse=True)).fit(a, b)
    assert (dense.labels_plain() == sparse.labels_plain()).mean() > 0.99
    np.testing.assert_allclose(dense.centroids_plain(),
                               sparse.centroids_plain(), atol=1e-3)


def test_sparse_real_paillier_end_to_end():
    x = make_blobs(30, 6, 2, seed=3, sparse_frac=0.5)
    res = SecureKMeans(KMeansConfig(k=2, iters=3, seed=7, sparse=True,
                                    he_backend=Paillier(512))
                       ).fit(x[:, :3], x[:, 3:])
    _, lab_ref = plaintext_kmeans(x, 2, 3, seed=7)
    assert (res.labels_plain() == lab_ref).mean() > 0.95


def test_convergence_early_stop():
    x = make_blobs(200, 4, 3, seed=4)
    res = SecureKMeans(KMeansConfig(k=3, iters=50, seed=5, tol=1e-6)
                       ).fit(x[:, :2], x[:, 2:])
    assert res.iters_run < 50


def test_empty_cluster_guard():
    """k > distinct points forces empty clusters; centroids must stay finite
    (secure CMP+MUX keeps the previous centroid)."""
    rng = np.random.default_rng(0)
    x = np.repeat(rng.uniform(-1, 1, (2, 4)), 20, axis=0)  # only 2 points
    res = SecureKMeans(KMeansConfig(k=5, iters=4, seed=1)).fit(x[:, :2], x[:, 2:])
    mu = res.centroids_plain()
    assert np.isfinite(mu).all()
    assert np.abs(mu).max() < 100.0


# ---------------------------------------------------------------------------
# communication properties (the paper's actual claims)
# ---------------------------------------------------------------------------

def test_online_offline_split_dominated_by_offline():
    """Fig 2: offline (triple generation) must dominate total traffic."""
    x = make_blobs(400, 4, 4, seed=6)
    res = SecureKMeans(KMeansConfig(k=4, iters=5, seed=2)).fit(x[:, :2], x[:, 2:])
    assert res.log.total_bytes("offline") > 5 * res.log.total_bytes("online")


def test_vectorized_rounds_much_smaller():
    """Fig 3: vectorization cuts rounds by orders of magnitude (same bytes)."""
    x = make_blobs(100, 6, 4, seed=7)
    vec = SecureKMeans(KMeansConfig(k=4, iters=2, seed=2)).fit(x[:, :3], x[:, 3:])
    nai = SecureKMeans(KMeansConfig(k=4, iters=2, seed=2,
                                    vectorized=False)).fit(x[:, :3], x[:, 3:])
    assert nai.log.total_rounds("online") > 20 * vec.log.total_rounds("online")
    assert nai.log.total_bytes("online") == vec.log.total_bytes("online")


def test_sparse_comm_beats_dense_at_high_dim():
    """Sec 4.3: Protocol 2 traffic independent of n*d; dense SS is not."""
    n, k = 4096, 4
    for d in (1 << 12, 1 << 14):
        p2 = sparse_matmul_comm_bytes(n, d, k)
        ss = dense_ss_matmul_comm_bytes(n, d, k)
        assert p2 < ss, (d, p2, ss)
    # and the crossover exists: tiny d favours dense SS
    assert sparse_matmul_comm_bytes(64, 2, 2) > dense_ss_matmul_comm_bytes(64, 2, 2)


# ---------------------------------------------------------------------------
# Protocol 2 property tests
# ---------------------------------------------------------------------------

@given(st.integers(2, 10), st.integers(2, 10), st.integers(1, 4),
       st.floats(0.0, 0.9))
@settings(deadline=None, max_examples=15)
def test_protocol2_random_shapes(n, d, k, sparsity):
    rng = np.random.default_rng(int(n * 1000 + d * 100 + k))
    xr = rng.uniform(-3, 3, (n, d)) * (rng.random((n, d)) >= sparsity)
    x = CSRMatrix.from_dense_real(xr)
    y_plain = rng.uniform(-3, 3, (d, k))
    ys = share(np.round(y_plain * (1 << ring.F)).astype(np.int64)
               .astype(np.uint64), rng)
    ctx = P.make_ctx(0)
    z = secure_sparse_matmul(ctx, x, np.asarray(ys.s1), SimulatedPHE())
    local = np.asarray(x.to_dense(), np.uint64) @ np.asarray(ys.s0)
    tot = AShare(z.s0 + local, z.s1)
    got = np.asarray(ring.decode(rec(P.trunc(tot, ring.F))))
    np.testing.assert_allclose(got, xr @ y_plain, atol=1e-3)


def test_protocol2_paillier_matches_simulated():
    rng = np.random.default_rng(9)
    xr = rng.uniform(-2, 2, (5, 7)) * (rng.random((5, 7)) > 0.5)
    x = CSRMatrix.from_dense_real(xr)
    yb = rng.integers(0, 1 << 63, (7, 3)).astype(np.uint64)
    for he in (SimulatedPHE(), Paillier(512)):
        z = secure_sparse_matmul(P.make_ctx(1), x, yb, he)
        want = np.asarray(x.to_dense(), np.uint64) @ yb
        got = np.asarray(rec(z), np.uint64)
        np.testing.assert_array_equal(got, want)


def test_paillier_homomorphism():
    he = Paillier(512)
    a, b, s = 123456789, 987654321, 42
    ct = he.encrypt(a) + he.encrypt(b)
    assert he.decrypt(ct) == a + b
    assert he.decrypt(s * he.encrypt(a)) == s * a
    # fresh randomness: same plaintext, different ciphertext
    assert he.encrypt(a).c != he.encrypt(a).c


def test_csr_roundtrip():
    rng = np.random.default_rng(10)
    x = (rng.random((13, 9)) > 0.6) * rng.integers(1, 100, (13, 9))
    m = CSRMatrix.from_dense(x.astype(np.uint64))
    np.testing.assert_array_equal(m.to_dense(), x.astype(np.uint64))
    assert m.nnz == (x != 0).sum()


def test_csr_transpose_matches_from_dense():
    """nnz-proportional transpose is layout-identical to densify+rebuild
    (the fast path pre-transposes X for the C^T X host exchange with it)."""
    rng = np.random.default_rng(11)
    for shape in [(13, 9), (1, 7), (8, 1), (6, 6)]:
        x = ((rng.random(shape) > 0.5)
             * rng.integers(1, 2**62, shape)).astype(np.uint64)
        a = CSRMatrix.from_dense(x).transpose()
        b = CSRMatrix.from_dense(x.T)
        assert a.shape == b.shape
        np.testing.assert_array_equal(a.indptr, b.indptr)
        np.testing.assert_array_equal(a.indices, b.indices)
        np.testing.assert_array_equal(a.data, b.data)


# ---------------------------------------------------------------------------
# fraud detection (Q5)
# ---------------------------------------------------------------------------

def test_fraud_jaccard_joint_beats_single_party():
    ds = FraudDataset.synthesize(n=800, d_a=6, d_b=8, seed=1)
    j_secure, _ = run_secure_fraud(ds, k=5, iters=6, seed=2)
    j_single = run_plaintext_fraud(ds, k=5, iters=6, seed=2, party_a_only=True)
    j_joint = run_plaintext_fraud(ds, k=5, iters=6, seed=2)
    assert j_secure > j_single          # paper: joint modelling wins
    assert abs(j_secure - j_joint) < 0.15  # secure ~ plaintext joint


def test_jaccard_bounds():
    r = np.zeros(10, bool); r[:3] = True
    assert jaccard(r, r) == 1.0
    assert jaccard(r, ~r) == 0.0
