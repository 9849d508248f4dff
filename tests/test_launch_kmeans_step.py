"""Parity tests for the pjit-able online Lloyd iteration in
launch/kmeans_step: one jit'd iteration — offline tensors materialized by a
TrustedDealer and fed through the ListDealer, Protocol-2 HE results entering
as share inputs — must agree with the simulated SecureKMeans iteration built
from the class's own _distances / argmin / _update methods."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import protocol as P
from repro.core import ring
from repro.core.he import SimulatedPHE
from repro.core.kmeans import KMeansConfig, SecureKMeans, _encode_np
from repro.core.sharing import AShare, rec, rec_real, share
from repro.core.sparse import CSRMatrix, secure_sparse_matmul
from repro.core.triples import TrustedDealer
from repro.launch.kmeans_step import (materialize_offline,
                                      online_iteration_fn,
                                      record_offline_shapes)

_materialize_offline = materialize_offline  # promoted into launch/kmeans_step


@pytest.mark.parametrize("sparse", [False, True])
def test_online_iteration_matches_secure_kmeans(sparse):
    n, d, k, d_a = 32, 4, 2, 2
    rng = np.random.default_rng(8)
    centers = rng.uniform(-4, 4, (k, d))
    x = centers[rng.integers(0, k, n)] + rng.normal(0, 0.2, (n, d))
    if sparse:
        x = x * (rng.random((n, d)) >= 0.4)
    x_a, x_b = x[:, :d_a], x[:, d_a:]
    enc_a, enc_b = _encode_np(x_a, ring.F), _encode_np(x_b, ring.F)
    csr_a = CSRMatrix.from_dense(enc_a) if sparse else None
    csr_b = CSRMatrix.from_dense(enc_b) if sparse else None
    mu0 = share(_encode_np(x[rng.choice(n, k, replace=False)], ring.F), rng)

    # ---- reference: one iteration through SecureKMeans's own methods -----
    skm = SecureKMeans(KMeansConfig(k=k, iters=1, sparse=sparse, seed=0))
    ctx = P.make_ctx(17)
    dist = skm._distances(ctx, enc_a, enc_b, csr_a, csr_b, mu0)
    c_ref = P.argmin_onehot(ctx, dist)
    mu_ref = skm._update(ctx, enc_a, enc_b, csr_a, csr_b, c_ref, mu0, n)

    # ---- pjit path: offline tensors in, one jit'd iteration --------------
    fn, _args = online_iteration_fn(n, d, k, d_a, sparse=sparse)
    dealer = TrustedDealer(seed=23)
    flat = _materialize_offline(
        record_offline_shapes(n, d, k, d_a, sparse=sparse), dealer)
    he_flat = []
    if sparse:
        # Protocol-2 joint products (core/kmeans orientation conventions).
        # j1/j2 only need mu0, known upfront. ja/jb need the ASSIGNMENT
        # SHARES the iteration itself produces in S2 — in deployment the
        # HE exchange runs mid-iteration on those shares — so capture them
        # with a first eager pass (zero ja/jb cannot influence S1/S2), then
        # feed the matching products to the jit'd run.
        ctx_he = P.make_ctx(99)
        he = SimulatedPHE()
        mut = AShare(mu0.s0.T, mu0.s1.T)
        j1 = secure_sparse_matmul(ctx_he, csr_a, np.asarray(mut.s1[:d_a]), he)
        z2 = secure_sparse_matmul(ctx_he, csr_b, np.asarray(mut.s0[d_a:]), he)
        j2 = AShare(z2.s1, z2.s0)
        zero_nk = jnp.zeros((k, d_a), ring.DTYPE)
        zero_nk2 = jnp.zeros((k, d - d_a), ring.DTYPE)
        probe = [j1.s0, j1.s1, j2.s0, j2.s1,
                 zero_nk, zero_nk, zero_nk2, zero_nk2]
        captured = {}
        orig_argmin = P.argmin_onehot

        def argmin_spy(ctx_, dist_):
            captured["c"] = c = orig_argmin(ctx_, dist_)
            return c

        P.argmin_onehot = argmin_spy
        try:
            fn(jnp.asarray(enc_a), jnp.asarray(enc_b), mu0.s0, mu0.s1,
               *probe, *flat)
        finally:
            P.argmin_onehot = orig_argmin
        ct = AShare(captured["c"].s0.T, captured["c"].s1.T)
        za = secure_sparse_matmul(ctx_he, CSRMatrix.from_dense(enc_a.T),
                                  np.asarray(ct.s1.T), he)
        ja = AShare(za.s0.T, za.s1.T)
        zb = secure_sparse_matmul(ctx_he, CSRMatrix.from_dense(enc_b.T),
                                  np.asarray(ct.s0.T), he)
        jb = AShare(zb.s1.T, zb.s0.T)
        for h in (j1, j2, ja, jb):
            he_flat += [h.s0, h.s1]
    out0, out1 = jax.jit(fn)(jnp.asarray(enc_a), jnp.asarray(enc_b),
                             mu0.s0, mu0.s1, *he_flat, *flat)
    mu_jit = AShare(out0, out1)

    # Same values flow through both paths; only the share/mask randomness
    # differs, so reconstructions agree up to truncation ulps.
    got = np.asarray(rec_real(mu_jit))
    want = np.asarray(rec_real(mu_ref))
    np.testing.assert_allclose(got, want, atol=1e-2)
    assert np.isfinite(got).all()
    # the reference iteration must itself be sane: one-hot rows summing to 1
    oh = np.asarray(rec(c_ref), np.uint64).astype(np.int64)
    assert (oh.sum(1) == 1).all()


@pytest.mark.parametrize("partition", ["vertical", "horizontal"])
@pytest.mark.parametrize("sparse", [False, True])
def test_fit_programs_two_launches_per_iteration(partition, sparse):
    """The pooled fast path runs EVERY partition x sparsity combo as exactly
    two compiled launches per online iteration (S1: distances+argmin, S3:
    update), with the sparse combos' Protocol-2 exchange as a host callback
    between them — no eager fallback, no two-pass trick."""
    import repro.launch.kmeans_step as K
    from repro.core.kmeans import KMeansConfig, SecureKMeans

    n, d, k, iters = 32, 4, 2, 3
    rng = np.random.default_rng(6)
    x = rng.normal(0, 2, (n, d))
    if sparse:
        x = x * (rng.random((n, d)) >= 0.5)
    if partition == "vertical":
        a, b = x[:, :2], x[:, 2:]
    else:
        a, b = x[:16], x[16:]
    cfg = KMeansConfig(k=k, iters=iters, partition=partition, sparse=sparse,
                       seed=5, backend="xla", offline="pooled")
    skm = SecureKMeans(cfg)
    enc_a, enc_b = _encode_np(np.asarray(a), ring.F), _encode_np(np.asarray(b), ring.F)
    progs = K.fit_programs(partition, sparse, enc_a.shape, enc_b.shape, k,
                           backend="xla")
    # same geometry+backend -> the fit must reuse this cached pair; wrap the
    # compiled callables with counters to count actual launches
    calls = {"s1": 0, "s3": 0}

    def wrap(name, fn):
        def counted(*args):
            calls[name] += 1
            return fn(*args)
        return counted

    key = (progs.geo, "xla")
    K._PROGRAM_CACHE[key] = progs._replace(s1=wrap("s1", progs.s1),
                                           s3=wrap("s3", progs.s3))
    try:
        res = skm.fit(a, b)
    finally:
        K._PROGRAM_CACHE[key] = progs
    assert calls == {"s1": iters, "s3": iters}
    assert res.iters_run == iters
    # S1 outputs valid one-hot assignment shares (the S2 callback contract:
    # the host exchange runs on exactly these)
    oh = np.asarray(rec(res.assignment), np.uint64).astype(np.int64)
    assert (oh.sum(1) == 1).all()
    # the sparse programs declare the Protocol-2 inputs; dense ones don't
    assert bool(progs.geo.he_shapes_s1()) == sparse
    assert bool(progs.geo.he_shapes_s3()) == sparse


def test_compiled_programs_carry_stable_names():
    """The device trace tells the programs apart by their module names and
    finds kernels by their name paths: the finalize and predict programs
    have names of their own, every Kogge-Stone recombination sits under
    `ks_fused`, and every ring product keeps `ring_matmul` in its path."""
    import re

    import repro.launch.kmeans_step as K

    fin = K.finalize_program(2, 8, 64, backend="xla").fn.as_text()
    pred = K.predict_program("vertical", False, (16, 4), (16, 4), 2,
                             backend="xla").fn.as_text()
    s1 = K.fit_batch_programs("vertical", False, (16, 4), (16, 4), 2,
                              backend="xla").s1.as_text()
    assert fin.startswith("HloModule jit_finalize_fn")
    assert pred.startswith("HloModule jit_predict_fn")
    for text, fn in ((fin, "finalize_fn"), (pred, "predict_fn"),
                     (s1, "s1_fn")):
        paths = set(re.findall(r'op_name="([^"]*)"', text))
        assert any(p.startswith(f"jit({fn})/ks_fused/") for p in paths)
    for text in (pred, s1):
        dots = re.findall(r'= s32\[[^\]]*\]\S* (?:dot|convolution)\('
                          r'.*op_name="([^"]*)"', text)
        assert dots and all("ring_matmul" in p for p in dots)


def test_fit_geometry_validation():
    from repro.launch.kmeans_step import FitGeometry
    with pytest.raises(ValueError, match="unknown partition"):
        FitGeometry("diagonal", False, (4, 2), (4, 2), 2)
    with pytest.raises(ValueError, match="equal sample counts"):
        FitGeometry("vertical", False, (4, 2), (5, 2), 2)
    with pytest.raises(ValueError, match="equal feature counts"):
        FitGeometry("horizontal", False, (4, 2), (4, 3), 2)


def test_online_iteration_backend_parity():
    """The pjit'd iteration must be bit-exact across ring backends when fed
    the IDENTICAL offline tensors and inputs."""
    n, d, k, d_a = 16, 4, 2, 2
    rng = np.random.default_rng(3)
    x = rng.normal(0, 2, (n, d))
    enc_a, enc_b = _encode_np(x[:, :d_a], ring.F), _encode_np(x[:, d_a:], ring.F)
    mu0 = share(_encode_np(x[:k], ring.F), rng)
    flat = _materialize_offline(record_offline_shapes(n, d, k, d_a),
                                TrustedDealer(seed=5))
    outs = {}
    for backend in ("xla", "pallas"):
        fn, _ = online_iteration_fn(n, d, k, d_a, backend=backend)
        s0, s1 = jax.jit(fn)(jnp.asarray(enc_a), jnp.asarray(enc_b),
                             mu0.s0, mu0.s1, *flat)
        outs[backend] = (np.asarray(s0, np.uint64), np.asarray(s1, np.uint64))
    np.testing.assert_array_equal(outs["xla"][0], outs["pallas"][0])
    np.testing.assert_array_equal(outs["xla"][1], outs["pallas"][1])


@pytest.mark.parametrize("env_dir", [None, "/var/cache/jax-elsewhere"])
def test_entry_point_compile_cache_location(monkeypatch, env_dir):
    """$JAX_COMPILATION_CACHE_DIR wins and is left to JAX; otherwise the
    entry points use one fixed directory at the root of the checkout."""
    import os
    from repro.launch import compile_cache
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    got = compile_cache.use_compile_cache()
    if env_dir is None:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert got == os.path.join(root, ".jax_cache")
        assert calls == [("jax_compilation_cache_dir", got)]
    else:
        assert got == env_dir and calls == []
