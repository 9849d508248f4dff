"""Unit + property tests for the MPC protocol layer (sharing, Beaver ops,
comparison, argmin, reciprocal, truncation)."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import protocol as P
from repro.core import ring
from repro.core.sharing import (AShare, rec, rec_b, rec_real, share, share_b,
                                share_real)

RNG = np.random.default_rng(123)


def _ctx():
    return P.make_ctx(RNG.integers(1 << 30))


# ---------------------------------------------------------------------------
# sharing / fixed point
# ---------------------------------------------------------------------------

@given(st.lists(st.floats(-1e6, 1e6, allow_nan=False), min_size=1, max_size=32))
@settings(deadline=None, max_examples=50)
def test_share_reconstruct_roundtrip(xs):
    x = np.asarray(xs)
    a = share_real(x, np.random.default_rng(0))
    np.testing.assert_allclose(np.asarray(rec_real(a)), x, atol=2.0 ** -ring.F)


@given(st.integers(0, 2 ** 64 - 1))
@settings(deadline=None, max_examples=50)
def test_ring_share_exact(v):
    a = share(np.array([v], np.uint64), np.random.default_rng(1))
    assert int(np.asarray(rec(a))[0]) == v


def test_share_uniformity():
    """Shares of a constant must look uniform (the security property the
    whole protocol rests on): mean of share bytes ~ uniform."""
    rng = np.random.default_rng(7)
    a = share(np.zeros(20000, np.uint64), rng)
    s0 = np.asarray(a.s0)
    # each of the 8 bytes of the share should be ~uniform over [0,256)
    bytes_view = s0.view(np.uint8)
    hist = np.bincount(bytes_view, minlength=256)
    assert hist.min() > 0.8 * hist.mean()
    assert hist.max() < 1.2 * hist.mean()


def test_trunc_error_envelope():
    """SecureML local truncation: trunc(share(x * 2^2f), f) ~ x * 2^f with at
    most one LSB of error per lane."""
    rng = np.random.default_rng(3)
    x = rng.uniform(-1000, 1000, 5000)
    enc2 = np.round(x * (1 << (2 * ring.F))).astype(np.int64).astype(np.uint64)
    sh = share(enc2, rng)
    back = np.asarray(rec_real(P.trunc(sh, ring.F)))
    np.testing.assert_allclose(back, x, atol=2.0 ** -ring.F * 2)


# ---------------------------------------------------------------------------
# SMUL / matmul
# ---------------------------------------------------------------------------

@given(st.integers(1, 12), st.integers(1, 12))
@settings(deadline=None, max_examples=10)
def test_smul_elementwise(n, m):
    rng = np.random.default_rng(n * 100 + m)
    x = rng.uniform(-50, 50, (n, m))
    y = rng.uniform(-50, 50, (n, m))
    z = P.smul(_ctx(), share_real(x, rng), share_real(y, rng), trunc_f=ring.F)
    np.testing.assert_allclose(np.asarray(rec_real(z)), x * y,
                               atol=2.0 ** -ring.F * (np.abs(x).max() + 2))


def test_smul_broadcast():
    rng = np.random.default_rng(0)
    x = rng.uniform(-2, 2, (5, 1))
    y = rng.uniform(-2, 2, (1, 7))
    z = P.smul(_ctx(), share_real(x, rng), share_real(y, rng), trunc_f=ring.F)
    np.testing.assert_allclose(np.asarray(rec_real(z)), x * y, atol=1e-4)


@given(st.integers(1, 10), st.integers(1, 10), st.integers(1, 10))
@settings(deadline=None, max_examples=10)
def test_smatmul(n, d, k):
    rng = np.random.default_rng(n + 10 * d + 100 * k)
    a = rng.uniform(-5, 5, (n, d))
    b = rng.uniform(-5, 5, (d, k))
    z = P.smatmul(_ctx(), share_real(a, rng), share_real(b, rng), trunc_f=ring.F)
    np.testing.assert_allclose(np.asarray(rec_real(z)), a @ b,
                               atol=2.0 ** -ring.F * (d + 2) * 8)


def test_smatmul_comm_accounting():
    ctx = _ctx()
    rng = np.random.default_rng(5)
    a, b = rng.uniform(-1, 1, (64, 32)), rng.uniform(-1, 1, (32, 8))
    P.smatmul(ctx, share_real(a, rng), share_real(b, rng))
    # online: both parties exchange E (64x32) and F (32x8): 2*(nd+dk)*8 bytes
    assert ctx.log.total_bytes("online") == 2 * (64 * 32 + 32 * 8) * 8
    assert ctx.log.total_rounds("online") == 1
    assert ctx.log.total_bytes("offline") > 0  # modelled OT triple traffic


# ---------------------------------------------------------------------------
# boolean layer: MSB / CMP / MUX / B2A
# ---------------------------------------------------------------------------

@given(st.lists(st.integers(-(2 ** 40), 2 ** 40), min_size=1, max_size=64))
@settings(deadline=None, max_examples=30)
def test_msb_matches_sign(vals):
    x = np.asarray(vals, np.int64).astype(np.uint64)
    rng = np.random.default_rng(11)
    b = P.msb_carry(_ctx(), share(x, rng))
    got = np.asarray(rec_b(b)).astype(np.int64)
    want = (np.asarray(vals) < 0).astype(np.int64)
    np.testing.assert_array_equal(got, want)


@given(st.lists(st.floats(-1e4, 1e4, allow_nan=False), min_size=2, max_size=40))
@settings(deadline=None, max_examples=30)
def test_cmp_lt(vals):
    half = len(vals) // 2
    x, y = np.asarray(vals[:half]), np.asarray(vals[half:2 * half])
    if half == 0:
        return
    rng = np.random.default_rng(13)
    c = P.cmp_lt(_ctx(), share_real(x, rng), share_real(y, rng))
    got = np.asarray(rec(c), np.uint64).astype(np.int64)
    enc = lambda v: np.round(v * (1 << ring.F)).astype(np.int64)
    np.testing.assert_array_equal(got, (enc(x) < enc(y)).astype(np.int64))


def test_mux_selects():
    rng = np.random.default_rng(17)
    x, y = rng.uniform(-9, 9, 100), rng.uniform(-9, 9, 100)
    ctx = _ctx()
    z = P.cmp_lt(ctx, share_real(x, rng), share_real(y, rng))
    m = P.mux(ctx, z, share_real(x, rng), share_real(y, rng))
    np.testing.assert_allclose(np.asarray(rec_real(m)), np.minimum(x, y),
                               atol=1e-4)


def test_b2a_bit():
    rng = np.random.default_rng(19)
    bits = rng.integers(0, 2, 200).astype(np.uint64)
    b = share_b(bits, rng)
    a = P.b2a_bit(_ctx(), b)
    np.testing.assert_array_equal(np.asarray(rec(a), np.uint64), bits)


# ---------------------------------------------------------------------------
# argmin / reciprocal
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", [2, 3, 5, 6, 8, 17])
def test_argmin_onehot(k):
    rng = np.random.default_rng(k)
    d = rng.uniform(0, 100, (64, k))
    oh = P.argmin_onehot(_ctx(), share_real(d, rng))
    got = np.asarray(rec(oh), np.uint64).astype(np.int64)
    assert (got.sum(1) == 1).all()
    np.testing.assert_array_equal(got.argmax(1), d.argmin(1))


@given(st.integers(1, 100000))
@settings(deadline=None, max_examples=30)
def test_reciprocal(den):
    rng = np.random.default_rng(29)
    d = share(np.array([den], np.uint64), rng)
    # plain scale-f output: absolute error ~ ulp => relative error ~ den*2^-f
    r = P.reciprocal(_ctx(), d, max_den=100000)
    rel = abs(float(np.asarray(rec_real(r))[0]) * den - 1.0)
    assert rel < max(1e-3, 3 * den * 2.0 ** -ring.F), (den, rel)


@given(st.integers(1, 100000))
@settings(deadline=None, max_examples=30)
def test_reciprocal_extended_precision(den):
    """extra_bits recovers full relative precision for large denominators
    (the centroid-update configuration)."""
    rng = np.random.default_rng(31)
    d = share(np.array([den], np.uint64), rng)
    extra = 17
    r = P.reciprocal(_ctx(), d, max_den=100000, extra_bits=extra)
    val = float(np.asarray(rec(r), np.uint64).astype(np.int64)[0]) \
        / (1 << (ring.F + extra))
    rel = abs(val * den - 1.0)
    assert rel < 1e-4, (den, rel)


def test_rounds_scale_logarithmically_with_k():
    """Vectorization invariant: argmin rounds ~ O(log k), not O(nk)."""
    rounds = {}
    for k in (4, 16, 64):
        ctx = _ctx()
        rng = np.random.default_rng(0)
        P.argmin_onehot(ctx, share_real(rng.uniform(0, 1, (8, k)), rng))
        rounds[k] = ctx.log.total_rounds("online")
    assert rounds[16] <= rounds[4] * 2.1
    assert rounds[64] <= rounds[4] * 3.1
