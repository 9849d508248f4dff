"""Tests for the beyond-deliverable extensions: serving driver, secure
normalization, and the KS-adder kernel."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import protocol as P
from repro.core import ring
from repro.core.normalize import (normalize_horizontal, normalize_local,
                                  secure_minmax)
from repro.core.sharing import rec_real


# ---------------------------------------------------------------------------
# serving driver
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["granite-34b", "rwkv6-1.6b",
                                  "deepseek-v2-236b"])
def test_serve_driver(arch):
    from repro.launch.serve import serve
    out = serve(arch, reduced=True, batch=2, prompt_len=8, gen=6,
                verbose=False)
    assert out["finite"]
    assert out["tokens"].shape == (2, 6)
    # greedy decode of a fixed model+prompt is deterministic
    out2 = serve(arch, reduced=True, batch=2, prompt_len=8, gen=6,
                 verbose=False)
    np.testing.assert_array_equal(out["tokens"], out2["tokens"])


# ---------------------------------------------------------------------------
# secure joint normalization
# ---------------------------------------------------------------------------

def test_normalize_local_bounds():
    rng = np.random.default_rng(0)
    x = rng.normal(3, 17, (50, 4))
    z = normalize_local(x)
    assert z.min() >= 0 and z.max() <= 1 + 1e-9


def test_secure_minmax_matches_plain():
    rng = np.random.default_rng(1)
    xa, xb = rng.normal(0, 5, (40, 6)), rng.normal(2, 3, (25, 6))
    ctx = P.make_ctx(0)
    g_min, g_max = secure_minmax(ctx, xa, xb, rng)
    full = np.vstack([xa, xb])
    np.testing.assert_allclose(np.asarray(rec_real(g_min)), full.min(0),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(rec_real(g_max)), full.max(0),
                               atol=1e-4)


def test_normalize_horizontal_end_to_end():
    rng = np.random.default_rng(2)
    xa, xb = rng.normal(0, 5, (30, 3)), rng.normal(1, 9, (20, 3))
    ctx = P.make_ctx(1)
    za, zb = normalize_horizontal(ctx, xa, xb, rng)
    z = np.vstack([za, zb])
    assert z.min() >= -1e-3 and z.max() <= 1 + 1e-3
    ref = normalize_local(np.vstack([xa, xb]))
    np.testing.assert_allclose(z, ref, atol=1e-3)


# ---------------------------------------------------------------------------
# KS-adder kernel == protocol.msb_carry local pieces
# ---------------------------------------------------------------------------

def test_ks_carry_kernel_matches_protocol():
    """Run the sequential band-by-band Kogge-Stone adder (the seed
    formulation of msb_carry) as the oracle, recording each level's
    exchanged masks and triples, then verify (a) the fused kernel reproduces
    both parties' final carry shares, and (b) protocol.msb_carry — which now
    dispatches the same fused recombination through the ring backend —
    extracts the identical MSB from identical dealer randomness."""
    from repro.core.channel import CommLog
    from repro.core.sharing import BShare, rec_b, share
    from repro.core.triples import TrustedDealer
    from repro.kernels.ksadder import (LEVELS, from_lanes, ks_carry_share,
                                       to_lanes)

    rng = np.random.default_rng(3)
    n, m = 16, 128
    vals = rng.integers(-(2 ** 40), 2 ** 40, (n, m))
    sh = share(vals.astype(np.int64).astype(np.uint64), rng)

    dealer = TrustedDealer(seed=9)
    state = []  # one (e, f, triple) per AND level

    def band_ref(x: BShare, y: BShare) -> BShare:
        t = dealer.bin_triple(x.shape)
        e = (x.b0 ^ t.u.b0) ^ (x.b1 ^ t.u.b1)
        f = (y.b0 ^ t.v.b0) ^ (y.b1 ^ t.v.b1)
        state.append((e, f, t))
        z0 = t.z.b0 ^ (t.u.b0 & f) ^ (e & (t.v.b0 ^ f))
        z1 = t.z.b1 ^ (t.u.b1 & f) ^ (e & t.v.b1)
        return BShare(z0, z1)

    x = BShare(sh.s0, jnp.zeros_like(sh.s0))
    y = BShare(jnp.zeros_like(sh.s1), sh.s1)
    g = band_ref(x, y)
    p = BShare(x.b0 ^ y.b0, x.b1 ^ y.b1)
    for s in LEVELS:
        lhs = BShare(jnp.stack([p.b0, p.b0]), jnp.stack([p.b1, p.b1]))
        rhs = BShare(jnp.stack([g.b0 << s, p.b0 << s]),
                     jnp.stack([g.b1 << s, p.b1 << s]))
        both = band_ref(lhs, rhs)
        g = BShare(g.b0 ^ both.b0[0], g.b1 ^ both.b1[0])
        p = BShare(both.b0[1], both.b1[1])

    e0, f0, t0 = state[0]
    el = jnp.stack([lv[0] for lv in state[1:]])
    fl = jnp.stack([lv[1] for lv in state[1:]])
    carries = {}
    for party0 in (True, False):
        ul = jnp.stack([(lv[2].u.b0 if party0 else lv[2].u.b1)
                        for lv in state[1:]])
        vl = jnp.stack([(lv[2].v.b0 if party0 else lv[2].v.b1)
                        for lv in state[1:]])
        zl = jnp.stack([(lv[2].z.b0 if party0 else lv[2].z.b1)
                        for lv in state[1:]])
        # the kernel runs on uint32 lanes: each ring word's two halves
        args = (sh.s0 if party0 else sh.s1, e0, f0,
                t0.u.b0 if party0 else t0.u.b1,
                t0.v.b0 if party0 else t0.v.b1,
                t0.z.b0 if party0 else t0.z.b1, el, fl, ul, vl, zl)
        carries[party0] = from_lanes(ks_carry_share(
            *(to_lanes(a) for a in args), party0=party0))
    # (a) fused kernel == sequential oracle, per party share
    np.testing.assert_array_equal(np.asarray(carries[True], np.uint64),
                                  np.asarray(g.b0, np.uint64))
    np.testing.assert_array_equal(np.asarray(carries[False], np.uint64),
                                  np.asarray(g.b1, np.uint64))
    gw = np.asarray(carries[True] ^ carries[False], np.uint64)
    # msb = p_orig[63] ^ G[62]  (protocol.msb_carry's final extraction)
    p_orig = np.asarray(sh.s0 ^ sh.s1, np.uint64)
    msb = ((p_orig >> 63) & 1) ^ ((gw >> 62) & 1)
    np.testing.assert_array_equal(msb.astype(np.int64),
                                  (vals < 0).astype(np.int64))
    # (b) protocol.msb_carry consumes the same triples in the same order, so
    # an identically-seeded dealer must yield the identical MSB bits
    ctx = P.Ctx(dealer=TrustedDealer(seed=9), log=CommLog())
    want_bit = P.msb_carry(ctx, sh)
    np.testing.assert_array_equal(np.asarray(rec_b(want_bit), np.uint64), msb)
