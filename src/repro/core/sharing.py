"""Additive secret sharing over Z_{2^64} (paper Sec 3.1).

A-shares are pairs (s0, s1) with x = s0 + s1 mod 2^64; B-shares are pairs of
*bit-packed* uint64 words with x = b0 XOR b1 — each tensor element carries its
64 bits in one lane, so bitwise protocol ops are lane-parallel across both the
tensor and the bit dimension.

Both parties' shares live in one process (simulated 2PC); protocol code only
ever combines them at explicit `rec` points which correspond 1:1 to real
communication, accounted in channel.CommLog.
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from repro.core import ring


class AShare(NamedTuple):
    """Arithmetic share: x = s0 + s1 (mod 2^64)."""

    s0: jnp.ndarray
    s1: jnp.ndarray

    @property
    def shape(self):
        return self.s0.shape


class BShare(NamedTuple):
    """Boolean share, bit-packed: x = b0 ^ b1 (64 bits per lane)."""

    b0: jnp.ndarray
    b1: jnp.ndarray

    @property
    def shape(self):
        return self.b0.shape


def share_np(x, rng: np.random.Generator) -> tuple:
    """Shr(x) on the host: the two uniform shares (s0, s1) as numpy words."""
    x = np.asarray(x, np.uint64)
    s0 = ring.rand_np(rng, x.shape)
    return s0, x - s0  # uint64 wraparound == mod 2^64


def share(x, rng: np.random.Generator) -> AShare:
    """Shr(x): split a ring tensor into two uniform shares."""
    s0, s1 = share_np(x, rng)
    return AShare(jnp.asarray(s0), jnp.asarray(s1))


def share_real(x, rng: np.random.Generator, f: int = ring.F) -> AShare:
    """Encode reals to fixed point then share."""
    enc = np.round(np.asarray(x, np.float64) * (1 << f)).astype(np.int64).astype(np.uint64)
    return share(enc, rng)


def rec(a: AShare) -> jnp.ndarray:
    """Rec(x): reconstruct (the only point where plaintext reappears)."""
    return (a.s0 + a.s1).astype(ring.DTYPE)


def rec_real(a: AShare, f: int = ring.F) -> jnp.ndarray:
    return ring.decode(rec(a), f)


def share_b(x, rng: np.random.Generator) -> BShare:
    x = np.asarray(x, np.uint64)
    b0 = ring.rand_np(rng, x.shape)
    return BShare(jnp.asarray(b0), jnp.asarray(x ^ b0))


def rec_b(b: BShare) -> jnp.ndarray:
    return b.b0 ^ b.b1


def zeros_like(a: AShare) -> AShare:
    z = jnp.zeros(a.shape, ring.DTYPE)
    return AShare(z, z)


def public_to_ashare(x) -> AShare:
    """Embed a public ring tensor as a (degenerate) share pair (P0 holds it)."""
    x = jnp.asarray(x, ring.DTYPE)
    return AShare(x, jnp.zeros_like(x))
