"""Pallas TPU kernel: fused Kogge-Stone carry network for the B-share MSB
(paper F^k_min's CMP — the S2 hot spot).

Each party's local work per AND level of the secure adder is a handful of
bitwise ops over bit-packed uint64 lanes (protocol.py msb_carry). Fusing all
6 levels' LOCAL pieces (the Beaver shares recombination given the already-
exchanged masked operands E_l, F_l per level) into one VMEM pass removes 12
HBM round-trips per CMP over the (n, m) comparison tensor.

Inputs are per-level public E/F masks + this party's triple shares
(u, v, z), i.e. exactly the online-phase state after the exchange rounds;
the kernel computes the party's share of the final carry-out word. Validated
in interpret mode against the pure-jnp oracle derived from protocol.py.

The kernel works on uint32 lanes: Mosaic has no 64-bit integer type, and
AND/XOR act on each bit alone, so a ring word split into its two 32-bit
halves (`to_lanes`, outside the kernel) and joined again afterwards
(`from_lanes`) gives exactly the uint64 result.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LEVELS = (1, 2, 4, 8, 16, 32)


def to_lanes(t):
    """(..., m) uint64 -> (..., 2m) uint32: each word's two 32-bit halves."""
    t = jax.lax.bitcast_convert_type(jnp.asarray(t, jnp.uint64), jnp.uint32)
    return t.reshape(t.shape[:-2] + (-1,))


def from_lanes(t):
    """Inverse of `to_lanes`."""
    t = jnp.asarray(t, jnp.uint32)
    t = t.reshape(t.shape[:-1] + (-1, 2))
    return jax.lax.bitcast_convert_type(t, jnp.uint64)


def _and_share(e, f, u, v, z, party0: bool):
    """One party's Beaver AND recombination on bit-packed words."""
    out = z ^ (u & f) ^ (e & v)
    if party0:
        out = out ^ (e & f)
    return out


def _kernel(x_ref, e0_ref, f0_ref, u0_ref, v0_ref, z0_ref,
            el_ref, fl_ref, ul_ref, vl_ref, zl_ref, o_ref, *, party0: bool):
    """x: this party's arithmetic-share word (the adder input bits).
    Level 0 = initial g = AND(x, y); levels 1..6 = the stacked (g,p) ANDs.
    All E/F are the publicly reconstructed masked operands."""
    g = _and_share(e0_ref[...], f0_ref[...], u0_ref[...], v0_ref[...],
                   z0_ref[...], party0)
    p = x_ref[...]                                # p-share: xor of inputs
    for li, s in enumerate(LEVELS):
        # batched AND pair: lhs = [p, p]; rhs = [g << s, p << s]
        eg, ep = el_ref[li, 0], el_ref[li, 1]
        fg, fp = fl_ref[li, 0], fl_ref[li, 1]
        new_g = g ^ _and_share(eg, fg, ul_ref[li, 0], vl_ref[li, 0],
                               zl_ref[li, 0], party0)
        new_p = _and_share(ep, fp, ul_ref[li, 1], vl_ref[li, 1],
                           zl_ref[li, 1], party0)
        g, p = new_g, new_p
    o_ref[...] = g


def _lvl_index(i, j):
    zero = jnp.int32(0)     # a bare 0 would be an int64 index under x64
    return zero, zero, i, j


@functools.partial(jax.jit,
                   static_argnames=("party0", "bm", "bn", "interpret"))
def ks_carry_share(x, e0, f0, u0, v0, z0, el, fl, ul, vl, zl, *,
                   party0: bool, bm: int = 8, bn: int = 128,
                   interpret: bool = True):
    """All tensors (n, m) uint32 lanes (`to_lanes` of the ring words)
    except the level-stacked ones (6, 2, n, m). Returns this party's share
    of the carry word (n, m), as lanes.

    Jit'd: the interpret-mode emulation pays a large fixed dispatch cost per
    *traced* grid step, so eager per-call execution was ~100x off the fused
    op's real cost; under jit it compiles once per (shape, party) and runs at
    XLA speed. Callers pick bm: 8 for (8, 128) VMEM tiles on a real TPU,
    n for a single grid cell in interpret mode (core/backend.py)."""
    n, m = x.shape
    assert n % bm == 0 and m % bn == 0, (n, m)
    grid = (n // bm, m // bn)
    lvl_spec = pl.BlockSpec((6, 2, bm, bn), _lvl_index)
    flat_spec = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
    return pl.pallas_call(
        functools.partial(_kernel, party0=party0),
        grid=grid,
        in_specs=[flat_spec] * 6 + [lvl_spec] * 5,
        out_specs=flat_spec,
        out_shape=jax.ShapeDtypeStruct((n, m), jnp.uint32),
        interpret=interpret,
    )(x, e0, f0, u0, v0, z0, el, fl, ul, vl, zl)
