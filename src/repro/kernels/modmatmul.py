"""Pallas TPU kernel: secret-share ring matmul mod 2^32 / 2^64.

This is the online-phase hot spot of every Beaver matmul (E@F, U_i@F, E@V_i
— paper Sec 4.1): an *integer* matmul whose accumulator must wrap mod 2^l.

TPU adaptation (DESIGN.md §3): the MXU has no 64-bit integer path, so the
u32 ring matmul is decomposed into 16-bit limbs —

    a*b mod 2^32 = ll + ((lh + hl) << 16)        (hh*2^32 vanishes)

where ll/lh/hl are int32 matmuls of 16-bit limb matrices: products fit and
int32 accumulation wraparound IS the ring reduction. That trick is exact for
the 32-bit ring only. Mod 2^64 the lowest limb product needs all 64 bits,
which no int32 accumulator holds; the exact 64-bit matmul is the signed
8-bit cascade of `core/backend.ring_matmul` (36 int8 partial products with
int32 accumulation). The u64 kernel here is a native uint64 matmul, valid
in interpret mode only: Mosaic refuses it ("matmul acc to be 32-bit"), and
`core/backend.PallasBackend` refuses a TPU for that reason.

Blocking: (bm x bk) @ (bk x bn) MXU-aligned tiles (multiples of 128 on the
lane dim), f32-free, VMEM accumulator scratch carried over the k grid axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel_u32(a_ref, b_ref, o_ref, acc_ref, *, n_kblocks: int):
    """Grid (m_blocks, n_blocks, k_blocks); acc carried across k_blocks."""
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]                      # (bm, bk) uint32
    b = b_ref[...]                      # (bk, bn) uint32
    mask = jnp.uint32(0xFFFF)
    a_lo, a_hi = (a & mask).astype(jnp.int32), (a >> 16).astype(jnp.int32)
    b_lo, b_hi = (b & mask).astype(jnp.int32), (b >> 16).astype(jnp.int32)
    dot = functools.partial(jax.lax.dot_general,
                            dimension_numbers=(((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.int32)
    ll = dot(a_lo, b_lo)
    lh = dot(a_lo, b_hi)
    hl = dot(a_hi, b_lo)
    prod = ll.astype(jnp.uint32) + ((lh + hl).astype(jnp.uint32) << 16)
    acc_ref[...] += prod

    @pl.when(kb == n_kblocks - 1)
    def _flush():
        o_ref[...] = acc_ref[...]


def _kernel_u64(a_ref, b_ref, o_ref, acc_ref, *, n_kblocks: int):
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # native uint64 lanes: interpret mode only (see module docstring)
    acc_ref[...] += jnp.matmul(a_ref[...], b_ref[...])

    @pl.when(kb == n_kblocks - 1)
    def _flush():
        o_ref[...] = acc_ref[...]


def modmatmul(a: jnp.ndarray, b: jnp.ndarray, *, bm: int = 128, bk: int = 128,
              bn: int = 128, interpret: bool = True) -> jnp.ndarray:
    """Ring matmul; dtype of `a` selects the u32 or u64 ring.

    Shapes must be multiples of the block sizes (ops.py pads).
    """
    n, d = a.shape
    d2, k = b.shape
    assert d == d2 and a.dtype == b.dtype
    assert n % bm == 0 and d % bk == 0 and k % bn == 0, (a.shape, b.shape)
    kern = _kernel_u32 if a.dtype == jnp.uint32 else _kernel_u64
    grid = (n // bm, k // bn, d // bk)
    return pl.pallas_call(
        functools.partial(kern, n_kblocks=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kb: (i, kb)),
            pl.BlockSpec((bk, bn), lambda i, j, kb: (kb, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kb: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, k), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), a.dtype)],
        interpret=interpret,
    )(a, b)
