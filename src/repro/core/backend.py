"""Pluggable ring-compute backend — the local hot ops of the online path.

Every *local* ring linear-algebra operation the 2PC protocols consume is
funnelled through one small dispatch interface (DESIGN.md §7):

  * ``ring_mm``       — uint64 matmul mod 2^64 (every Beaver recombination,
                        every public-x-share product, every C^T X block).
  * ``ring_spmm``     — blocked-ELL sparse x dense over the ring (the
                        nnz-proportional step-2 compute of Protocol 2).
  * ``ks_fused``      — one party's fused Kogge-Stone recombination: all
                        7 AND levels of the secure-adder MSB collapsed into a
                        single local pass given the exchanged masked operands.

Three implementations:

  * ``xla``    — pure jnp; the device path on every platform. Its ring
                 matmuls run the signed 8-bit limb cascade of `ring_matmul`
                 (DESIGN.md §3): no uint64 dot reaches the compiler, so the
                 same code compiles for the TPU and runs in the CPU tests.
  * ``pallas`` — the kernels in ``repro.kernels``, in interpret mode only.
                 Their ring matmul and spmm still compute on uint64 inside
                 the kernel, which Mosaic refuses, so constructing this
                 backend with a TPU attached raises.
  * ``numpy``  — host-side, for the offline dealer in ``core/triples.py``
                 and Protocol 2's host-resident sparse data.

Selection: ``get_backend("auto")`` resolves to ``xla`` on every platform, a
TPU included. ``KMeansConfig.backend`` / ``Ctx.backend`` carry the choice
through the protocol stack, so the compiled programs in
``launch/kmeans_step`` and the eager path in ``core/kmeans`` execute the
same dispatch. All implementations are bit-exact in Z_{2^64}: the parity
tests assert equality, not closeness.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ring
# The level schedule and Beaver AND recombination have ONE canonical source
# (the kernel module): the xla/numpy paths below must stay bit-identical to
# the pallas kernel for the backend parity guarantee to hold.
from repro.kernels.ksadder import LEVELS as KS_LEVELS
from repro.kernels.ksadder import _and_share


def _ks_fused_generic(x, e0, f0, u0, v0, z0, el, fl, ul, vl, zl,
                      party0: bool):
    """Fused local Kogge-Stone recombination (works on jnp and np arrays).

    Level 0 is the initial g = AND(x, y) triple; levels 1..6 are the
    stacked (g, p) AND pairs. All e/f are the publicly reconstructed masked
    operands. Returns this party's share of the carry word G.

    Only the g-chain is recombined: the per-level p *shares* feed nothing
    locally (the next level's public masks are part of the transcript), so
    the slot-1 operands — and `x`, the party's p0-share — are accepted for
    interface parity with the kernel but not computed on. The slot-1
    triples are still drawn and exchanged by msb_carry: they mask the
    public transcript itself.
    """
    g = _and_share(e0, f0, u0, v0, z0, party0)
    for li in range(len(KS_LEVELS)):
        g = g ^ _and_share(el[li, 0], fl[li, 0], ul[li, 0], vl[li, 0],
                           zl[li, 0], party0)
    return g


def _csr_spmm_chunked(csr, y):
    """Host-side CSR x dense mod 2^64: gather-multiply-scatter, chunked so
    the intermediate stays O(chunk * k) regardless of sparsity skew."""
    y = np.asarray(y, ring.NP_DTYPE)
    n = csr.shape[0]
    z = np.zeros((n, y.shape[1]), ring.NP_DTYPE)
    rows = np.repeat(np.arange(n), np.diff(csr.indptr))
    chunk = 1 << 22
    for lo in range(0, csr.nnz, chunk):
        hi = min(csr.nnz, lo + chunk)
        contrib = csr.data[lo:hi, None] * y[csr.indices[lo:hi]]
        np.add.at(z, rows[lo:hi], contrib)
    return z


# ---------------------------------------------------------------------------
# the exact 64-bit ring matmul on 8-bit dots (DESIGN.md §3)
# ---------------------------------------------------------------------------

LIMBS = 8
# Adding 0x80 to every byte before reading the bytes off gives signed
# digits: if w' = w + BIAS has bytes b_i, then w = sum (b_i - 128) 256^i
# mod 2^64, every digit in [-128, 127].
_DIGIT_BIAS = np.uint64(0x8080808080808080)
# A digit product is at most 128 * 128 = 2^14 in magnitude, so a dot of
# K digit pairs is exact in int32 while K * 2^14 <= 2^31 - 1.
MAX_CONTRACTION = (2 ** 31 - 1) >> 14       # 131071


def _digits(x):
    """(8, *x.shape) int8 signed base-256 digits of uint64 words."""
    w = jnp.asarray(x, ring.DTYPE) + jnp.uint64(_DIGIT_BIAS)
    shift = np.arange(0, 64, 8, dtype=np.uint64).reshape((LIMBS,)
                                                          + (1,) * w.ndim)
    return (((w[None] >> shift) & 0xFF).astype(jnp.int32) - 128) \
        .astype(jnp.int8)


# digit product (i, j) lands at bit 8(i+j); those with i+j >= 8 vanish
_PAIR_SHIFT = 8 * (np.arange(LIMBS)[:, None, None, None]
                   + np.arange(LIMBS)[None, None, :, None])
_PAIR_KEEP = _PAIR_SHIFT < 64


def _cascade(a, b):
    """a (*B, m, K) @ b (*B, K, n) mod 2^64 for K <= MAX_CONTRACTION.

    a*b = sum_{i+j<=7} (A_i @ B_j) 2^(8(i+j)) mod 2^64 over the digit
    matrices A_i, B_j: 36 int8 x int8 -> int32 partial products,
    recombined by uint64 shift-adds. One dot forms all 64 digit products
    (the 28 with i+j >= 8 are masked off): one op in place of 36 keeps the
    compiled programs small, at 64/36 of the minimal MXU work."""
    nb = a.ndim - 2
    batch = tuple(range(1, nb + 1))
    p = jax.lax.dot_general(
        _digits(a), _digits(b), (((nb + 2,), (nb + 1,)), (batch, batch)),
        preferred_element_type=jnp.int32)           # (*B, 8, m, 8, n)
    p = p.astype(jnp.int64).astype(ring.DTYPE) \
        << jnp.asarray(np.where(_PAIR_KEEP, _PAIR_SHIFT, 0), ring.DTYPE)
    p = jnp.where(_PAIR_KEEP, p, jnp.uint64(0))
    return p.sum((nb, nb + 2), dtype=ring.DTYPE)


@jax.jit
def ring_matmul(a, b):
    """Exact a (*B, m, K) @ b (*B, K, n) mod 2^64 on 8-bit dots.

    A contraction longer than MAX_CONTRACTION is split into pieces that
    stay exact; their products add in uint64."""
    a = jnp.asarray(a, ring.DTYPE)
    b = jnp.asarray(b, ring.DTYPE)
    kdim = a.shape[-1]
    out = None
    for lo in range(0, max(kdim, 1), MAX_CONTRACTION):
        hi = min(kdim, lo + MAX_CONTRACTION)
        part = _cascade(a[..., lo:hi], b[..., lo:hi, :])
        out = part if out is None else out + part
    return out


class RingBackend:
    """Dispatch interface for the local ring ops on the online hot path."""

    name = "base"

    def ring_mm(self, a, b):
        """(n, d) @ (d, k) mod 2^64."""
        raise NotImplementedError

    def ring_spmm(self, blocks, idx, counts, y):
        """Blocked-ELL sparse x dense over the ring -> (nrb*bm, k)."""
        raise NotImplementedError

    def ring_spmm_csr(self, csr, y, *, bm: int = 8, bk: int = 128):
        """CSR sparse x dense mod 2^64 -> (n, k) via the blocked-ELL op.

        The ELL layout pads every row block to the max tile count, so a
        skewed matrix (one dense row block) costs O(nrb * maxb) — inherent
        to ELL and acceptable when the tiles feed an accelerator kernel.
        Host-only backends override this with the chunked CSR loop.
        """
        from repro.kernels.spmm import csr_to_ell
        blocks, idx, counts = csr_to_ell(csr.indptr, csr.indices, csr.data,
                                         csr.shape, bm=bm, bk=bk)
        y = np.asarray(y, ring.NP_DTYPE)
        pad = (-y.shape[0]) % bk
        if pad:
            y = np.pad(y, ((0, pad), (0, 0)))
        out = self.ring_spmm(blocks, idx, counts, y)
        return out[: csr.shape[0]]

    def ks_fused(self, x, e0, f0, u0, v0, z0, el, fl, ul, vl, zl, *,
                 party0: bool):
        """One party's fused 7-level Kogge-Stone local recombination."""
        raise NotImplementedError


class XlaBackend(RingBackend):
    """Pure-jnp implementation — the device path on every platform."""

    name = "xla"

    def ring_mm(self, a, b):
        return ring_matmul(a, b)

    def ring_spmm(self, blocks, idx, counts, y):
        blocks = jnp.asarray(blocks, ring.DTYPE)
        y = jnp.asarray(y, ring.DTYPE)
        nrb, maxb, bm, bk = blocks.shape
        k = y.shape[1]
        y_tiles = y.reshape(-1, bk, k)[jnp.asarray(idx)]    # (nrb, maxb, bk, k)
        contrib = ring_matmul(blocks, y_tiles)              # (nrb, maxb, bm, k)
        keep = (jnp.arange(maxb)[None, :] < jnp.asarray(counts)[:, None])
        contrib = jnp.where(keep[..., None, None], contrib, jnp.uint64(0))
        return contrib.sum(1).reshape(nrb * bm, k)

    def ring_spmm_csr(self, csr, y, *, bm: int = 8, bk: int = 128):
        # Protocol 2's sparse data is host-resident and the result is
        # masked on the host, so it stays there; with no accelerator to
        # feed, the chunked CSR loop beats an ELL densification (which
        # blows up O(nrb*maxb) on skewed matrices) — the ELL ring_spmm
        # above stays as the parity oracle for the pallas kernel.
        return _csr_spmm_chunked(csr, y)

    def ks_fused(self, x, e0, f0, u0, v0, z0, el, fl, ul, vl, zl, *,
                 party0: bool):
        return _ks_fused_generic(x, e0, f0, u0, v0, z0, el, fl, ul, vl, zl,
                                 party0)


class PallasBackend(RingBackend):
    """Routes through the Pallas kernels, in interpret mode. The ring
    matmul and spmm kernels compute on uint64 inside the kernel, which
    Mosaic cannot lower, so this backend refuses a TPU outright instead of
    emulating the kernels there."""

    name = "pallas"

    def __init__(self):
        if _has_tpu():
            raise RuntimeError(
                "the pallas ring backend runs in interpret mode only: its "
                "ring_mm/ring_spmm kernels compute on uint64, which Mosaic "
                "does not lower for a TPU. Use backend='xla' (what 'auto' "
                "resolves to), whose limb cascade compiles for the chip.")

    def ring_mm(self, a, b):
        from repro.kernels import ops
        return ops.ring_matmul(jnp.asarray(a, ring.DTYPE),
                               jnp.asarray(b, ring.DTYPE), interpret=True)

    def ring_spmm(self, blocks, idx, counts, y):
        from repro.kernels import ops
        return ops.spmm(jnp.asarray(blocks, ring.DTYPE), jnp.asarray(idx),
                        jnp.asarray(counts), jnp.asarray(y, ring.DTYPE),
                        interpret=True)

    def ks_fused(self, x, e0, f0, u0, v0, z0, el, fl, ul, vl, zl, *,
                 party0: bool):
        from repro.kernels.ksadder import from_lanes, ks_carry_share, to_lanes
        shape = jnp.shape(x)
        size = max(1, int(np.prod(shape, dtype=np.int64)))
        bn = 128
        rows = -(-2 * size // bn)       # two uint32 lanes per ring word
        pad = rows * bn - 2 * size

        def flat2d(t):
            t = to_lanes(jnp.asarray(t, ring.DTYPE).reshape(-1))
            return jnp.pad(t, (0, pad)).reshape(rows, bn)

        def lvl2d(t):
            t = jnp.asarray(t, ring.DTYPE).reshape(len(KS_LEVELS), 2, -1)
            t = jnp.pad(to_lanes(t), ((0, 0), (0, 0), (0, pad)))
            return t.reshape(len(KS_LEVELS), 2, rows, bn)

        # single grid cell: the interpret emulation pays per grid step, so
        # tiling rows 8 at a time made this op ~60x slower than XLA
        out = ks_carry_share(flat2d(x), flat2d(e0), flat2d(f0), flat2d(u0),
                             flat2d(v0), flat2d(z0), lvl2d(el), lvl2d(fl),
                             lvl2d(ul), lvl2d(vl), lvl2d(zl), party0=party0,
                             bm=rows, bn=bn, interpret=True)
        return from_lanes(out.reshape(-1)[:2 * size]).reshape(shape)


class NumpyBackend(RingBackend):
    """Host-side implementation for the offline dealer and Protocol 2."""

    name = "numpy"

    def ring_mm(self, a, b):
        return np.einsum("ij,jk->ik", np.asarray(a, ring.NP_DTYPE),
                         np.asarray(b, ring.NP_DTYPE),
                         dtype=ring.NP_DTYPE, casting="unsafe")

    def ring_spmm(self, blocks, idx, counts, y):
        blocks = np.asarray(blocks, ring.NP_DTYPE)
        y = np.asarray(y, ring.NP_DTYPE)
        nrb, maxb, bm, bk = blocks.shape
        k = y.shape[1]
        y_tiles = y.reshape(-1, bk, k)[np.asarray(idx)]
        contrib = np.einsum("rbmi,rbik->rbmk", blocks, y_tiles,
                            dtype=ring.NP_DTYPE, casting="unsafe")
        keep = np.arange(maxb)[None, :] < np.asarray(counts)[:, None]
        contrib *= keep[..., None, None].astype(ring.NP_DTYPE)
        return contrib.sum(1, dtype=ring.NP_DTYPE).reshape(nrb * bm, k)

    def ring_spmm_csr(self, csr, y, *, bm: int = 8, bk: int = 128):
        return _csr_spmm_chunked(csr, y)

    def ks_fused(self, x, e0, f0, u0, v0, z0, el, fl, ul, vl, zl, *,
                 party0: bool):
        args = [np.asarray(t, ring.NP_DTYPE)
                for t in (x, e0, f0, u0, v0, z0, el, fl, ul, vl, zl)]
        return _ks_fused_generic(*args, party0)


# ---------------------------------------------------------------------------
# resolution
# ---------------------------------------------------------------------------

_INSTANCES: dict[str, RingBackend] = {}


def _has_tpu() -> bool:
    """Whether JAX's default backend is a TPU. Device-initialisation errors
    propagate: a chip that another process holds must fail loudly, not
    turn into a CPU run."""
    return jax.default_backend() == "tpu"


def get_backend(name: "str | RingBackend | None" = "auto") -> RingBackend:
    """Resolve a backend name ('auto'|'xla'|'pallas'|'numpy') or pass an
    instance through. 'auto' = xla on every platform: it is the one
    backend whose every kernel compiles for the TPU."""
    if isinstance(name, RingBackend):
        return name
    if name is None or name == "auto":
        name = "xla"
    if name not in _INSTANCES:
        try:
            cls = {"xla": XlaBackend, "pallas": PallasBackend,
                   "numpy": NumpyBackend}[name]
        except KeyError:
            raise ValueError(
                f"unknown ring backend {name!r}; "
                "expected 'auto', 'xla', 'pallas' or 'numpy'") from None
        _INSTANCES[name] = cls()
    return _INSTANCES[name]
