"""Pallas TPU kernel: blocked-ELL sparse x dense matmul (paper Sec 4.3).

TPU adaptation of the paper's sparsity optimization (DESIGN.md §3): CSR
gathers are GPU-idiomatic; the TPU-native layout is *blocked-ELL* — the
sparse matrix is cut into (bm x bk) tiles, only non-empty tiles are stored
(row-block major, padded to max_blocks per row block), and each tile is a
dense MXU-aligned matmul. HBM->VMEM traffic and MXU work are proportional to
the number of NON-EMPTY blocks, which is the paper's nnz-proportional-cost
insight transplanted to the TPU memory hierarchy.

The dense operand Y (the small k x d centroid block in K-means; the paper's
"shape of Y is much smaller than X") is held fully in VMEM and indexed
dynamically with the tile's column-block id — valid while d*k*4B fits the
~16 MB VMEM budget, which `ops.spmm` asserts.

Supports f32 (plaintext path), the u32 ring via the same 16-bit limb trick
as kernels/modmatmul, and the u64 ring as a native uint64 tile matmul. The
u64 variant runs in interpret mode only: Mosaic has no 64-bit integer
type, so `core/backend.PallasBackend` refuses a TPU, and the chip runs the
XLA backend's ELL spmm on the signed 8-bit limb cascade (DESIGN.md §3).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl


def _tile_matmul(xb, yb, dtype):
    if dtype == jnp.uint64:
        # native uint64 lanes: interpret mode only (see module docstring)
        return jnp.matmul(xb, yb)
    if dtype == jnp.uint32:
        mask16 = jnp.uint32(0xFFFF)
        x_lo = (xb & mask16).astype(jnp.int32)
        x_hi = (xb >> 16).astype(jnp.int32)
        y_lo = (yb & mask16).astype(jnp.int32)
        y_hi = (yb >> 16).astype(jnp.int32)
        dot = functools.partial(
            jax.lax.dot_general,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        return dot(x_lo, y_lo).astype(jnp.uint32) \
            + ((dot(x_lo, y_hi) + dot(x_hi, y_lo)).astype(jnp.uint32) << 16)
    return jax.lax.dot_general(
        xb, yb, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _kernel(idx_ref, cnt_ref, blocks_ref, y_ref, o_ref, *, bk: int,
            max_blocks: int, group: int, dtype):
    """One grid cell handles `group` row blocks. group=1 is the TPU tiling
    (one MXU-aligned row block per cell); group=nrb collapses the grid to a
    single cell for interpret mode, where the emulation's fixed per-grid-step
    cost — not the tile math — dominated the old (nrb,)-grid runtime 60x."""
    bm = blocks_ref.shape[2]
    k = y_ref.shape[1]

    def row_block(g):
        def body(j, acc):
            start = idx_ref[g, j].astype(jnp.int32) * jnp.int32(bk)
            yb = y_ref[pl.ds(start, bk), :]
            xb = blocks_ref[g, j]
            contrib = _tile_matmul(xb, yb, dtype)
            keep = (j < cnt_ref[g]).astype(contrib.dtype)
            return acc + keep * contrib
        return jax.lax.fori_loop(0, max_blocks, body, jnp.zeros((bm, k), dtype))

    if group == 1:
        o_ref[0] = row_block(0)
    else:
        def row(g, carry):
            o_ref[g] = row_block(g)
            return carry
        jax.lax.fori_loop(0, group, row, 0)


@functools.partial(jax.jit, static_argnames=("interpret", "group"))
def spmm_ell(blocks: jnp.ndarray, idx: jnp.ndarray, counts: jnp.ndarray,
             y: jnp.ndarray, *, interpret: bool = True,
             group: int | None = None) -> jnp.ndarray:
    """blocks (nrb, maxb, bm, bk), idx (nrb, maxb) i32, counts (nrb,) i32,
    y (d, k) -> (nrb*bm, k). dtype of `blocks` selects f32 / u32 / u64.

    `group` row blocks are processed per grid cell (must divide nrb);
    default: all of them in interpret mode (single cell — the emulation's
    per-cell cost dwarfs the tile work), one per cell on a real TPU."""
    nrb, maxb, bm, bk = blocks.shape
    d, k = y.shape
    if group is None:
        group = nrb if interpret else 1
    assert nrb % group == 0, (nrb, group)
    if blocks.dtype in (jnp.uint32, jnp.uint64):
        out_dtype = blocks.dtype
    else:
        out_dtype = jnp.float32
    out = pl.pallas_call(
        functools.partial(_kernel, bk=bk, max_blocks=maxb, group=group,
                          dtype=out_dtype),
        grid=(nrb // group,),
        in_specs=[
            pl.BlockSpec((group, maxb), lambda i: (i, 0)),       # idx
            pl.BlockSpec((group,), lambda i: (i,)),              # counts
            pl.BlockSpec((group, maxb, bm, bk), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((d, k), lambda i: (0, 0)),              # whole Y
        ],
        out_specs=pl.BlockSpec((group, bm, k), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nrb, bm, k), out_dtype),
        interpret=interpret,
    )(idx, counts, blocks, y.astype(out_dtype))
    return out.reshape(nrb * bm, k)


# ---------------------------------------------------------------------------
# layout conversion: dense / CSR -> blocked-ELL
# ---------------------------------------------------------------------------

def dense_to_ell(x: np.ndarray, bm: int = 8, bk: int = 128):
    """Pack a dense matrix into blocked-ELL (numpy, host-side, offline)."""
    n, d = x.shape
    n_pad = (-n) % bm
    d_pad = (-d) % bk
    xp = np.pad(x, ((0, n_pad), (0, d_pad)))
    nrb, ncb = xp.shape[0] // bm, xp.shape[1] // bk
    tiles = xp.reshape(nrb, bm, ncb, bk).transpose(0, 2, 1, 3)  # (nrb,ncb,bm,bk)
    nonempty = (tiles != 0).any(axis=(2, 3))                    # (nrb, ncb)
    counts = nonempty.sum(1).astype(np.int32)
    maxb = max(1, int(counts.max()))
    blocks = np.zeros((nrb, maxb, bm, bk), x.dtype)
    idx = np.zeros((nrb, maxb), np.int32)
    for i in range(nrb):
        cols = np.flatnonzero(nonempty[i])
        blocks[i, :len(cols)] = tiles[i, cols]
        idx[i, :len(cols)] = cols
    return blocks, idx, counts


def csr_to_ell(indptr, indices, data, shape, bm: int = 8, bk: int = 128):
    """CSR -> blocked-ELL without densifying: memory stays proportional to
    the number of non-empty (bm x bk) tiles, never to n*d. Fully vectorized
    (one sort over nnz), so the offline pack keeps up with large inputs."""
    n, d = shape
    indptr = np.asarray(indptr, np.int64)
    indices = np.asarray(indices, np.int64)
    data = np.asarray(data)
    nrb = -(-n // bm)
    ncb = -(-d // bk)
    nnz = len(data)
    if nnz == 0:
        return (np.zeros((nrb, 1, bm, bk), data.dtype),
                np.zeros((nrb, 1), np.int32), np.zeros((nrb,), np.int32))
    rows = np.repeat(np.arange(n), np.diff(indptr))
    rb, cb = rows // bm, indices // bk
    tile_id = rb * ncb + cb
    uniq, inv = np.unique(tile_id, return_inverse=True)
    counts = np.bincount(uniq // ncb, minlength=nrb).astype(np.int32)
    maxb = max(1, int(counts.max()))
    # slot of each unique tile within its row block (uniq is sorted, so
    # tiles of one row block are contiguous)
    starts = np.zeros(nrb, np.int64)
    np.cumsum(counts[:-1], out=starts[1:])
    slot_of_uniq = np.arange(len(uniq)) - starts[uniq // ncb]
    blocks = np.zeros((nrb, maxb, bm, bk), data.dtype)
    idx = np.zeros((nrb, maxb), np.int32)
    idx[uniq // ncb, slot_of_uniq] = (uniq % ncb).astype(np.int32)
    blocks[rb, slot_of_uniq[inv], rows % bm, indices % bk] = data
    return blocks, idx, counts
