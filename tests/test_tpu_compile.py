"""Compile rehearsal for one TPU v5e chip; no chip is needed.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached. These tests compile what the secure fit and the
scoring service run on the chip, at the fraud deployment's widths
(configs/kmeans_fraud.py: d_a=512 per party, k=16, minibatches of 16,384
rows), and check that no 64-bit integer dot reaches the compiler: the
chip has none, and XLA refuses one.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import ring
from repro.core.backend import KS_LEVELS, get_backend
from repro.kernels.ksadder import ks_carry_share
from repro.launch import kmeans_step as K

BATCH, D_A, K_CLUSTERS = 16_384, 512, 16
U64_DOT = re.compile(r"u64\[[^\]]*\]\S*\s+(dot|convolution)\(")


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


def _no_u64_dot(compiled):
    text = compiled.as_text()
    assert not U64_DOT.search(text), U64_DOT.search(text).group(0)
    return text


def test_auto_backend_is_the_one_that_compiles():
    assert get_backend("auto").name == "xla"


@pytest.mark.parametrize("lhs,rhs", [((BATCH, D_A), (D_A, K_CLUSTERS)),
                                     ((K_CLUSTERS, BATCH), (BATCH, D_A))],
                         ids=["x_mu", "ct_x"])
def test_ring_mm_compiles_for_v5e(one_chip, lhs, rhs):
    """X mu^T and C^T X, the two ring matmul shapes of a fit batch."""
    mm = get_backend("auto").ring_mm
    c = jax.jit(mm).lower(_spec(lhs, ring.DTYPE, one_chip),
                          _spec(rhs, ring.DTYPE, one_chip)).compile()
    text = _no_u64_dot(c)
    assert re.search(r"s32\[[^\]]*\]\S*\s+(dot|convolution)\(", text)
    assert c.memory_analysis() is not None


def test_ring_spmm_compiles_for_v5e(one_chip):
    """The blocked-ELL spmm at a Protocol-2 tile layout (8 x 128 tiles)."""
    nrb, maxb, bm, bk = BATCH // 8, 4, 8, 128
    spmm = get_backend("auto").ring_spmm
    c = jax.jit(spmm).lower(
        _spec((nrb, maxb, bm, bk), ring.DTYPE, one_chip),
        _spec((nrb, maxb), jnp.int32, one_chip),
        _spec((nrb,), jnp.int32, one_chip),
        _spec((D_A, K_CLUSTERS), ring.DTYPE, one_chip)).compile()
    _no_u64_dot(c)


def _ks_args(shape, dtype, sharding):
    flat = [_spec(shape, dtype, sharding) for _ in range(6)]
    lvls = [_spec((len(KS_LEVELS), 2) + shape, dtype, sharding)
            for _ in range(5)]
    return flat + lvls


def test_ks_fused_compiles_for_v5e(one_chip):
    """The first tournament round's comparison tensor (n, k/2)."""
    ks = get_backend("auto").ks_fused
    shape = (BATCH, K_CLUSTERS // 2)
    jax.jit(lambda *a: ks(*a, party0=True)).lower(
        *_ks_args(shape, ring.DTYPE, one_chip)).compile()


def test_ks_carry_share_kernel_compiles_for_v5e(one_chip):
    """The Pallas carry kernel on uint32 lanes, with the chip's (8, 128)
    tiling, at the same tournament shape (two lanes per ring word)."""
    rows = BATCH * (K_CLUSTERS // 2) * 2 // 128
    c = jax.jit(lambda *a: ks_carry_share(*a, party0=True, bm=8, bn=128,
                                          interpret=False)).lower(
        *_ks_args((rows, 128), jnp.uint32, one_chip)).compile()
    assert "tpu_custom_call" in c.as_text()


def test_fit_batch_programs_compile_for_v5e(one_chip, monkeypatch):
    """The S1 and S3-partial programs of one dense vertical fit batch,
    built by fit_batch_programs with its argument shapes placed on the
    described chip."""
    def sds(shape):
        return _spec(shape, ring.NP_DTYPE, one_chip)

    specs = K.offline_tensor_specs
    monkeypatch.setattr(K, "_sds", sds)
    monkeypatch.setattr(K, "offline_tensor_specs", lambda req, n: [
        _spec(s.shape, s.dtype, one_chip) for s in specs(req, n)])
    monkeypatch.setattr(K, "_BATCH_PROGRAM_CACHE", {})
    progs = K.fit_batch_programs("vertical", False, (BATCH, D_A),
                                 (BATCH, D_A), K_CLUSTERS)
    for prog in (progs.s1, progs.s3p):
        _no_u64_dot(prog)
        mem = prog.memory_analysis()
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
            < 16 * 1024 ** 3
