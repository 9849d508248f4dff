"""Beaver triple generation — the data-independent OFFLINE phase (paper Sec 4.1).

Provider flavours:

* `TrustedDealer` — generates correct triples on demand (numpy). This matches
  the paper's remark that "if there is a trusted third party that does the
  offline phase, the overall efficiency will improve further". On-demand
  generation puts the dealer's host work on the ONLINE critical path, which is
  exactly what the paper's offline/online split avoids — it remains as the
  oracle and as the no-preprocessing baseline.
* `PlanningDealer` + `TriplePlan` — a dry-run trace (the `ListDealer`-style
  replay discipline of launch/kmeans_step) that records the exact
  correlated-randomness schedule a protocol run will consume. The schedule is
  data-independent — that is WHY an offline phase exists at all.
* `PooledDealer` — executes a `TriplePlan` ahead of time with ONE stacked RNG
  draw and ONE batched ring op per shape-class (instead of thousands of tiny
  numpy calls), puts each class's per-request arrays on the device in one
  call, and serves the online phase with zero host work. Bit-exact against
  `TrustedDealer` under the same seed: both draw from identical per-class
  PCG64 streams, and a stacked full-range uint64 draw equals the
  concatenation of the per-request draws.
* `StreamingPooledDealer` — the pooled dealer's generation chunked into
  per-iteration *tranches*, double-buffered on a background worker: tranche
  t+1 is generated while iteration t's launches consume tranche t, so peak
  pool residency is O(1 iteration) — independent of `iters` — and fits whose
  total pool exceeds device memory become possible. Bit-exact with both other
  dealers (persistent per-class streams + draw concatenation). `group`
  merges several small iterations into one generation wakeup.
* `SlotDealer` — the minibatch/pipeline generalization: the schedule is a
  SEQUENCE of per-(iteration, batch, stage) slot plans, tranches generated
  in canonical slot order (streamed on a worker, or all up front), and
  `acquire(i)` hands slot i out as a dealer view in ANY order within the
  window — the pipelined executor's double-buffer contract (DESIGN.md §11).
* OT-based generation is *cost-modelled* (we cannot run a real network OT
  extension here): per 64-bit scalar product the Gilboa/ABY protocol transfers
  l correlated OTs of (kappa + l)-bit strings per direction. Offline bytes and
  a CPU-rate-based time estimate are logged so Table 1/2's offline column can
  be reproduced analytically alongside the measured dealer wall-time.
* `HE-based` generation for matrix triples (paper ref [34] style) is available
  through repro.core.he for small shapes (real Paillier), mainly for tests.

Every request is tagged so the offline cost decomposes per Lloyd step.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ring
from repro.core.channel import CommLog
from repro.core.sharing import AShare, BShare
from repro.obs import trace as _trace

KAPPA = 128  # computational security parameter (paper Sec 5.1)


class MatmulTriple(NamedTuple):
    u: AShare  # (n, d)
    v: AShare  # (d, k)
    z: AShare  # (n, k) with Z = U @ V mod 2^64


class MulTriple(NamedTuple):
    u: AShare
    v: AShare
    z: AShare  # elementwise, broadcastable


class BinTriple(NamedTuple):
    u: BShare
    v: BShare
    z: BShare  # bit-packed, z = u & v


class PoolExhaustedError(RuntimeError):
    """The online phase asked for correlated randomness the plan did not
    include (wrong shape-class, or more requests than planned)."""


# ---------------------------------------------------------------------------
# Offline communication cost model (documented formulas, paper-calibrated)
# ---------------------------------------------------------------------------

def ot_mul_triple_bytes(n_scalar_products: int, l: int = ring.L,
                        kappa: int = KAPPA) -> int:
    """Gilboa-style OT multiplication: l COTs of (kappa+l) bits, both dirs."""
    return int(n_scalar_products) * 2 * l * (kappa + l) // 8


def ot_bin_triple_bytes(n_bits: int, kappa: int = KAPPA) -> int:
    """Binary triples via R-OT: ~2(kappa+1) bits per AND gate."""
    return int(n_bits) * 2 * (kappa + 1) // 8


# Calibration: a 2.5 GHz Xeon does ~2e6 OT-extension 64-bit triple ops/s/core
# (ABY paper, Table 2 ballpark). Used only for the modelled offline *time*.
OT_TRIPLES_PER_SEC = 2.0e6
OT_BIN_TRIPLES_PER_SEC = 2.0e7


# ---------------------------------------------------------------------------
# Shape-class generation core — shared by the on-demand and bulk dealers
# ---------------------------------------------------------------------------
#
# A *shape-class* is (kind, shape); every request of a class draws the same
# flat block of full-range uint64 words from the class's own PCG64 stream.
# Because a stacked draw of `count` blocks equals `count` sequential
# single-block draws (verified by tests/test_triples_pool.py), the bulk
# dealer below is bit-identical to the on-demand dealer per construction.

_KIND_ID = {"matmul": 0, "mul": 1, "bin": 2, "rand": 3, "seed": 4}


def _class_key(kind: str, shape) -> tuple:
    if kind == "matmul":
        sa, sb = shape
        return (kind, tuple(sa), tuple(sb))
    return (kind, tuple(shape))


def _class_rng(seed: int, key: tuple) -> np.random.Generator:
    """Deterministic per-class stream: entropy = (seed, kind, dims...)."""
    kind = key[0]
    dims = [d for s in key[1:] for d in (len(s), *s)]
    ent = (int(seed), _KIND_ID[kind], *[int(d) for d in dims])
    return np.random.default_rng(np.random.SeedSequence(ent))


def _advanced_rng(seed: int, key: tuple, skip: int) -> np.random.Generator:
    """The class stream positioned AFTER `skip` requests — the checkpoint/
    resume primitive. `ring.rand_np` draws exactly `_class_words(key)` PCG64
    words per request, so one `bit_generator.advance` jump reconstructs the
    stream position of any request offset without replaying the draws."""
    rng = _class_rng(seed, key)
    if skip:
        rng.bit_generator.advance(int(skip) * _class_words(key))
    return rng


_SERVE_DOMAIN = 0x53657276  # "Serv"


def serve_seed(fit_seed: int) -> int:
    """Domain-separated dealer seed for the SERVING side of a model fitted
    under `fit_seed`. Per-class streams are keyed by (seed, class) only, so
    reusing the fit's seed for predict-time randomness would replay the
    exact Beaver masks the fit already consumed on overlapping shape-
    classes — and a mask reused on two secrets reveals their difference.
    Every serve-side default (predict/score's on-demand dealer, the
    ScoringService bank, the launch driver) derives its seed through this
    helper; only an explicitly passed equal seed can collide."""
    return int(np.random.SeedSequence(
        (int(fit_seed), _SERVE_DOMAIN)).generate_state(1, np.uint64)[0])


def _nelem(shape) -> int:
    return int(np.prod(shape, dtype=np.int64))


def _check_matmul_dims(shape_a, shape_b) -> None:
    """Planner bugs must surface under `python -O` too — never a bare
    assert."""
    if tuple(shape_a)[1] != tuple(shape_b)[0]:
        raise ValueError(
            f"matmul triple inner dims disagree: A is {tuple(shape_a)}, "
            f"B is {tuple(shape_b)}")


def _check_elemwise_shape(kind: str, shape) -> None:
    """Elementwise (mul/bin) triples take ONE tensor shape; a nested or
    non-integer 'shape' is a planner bug (e.g. a matmul-style ((n,d),(d,k))
    pair leaking into mul_triple) and must raise, matching the matmul inner-
    dim check above."""
    try:
        dims = tuple(shape)
    except TypeError:
        raise ValueError(
            f"{kind} triple shape must be an iterable of ints, "
            f"got {shape!r}") from None
    for s in dims:
        if isinstance(s, bool) or not isinstance(s, (int, np.integer)):
            raise ValueError(
                f"{kind} triple shape must be a flat tuple of ints, got "
                f"{dims!r} (offending entry {s!r})")
        if int(s) < 0:
            raise ValueError(
                f"{kind} triple shape has a negative dimension: {dims!r}")


def _gen_matmul(rng, sa, sb, count: int):
    """`count` matmul triples in one stacked draw + one batched ring matmul.

    Per-request word layout (the TrustedDealer draw order):
    u, v, mask_u, mask_v, mask_z. Returns six (count, ...) uint64 arrays
    (u0, u1, v0, v1, z0, z1)."""
    _check_matmul_dims(sa, sb)
    (n, d), (_, k) = tuple(sa), tuple(sb)
    nd, dk, nk = n * d, d * k, n * k
    per = 2 * nd + 2 * dk + nk
    flat = ring.rand_np(rng, (count, per))
    u = flat[:, :nd].reshape(count, n, d)
    v = flat[:, nd:nd + dk].reshape(count, d, k)
    mu = flat[:, nd + dk:2 * nd + dk].reshape(count, n, d)
    mv = flat[:, 2 * nd + dk:2 * (nd + dk)].reshape(count, d, k)
    mz = flat[:, 2 * (nd + dk):].reshape(count, n, k)
    z = np.einsum("bij,bjk->bik", u, v, dtype=ring.NP_DTYPE, casting="unsafe")
    return mu, u - mu, mv, v - mv, mz, z - mz


def _gen_mul(rng, shape, count: int):
    sz = _nelem(shape)
    flat = ring.rand_np(rng, (count, 5 * sz))
    u, v, mu, mv, mz = (flat[:, i * sz:(i + 1) * sz].reshape((count,) + tuple(shape))
                        for i in range(5))
    z = u * v  # uint64 wraps mod 2^64
    return mu, u - mu, mv, v - mv, mz, z - mz


def _gen_bin(rng, shape, count: int):
    sz = _nelem(shape)
    flat = ring.rand_np(rng, (count, 5 * sz))
    u, v, mu, mv, mz = (flat[:, i * sz:(i + 1) * sz].reshape((count,) + tuple(shape))
                        for i in range(5))
    z = u & v
    return mu, u ^ mu, mv, v ^ mv, mz, z ^ mz


def _gen_rand(rng, shape, count: int):
    return (ring.rand_np(rng, (count,) + tuple(shape)),)


def _gen_seed(rng, shape, count: int):
    # full-range uint64 seeds for host-side mask streams (Protocol 2 HE2SS)
    return (ring.rand_np(rng, (count,)),)


_GEN = {"mul": _gen_mul, "bin": _gen_bin, "rand": _gen_rand,
        "seed": _gen_seed}


def _gen_class(rng, kind: str, shape, count: int):
    if kind == "matmul":
        return _gen_matmul(rng, *shape, count)
    return _GEN[kind](rng, shape, count)


def _class_words(key: tuple) -> int:
    """PCG64 words ONE request of this shape-class draws — the stream
    advance per request. Must mirror the `_gen_*` draw widths above exactly:
    it is what lets a worker jump its class stream to an arbitrary request
    offset with `bit_generator.advance` and land on the same words a single
    stacked draw would produce there."""
    kind = key[0]
    if kind == "matmul":
        (n, d), (_, k) = key[1], key[2]
        return 2 * (n * d) + 2 * (d * k) + n * k
    if kind in ("mul", "bin"):
        return 5 * _nelem(key[1])
    if kind == "rand":
        return _nelem(key[1])
    return 1  # seed


# ---------------------------------------------------------------------------
# TrustedDealer — on-demand generation (oracle / no-preprocessing baseline)
# ---------------------------------------------------------------------------

class TrustedDealer:
    """On-demand offline-phase provider. Each request synthesizes one triple
    from its shape-class stream; logs modelled OT cost + measured dealer
    time. The host work lands on the online critical path — `PooledDealer`
    moves it into a true offline phase."""

    def __init__(self, seed: int = 0, log: CommLog | None = None,
                 backend=None, advance: dict | None = None):
        # `backend` is accepted for interface compatibility; generation is
        # host-side numpy (bit-exact with every ring backend by the parity
        # guarantee in core/backend.py).
        del backend
        self.seed = seed
        self.log = log if log is not None else CommLog()
        self._rngs: dict[tuple, np.random.Generator] = {}
        # checkpoint resume: {class_key: requests already consumed} — each
        # class stream starts pre-advanced past them (applied lazily in
        # _rng_for, matching the lazy stream creation)
        self._advance = {tuple(k): int(v)
                         for k, v in (advance or {}).items()}
        self.dealer_seconds = 0.0
        self.modelled_ot_seconds = 0.0
        self.n_matmul = 0
        self.n_mul = 0
        self.n_bin = 0

    # -- helpers ---------------------------------------------------------
    def _rng_for(self, key: tuple) -> np.random.Generator:
        rng = self._rngs.get(key)
        if rng is None:
            rng = self._rngs[key] = _advanced_rng(
                self.seed, key, self._advance.get(key, 0))
        return rng

    def _one(self, kind: str, shape):
        key = _class_key(kind, shape)
        out = _gen_class(self._rng_for(key), kind, shape, 1)
        return [jnp.asarray(a[0]) for a in out]

    def _account(self, scalar_products: int, tag: str) -> None:
        """Model OT generation traffic + dealer->party distribution."""
        self.log.send(ot_mul_triple_bytes(scalar_products), tag=tag,
                      phase="offline", rounds=2)
        self.modelled_ot_seconds += scalar_products / OT_TRIPLES_PER_SEC

    def matmul_triple(self, shape_a, shape_b, *, tag: str = "misc") -> MatmulTriple:
        t0 = time.perf_counter()
        (n, d), (_, k) = tuple(shape_a), tuple(shape_b)
        u0, u1, v0, v1, z0, z1 = self._one("matmul", (shape_a, shape_b))
        tr = MatmulTriple(AShare(u0, u1), AShare(v0, v1), AShare(z0, z1))
        self.dealer_seconds += time.perf_counter() - t0
        # A matrix triple is worth n*d*k scalar products under OT generation.
        self._account(n * d * k, tag)
        self.n_matmul += 1
        return tr

    def mul_triple(self, shape, *, tag: str = "misc") -> MulTriple:
        _check_elemwise_shape("mul", shape)
        t0 = time.perf_counter()
        u0, u1, v0, v1, z0, z1 = self._one("mul", shape)
        tr = MulTriple(AShare(u0, u1), AShare(v0, v1), AShare(z0, z1))
        self.dealer_seconds += time.perf_counter() - t0
        self._account(_nelem(shape), tag)
        self.n_mul += 1
        return tr

    def bin_triple(self, shape, *, tag: str = "misc") -> BinTriple:
        """Bit-packed binary AND triples: each uint64 lane = 64 AND gates."""
        _check_elemwise_shape("bin", shape)
        t0 = time.perf_counter()
        u0, u1, v0, v1, z0, z1 = self._one("bin", shape)
        tr = BinTriple(BShare(u0, u1), BShare(v0, v1), BShare(z0, z1))
        self.dealer_seconds += time.perf_counter() - t0
        n_bits = _nelem(shape) * 64
        self.log.send(ot_bin_triple_bytes(n_bits), tag=tag, phase="offline",
                      rounds=2)
        self.modelled_ot_seconds += n_bits / OT_BIN_TRIPLES_PER_SEC
        self.n_bin += 1
        return tr

    def rand(self, shape) -> jnp.ndarray:
        """Correlated-randomness source for share-resharing steps (B2A)."""
        return self._one("rand", shape)[0]

    def mask_seed(self) -> int:
        """Seed for a host-side statistical-mask stream (Protocol 2 HE2SS)."""
        return int(self._one("seed", ())[0])


# ---------------------------------------------------------------------------
# Planner — derive the exact offline schedule by dry-run trace
# ---------------------------------------------------------------------------

class PlanRequest(NamedTuple):
    kind: str    # matmul | mul | bin | rand | seed
    shape: tuple  # (sa, sb) for matmul, the tensor shape otherwise
    tag: str


@dataclasses.dataclass
class TriplePlan:
    """The correlated-randomness schedule of a protocol run, in consumption
    order. Data-independent: derived once per (n, k, d, iters, partition,
    sparsity) config and valid for every input of those shapes."""

    requests: list

    def repeat(self, reps: int) -> "TriplePlan":
        """Schedule of `reps` identical passes (e.g. Lloyd iterations)."""
        return TriplePlan(list(self.requests) * int(reps))

    def __add__(self, other: "TriplePlan") -> "TriplePlan":
        return TriplePlan(list(self.requests) + list(other.requests))

    def __len__(self) -> int:
        return len(self.requests)

    def class_counts(self) -> dict:
        """{class_key: count} — the shape-class histogram the bulk dealer
        generates, one stacked draw each."""
        out: dict[tuple, int] = {}
        for r in self.requests:
            key = _class_key(r.kind, r.shape)
            out[key] = out.get(key, 0) + 1
        return out

    def pool_words(self) -> int:
        """uint64 words a generated pool/tranche of this plan holds (six
        share tensors per triple, one tensor per rand, one word per seed) —
        the device-residency estimate the tranche-grouping heuristics size
        against."""
        words = 0
        for r in self.requests:
            if r.kind == "matmul":
                (n, d), (_, k) = r.shape
                words += 2 * (n * d + d * k + n * k)
            elif r.kind in ("mul", "bin"):
                words += 6 * _nelem(r.shape)
            elif r.kind == "rand":
                words += _nelem(r.shape)
            else:  # seed
                words += 1
        return words


class _TripleServing:
    """Shared dealer-interface surface for pool-backed providers: validate
    the request, draw its word tuple from ``self._next(kind, shape)``, wrap
    into the triple type, bump the counters. PooledDealer,
    StreamingPooledDealer, BankDealer and SlotDealer views all serve
    through this one implementation — only their `_next` differs."""

    def matmul_triple(self, shape_a, shape_b, *,
                      tag: str = "misc") -> MatmulTriple:
        _check_matmul_dims(shape_a, shape_b)
        u0, u1, v0, v1, z0, z1 = self._next(
            "matmul", (tuple(shape_a), tuple(shape_b)))
        self.n_matmul += 1
        return MatmulTriple(AShare(u0, u1), AShare(v0, v1), AShare(z0, z1))

    def mul_triple(self, shape, *, tag: str = "misc") -> MulTriple:
        _check_elemwise_shape("mul", shape)
        u0, u1, v0, v1, z0, z1 = self._next("mul", shape)
        self.n_mul += 1
        return MulTriple(AShare(u0, u1), AShare(v0, v1), AShare(z0, z1))

    def bin_triple(self, shape, *, tag: str = "misc") -> BinTriple:
        _check_elemwise_shape("bin", shape)
        u0, u1, v0, v1, z0, z1 = self._next("bin", shape)
        self.n_bin += 1
        return BinTriple(BShare(u0, u1), BShare(v0, v1), BShare(z0, z1))

    def rand(self, shape) -> jnp.ndarray:
        return self._next("rand", shape)[0]

    def mask_seed(self) -> int:
        return int(self._next("seed", ())[0])


class PlanningDealer:
    """Records the (kind, shape, tag) schedule while the traced code runs on
    zeros — the `ListDealer` replay discipline turned into a planner. The
    trace executes the real protocol (eagerly, on zero data), so control flow
    that depends on tensor *shapes* is followed exactly."""

    def __init__(self):
        self.requests: list[PlanRequest] = []

    def _z(self, shape):
        return jnp.zeros(shape, ring.DTYPE)

    def plan(self) -> TriplePlan:
        return TriplePlan(list(self.requests))

    def matmul_triple(self, shape_a, shape_b, *, tag: str = "misc"):
        _check_matmul_dims(shape_a, shape_b)
        (n, d), (_, k) = tuple(shape_a), tuple(shape_b)
        self.requests.append(
            PlanRequest("matmul", (tuple(shape_a), tuple(shape_b)), tag))
        return MatmulTriple(AShare(self._z((n, d)), self._z((n, d))),
                            AShare(self._z((d, k)), self._z((d, k))),
                            AShare(self._z((n, k)), self._z((n, k))))

    def mul_triple(self, shape, *, tag: str = "misc"):
        _check_elemwise_shape("mul", shape)
        self.requests.append(PlanRequest("mul", tuple(shape), tag))
        z = self._z(shape)
        return MulTriple(AShare(z, z), AShare(z, z), AShare(z, z))

    def bin_triple(self, shape, *, tag: str = "misc"):
        _check_elemwise_shape("bin", shape)
        self.requests.append(PlanRequest("bin", tuple(shape), tag))
        z = self._z(shape)
        return BinTriple(BShare(z, z), BShare(z, z), BShare(z, z))

    def rand(self, shape):
        self.requests.append(PlanRequest("rand", tuple(shape), "misc"))
        return self._z(shape)

    def mask_seed(self) -> int:
        self.requests.append(PlanRequest("seed", (), "misc"))
        return 0


# ---------------------------------------------------------------------------
# PooledDealer — planned bulk generation, zero-host-work serving
# ---------------------------------------------------------------------------

def _account_offline_plan(plan: TriplePlan, log: CommLog) -> float:
    """Log a plan's modelled OT generation traffic (identical totals to the
    on-demand dealer serving the same schedule); returns the modelled OT
    wall-time. Shared by the pooled and streaming dealers."""
    modelled_s = 0.0
    groups: dict[tuple, int] = {}
    for r in plan.requests:
        key = (r.kind, _class_key(r.kind, r.shape), r.tag)
        groups[key] = groups.get(key, 0) + 1
    for (kind, key, tag), count in groups.items():
        if kind == "matmul":
            (n, d), (_, k) = key[1], key[2]
            sp = n * d * k
            log.send(count * ot_mul_triple_bytes(sp), tag=tag,
                     phase="offline", rounds=2 * count)
            modelled_s += count * sp / OT_TRIPLES_PER_SEC
        elif kind == "mul":
            sp = _nelem(key[1])
            log.send(count * ot_mul_triple_bytes(sp), tag=tag,
                     phase="offline", rounds=2 * count)
            modelled_s += count * sp / OT_TRIPLES_PER_SEC
        elif kind == "bin":
            n_bits = _nelem(key[1]) * 64
            log.send(count * ot_bin_triple_bytes(n_bits), tag=tag,
                     phase="offline", rounds=2 * count)
            modelled_s += count * n_bits / OT_BIN_TRIPLES_PER_SEC
    return modelled_s


def _host_row(a: np.ndarray, i: int) -> np.ndarray:
    """Request i of a stacked (count, ...) host array, as a C-contiguous
    array (0-d for the seed class). The `_gen_*` arrays' rows are views
    that already are; only another layout is copied."""
    row = a[i, ...]
    return row if row.flags.c_contiguous else np.ascontiguousarray(row)


def _put_class(name: str, arrays: tuple, count: int) -> tuple:
    """Put one shape-class's `count` requests on the device; returns
    ([(array, ...) per request], bytes put), the arrays in the `_gen_*`
    order, uint64 on the default device. `dealer.split` cuts the stacked
    host arrays into per-request row views (list work, no device op);
    `dealer.upload` puts the whole list with ONE `jax.device_put`, so no
    request costs a dispatch of its own and the stacked array never exists
    on the device. `arrays=` on the upload span counts the buffers made."""
    nbytes = sum(a.nbytes for a in arrays)
    with _trace.span("dealer.split", key=name, count=count):
        rows = [tuple(_host_row(a, i) for a in arrays) for i in range(count)]
    with _trace.span("dealer.upload", key=name, count=count,
                     h2d_bytes=nbytes, arrays=count * len(arrays)):
        return jax.device_put(rows), nbytes


def _gen_tranche(rngs: dict, counts: dict):
    """Generate one {class key: [per-request device-array tuples]} tranche
    from persistent per-class RNG streams. Because a class's stream is
    advanced by exactly count*words_per_request words per call, consecutive
    tranches concatenate to the single stacked draw PooledDealer performs —
    the bit-exactness property, chunked.

    Each class's three costs are spans of their own: `dealer.draw` (the
    host draw and ring products), `dealer.split` (host list work: the
    stacked arrays cut into per-request row views) and `dealer.upload`
    (the one device put of all of them, with its `h2d_bytes`)."""
    pools: dict[tuple, list] = {}
    nbytes = 0
    for key, count in counts.items():
        kind = key[0]
        shape = key[1:] if kind == "matmul" else key[1]
        name = _key_to_str(key)
        with _trace.span("dealer.draw", key=name, count=count):
            arrays = _gen_class(rngs[key], kind, shape, count)
        pools[key], size = _put_class(name, arrays, count)
        nbytes += size
    return pools, nbytes


class PooledDealer(_TripleServing):
    """Executes a `TriplePlan` up front and serves it back as a plain list
    index over per-request device arrays.

    Generation batches every shape-class into ONE stacked RNG draw and one
    batched ring op (`np.einsum` over the stacked operands for matmul
    triples, elementwise `*`/`&` otherwise), then puts each class's
    per-request arrays on the device in one call. Bit-exact with
    `TrustedDealer(seed)` serving the same request sequence: per-class
    streams + the uint64 draw-concatenation property make the stacked draw
    identical to the per-request draws.

    Serving past the planned count — or requesting a shape-class the plan
    never mentioned — raises `PoolExhaustedError`: the trace and the online
    run disagreed, which is a planner bug, not a condition to paper over.
    """

    def __init__(self, plan: TriplePlan, seed: int = 0,
                 log: CommLog | None = None, advance: dict | None = None):
        t0 = time.perf_counter()
        self.plan = plan
        self.seed = seed
        self.log = log if log is not None else CommLog()
        self.modelled_ot_seconds = 0.0
        self.n_matmul = 0
        self.n_mul = 0
        self.n_bin = 0
        self._served: dict[tuple, int] = {}     # class key -> cursor
        counts = plan.class_counts()
        # one host->device put per class of its per-request arrays HERE
        # (still offline), so online serving is a plain list index — no
        # gather launches on the critical path.
        # `advance`: checkpoint resume — pass the REMAINING plan and the
        # per-class request counts the interrupted run already consumed;
        # each class stream jumps past them before generating.
        advance = advance or {}
        rngs = {key: _advanced_rng(seed, key, advance.get(key, 0))
                for key in counts}
        self._pools, self.pool_bytes = _gen_tranche(rngs, counts)
        self._served = {key: 0 for key in counts}
        self.modelled_ot_seconds = _account_offline_plan(plan, self.log)
        self.dealer_seconds = time.perf_counter() - t0

    # -- serving ---------------------------------------------------------
    def _next(self, kind: str, shape) -> tuple:
        key = _class_key(kind, shape)
        pool = self._pools.get(key)
        if pool is None:
            raise PoolExhaustedError(
                f"no pool for {kind} {shape}: the offline plan never "
                "scheduled this shape-class (planner/online mismatch)")
        i = self._served[key]
        if i >= len(pool):
            raise PoolExhaustedError(
                f"pool exhausted for {kind} {shape}: planned "
                f"{len(pool)} requests, online asked for more")
        self._served[key] = i + 1
        return pool[i]

    def remaining(self) -> dict:
        """{class_key: unserved} — surplus after e.g. tol early-stop."""
        return {k: len(p) - self._served[k] for k, p in self._pools.items()}


# ---------------------------------------------------------------------------
# StreamingPooledDealer — double-buffered per-iteration pool generation
# ---------------------------------------------------------------------------

GROUP_TRANCHE_BYTES = 4 << 20
# auto-grouping target: when one iteration's tranche is tiny (small k*d),
# generating it alone makes the background worker wake up per iteration for
# microseconds of work — group consecutive iterations until a tranche
# reaches ~this many device bytes (bit-exact either way: the per-class
# streams just advance in bigger stacked draws).


class StreamingPooledDealer(_TripleServing):
    """`PooledDealer` semantics with O(1-iteration) device residency.

    Instead of materializing `iters` iterations' worth of every shape-class
    up front (pool residency O(iters), capping fit size at device memory),
    the plan of ONE iteration is generated as a *tranche* — one stacked draw
    + one batched ring op + one upload per shape-class, exactly like the bulk
    dealer but with per-iteration counts — and tranche t+1 is generated on a
    background worker WHILE iteration t's launches consume tranche t. At any
    moment at most `prefetch` tranches are alive (double-buffered by
    default), so peak residency is independent of `iters`.

    Bit-exact with ``PooledDealer(iter_plan.repeat(iters), seed)``: each
    shape-class keeps ONE persistent PCG64 stream across tranches, and the
    uint64 draw-concatenation property makes `iters` sequential per-iteration
    draws identical to the single stacked draw (property-tested in
    tests/test_triples_pool.py).

    Tranche advance is request-counted: the online phase consumes exactly
    ``len(iter_plan)`` requests per iteration (the plan IS the per-iteration
    schedule), so when that many have been served the current tranche's
    device buffers are dropped, the prefetched tranche becomes current, and
    generation of the next one is dispatched. Serving past the per-iteration
    class count — or an unplanned class — raises `PoolExhaustedError` just
    like the bulk dealer.

    Timing accounting: ``dealer_seconds`` is construction (first-tranche)
    time only; generation overlapped with the online loop accumulates in
    ``gen_seconds`` (worker wall-time) and ``wait_seconds`` (time the online
    loop blocked on a tranche that was not ready — real online stalls, left
    IN the caller's online wall-clock on purpose).
    """

    def __init__(self, iter_plan: TriplePlan, iters: int, seed: int = 0,
                 log: CommLog | None = None, prefetch: int = 2,
                 async_gen: bool = True, group: int | str = 1,
                 advance: dict | None = None):
        t0 = time.perf_counter()
        self.iter_plan = TriplePlan(list(iter_plan.requests))
        self.iters = int(iters)
        self.seed = seed
        self.log = log if log is not None else CommLog()
        self.n_matmul = 0
        self.n_mul = 0
        self.n_bin = 0
        self._iter_counts = self.iter_plan.class_counts()
        self._per_iter = len(self.iter_plan)
        # tranche grouping: `group` iterations share one generation wakeup
        # (one stacked draw per class covers them all — the concatenation
        # property keeps every served word identical to group=1); "auto"
        # sizes tranches to ~GROUP_TRANCHE_BYTES so tiny k*d fits don't pay
        # a worker wakeup per iteration
        if group == "auto":
            words = max(1, self.iter_plan.pool_words())
            group = max(1, GROUP_TRANCHE_BYTES // (8 * words))
        self.group = max(1, min(int(group), max(1, self.iters)))
        self._tranche_iters = 1      # iterations covered by _current
        # checkpoint resume: `iters` = REMAINING iterations; `advance` =
        # per-class requests the interrupted run already consumed
        advance = advance or {}
        self._rngs = {key: _advanced_rng(seed, key, advance.get(key, 0))
                      for key in self._iter_counts}
        self.modelled_ot_seconds = _account_offline_plan(
            self.iter_plan.repeat(self.iters), self.log)
        self.gen_seconds = 0.0
        self.wait_seconds = 0.0
        self.pool_bytes = 0          # PEAK concurrent device residency
        self._live_bytes = 0
        import threading
        self._lock = threading.Lock()
        self._executor = None
        if async_gen:
            from concurrent.futures import ThreadPoolExecutor
            self._executor = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="triple-dealer")
        self._pending: list = []     # generated-or-in-flight tranches, FIFO
        self._next_gen = 0           # next tranche index to dispatch
        self._current: dict | None = None
        self._current_bytes = 0
        self._cursors: dict[tuple, int] = {}
        self._served_in_tranche = 0
        self.served_iters = 0
        for _ in range(max(1, prefetch)):
            self._dispatch()
        if self._per_iter and self.iters:
            self._advance()
        # the first-tranche wait is construction (offline) time, already in
        # dealer_seconds — wait_seconds reports ONLINE stalls only
        self.wait_seconds = 0.0
        self.dealer_seconds = time.perf_counter() - t0

    # -- tranche lifecycle ----------------------------------------------
    def _generate(self, counts):
        t0 = time.perf_counter()
        pools, nbytes = _gen_tranche(self._rngs, counts)
        with self._lock:
            self.gen_seconds += time.perf_counter() - t0
            self._live_bytes += nbytes
            self.pool_bytes = max(self.pool_bytes, self._live_bytes)
        return pools, nbytes

    def _dispatch(self) -> None:
        """Queue generation of the next tranche (async on the worker) —
        covering `group` iterations (fewer for the tail). The single worker
        serializes tranches, so the per-class streams advance in tranche
        order no matter when the futures are submitted."""
        if self._next_gen >= self.iters:
            return
        g = min(self.group, self.iters - self._next_gen)
        self._next_gen += g
        counts = self._iter_counts if g == 1 else \
            {k: c * g for k, c in self._iter_counts.items()}
        if self._executor is None:
            self._pending.append((g, "done", self._generate(counts)))
        else:
            self._pending.append(
                (g, "fut", self._executor.submit(self._generate, counts)))

    def _advance(self) -> None:
        g, kind, payload = self._pending.pop(0)
        t0 = time.perf_counter()
        pools, nbytes = payload.result() if kind == "fut" else payload
        self.wait_seconds += time.perf_counter() - t0
        self._current, self._current_bytes = pools, nbytes
        self._tranche_iters = g
        self._cursors = {}
        self._served_in_tranche = 0

    def _drop_current(self) -> None:
        self._current = None
        with self._lock:
            self._live_bytes -= self._current_bytes
        self._current_bytes = 0

    def _finish_tranche(self) -> None:
        """Drop the consumed tranche and queue the next generation. The
        ADVANCE to the prefetched tranche is deferred to the next serve
        call: blocking here would make the LAST iteration of a tol
        early-stopped fit stall on randomness it is about to throw away."""
        self.served_iters += self._tranche_iters
        self._drop_current()
        self._cursors = {}
        self._served_in_tranche = 0
        self._dispatch()
        if self.served_iters >= self.iters and self._executor is not None:
            self._executor.shutdown(wait=False)

    # -- serving ---------------------------------------------------------
    def _next(self, kind: str, shape) -> tuple:
        key = _class_key(kind, shape)
        per_iter = self._iter_counts.get(key)
        if per_iter is None:
            raise PoolExhaustedError(
                f"no pool for {kind} {shape}: the offline plan never "
                "scheduled this shape-class (planner/online mismatch)")
        if self._current is None and self.served_iters < self.iters:
            self._advance()                  # lazy: first request of an iter
        i = self._cursors.get(key, 0)
        if self._current is None or i >= per_iter * self._tranche_iters:
            raise PoolExhaustedError(
                f"pool exhausted for {kind} {shape}: planned {per_iter} "
                f"requests/iteration x {self.iters} iterations, online "
                "asked for more")
        self._cursors[key] = i + 1
        out = self._current[key][i]
        self._served_in_tranche += 1
        if self._served_in_tranche == self._per_iter * self._tranche_iters:
            self._finish_tranche()
        return out

    def remaining(self) -> dict:
        """{class_key: unserved across ALL remaining iterations} — surplus
        after e.g. a tol early-stop (undispatched tranches are never even
        generated)."""
        rem_tranches = self.iters - self.served_iters
        out = {}
        for key, c in self._iter_counts.items():
            out[key] = rem_tranches * c - self._cursors.get(key, 0)
        return out

    def close(self) -> None:
        """Drop buffers and stop the worker — called by an early-stopped
        fit so the prefetched tranches and the executor thread don't outlive
        the loop (idempotent; a fully-served fit has already shut the worker
        down via the last tranche). `remaining()` stays valid after close:
        it is pure counter arithmetic."""
        if self._executor is not None:
            self._executor.shutdown(wait=True)   # let in-flight gen finish
        for _g, kind, payload in self._pending:
            pools, nbytes = payload.result() if kind == "fut" else payload
            del pools
            with self._lock:
                self._live_bytes -= nbytes
        self._pending.clear()
        if self._current is not None:
            self._drop_current()


# ---------------------------------------------------------------------------
# SlotDealer — per-(iteration, batch) tranches for the pipelined executor
# ---------------------------------------------------------------------------

def _gen_tranche_split(rngs: dict, counts_list: list):
    """Generate several consecutive tranches in ONE merged stacked draw per
    shape-class (and one device put per class), then hand the per-request
    device tuples back out per tranche: list slicing only, no device op.
    Stream-identical to generating each tranche separately (the uint64
    draw-concatenation property) — this is what lets one worker wakeup
    amortize over several small slots. Returns [(pools, nbytes), ...]."""
    merged: dict[tuple, int] = {}
    for counts in counts_list:
        for key, c in counts.items():
            merged[key] = merged.get(key, 0) + c
    pools, _ = _gen_tranche(rngs, merged)
    cursors = {key: 0 for key in merged}
    out = []
    for counts in counts_list:
        slot_pools: dict[tuple, list] = {}
        slot_bytes = 0
        for key, c in counts.items():
            i = cursors[key]
            entries = pools[key][i:i + c]
            cursors[key] = i + c
            slot_pools[key] = entries
            slot_bytes += sum(int(a.size) * 8 for t in entries for a in t)
        out.append((slot_pools, slot_bytes))
    return out


class _SlotView(_TripleServing):
    """Dealer view over ONE acquired slot tranche: serves exactly the
    slot's planned requests (per-class cursors, `PoolExhaustedError` past
    them). Counters aggregate on the owning SlotDealer; when the last
    request is served the tranche's device buffers are released and the
    dealer's generation window frees a slot."""

    def __init__(self, dealer: "SlotDealer", index: int, pools: dict,
                 counts: dict, total: int, nbytes: int):
        self.dealer = dealer
        self.index = index
        self.log = dealer.log
        self._pools = pools
        self._counts = counts
        self._total = total
        self._nbytes = nbytes
        self._cursors: dict[tuple, int] = {}
        self._served = 0

    # the fit-level dealer counters live on the SlotDealer so results can
    # compare them across offline/pipeline modes
    @property
    def n_matmul(self):
        return self.dealer.n_matmul

    @n_matmul.setter
    def n_matmul(self, v):
        self.dealer.n_matmul = v

    @property
    def n_mul(self):
        return self.dealer.n_mul

    @n_mul.setter
    def n_mul(self, v):
        self.dealer.n_mul = v

    @property
    def n_bin(self):
        return self.dealer.n_bin

    @n_bin.setter
    def n_bin(self, v):
        self.dealer.n_bin = v

    def _next(self, kind: str, shape) -> tuple:
        key = _class_key(kind, shape)
        limit = self._counts.get(key)
        if limit is None:
            raise PoolExhaustedError(
                f"no pool for {kind} {shape} in slot {self.index}: the slot "
                "plan never scheduled this shape-class (planner/online "
                "mismatch)")
        i = self._cursors.get(key, 0)
        if i >= limit:
            raise PoolExhaustedError(
                f"slot {self.index} pool exhausted for {kind} {shape}: "
                f"planned {limit} requests, online asked for more")
        self._cursors[key] = i + 1
        out = self._pools[key][i]
        self._served += 1
        if self._served == self._total:
            self._pools = {}
            self.dealer._release(self.index, self._nbytes)
        return out


class SlotDealer:
    """Per-slot tranche pools for the pipelined minibatch executor
    (DESIGN.md §11).

    The offline schedule is a SEQUENCE of slot plans — e.g. per Lloyd
    iteration ``[S1(batch 0), S3(batch 0), S1(batch 1), ..., finalize]`` —
    and each slot's correlated randomness is generated as its own tranche
    from the SAME persistent per-class PCG64 streams as every other dealer,
    always in canonical slot order. ``acquire(i)`` hands slot i's tranche
    out as a dealer view; acquisition may run AHEAD of lower slots (the
    pipelined executor pins batch t+1's S1 tranche while batch t's launch
    is still in flight) without perturbing a single served word, because
    GENERATION order — not acquisition order — fixes the streams. That is
    the double-buffer contract that makes ``pipeline=True`` stream-identical
    to ``pipeline=False``.

    stream=False (the pooled offline phase): every slot is generated up
    front in one merged stacked draw per shape-class — PooledDealer
    residency and bulk-generation speed, slot-indexed serving. stream=True:
    a background worker generates slots in order with at most ``window``
    generated-but-unconsumed slots alive (backpressure), so peak residency
    is O(window x slot bytes) — independent of n and iters. ``group_bytes``
    merges consecutive small slots into one generation wakeup (still split
    and served per slot; "auto" targets GROUP_TRANCHE_BYTES). A wakeup puts
    each shape-class's per-request arrays on the device in one call, so a
    slot's tranche is a list of device arrays ready to serve: handing it
    out runs no device op.

    Bit-exact with ``PooledDealer(concat(slot_plans), seed)`` for any
    acquisition order that consumes each slot's own plan exactly
    (property-tested in tests/test_pipeline.py)."""

    def __init__(self, slot_plans, seed: int = 0, log: CommLog | None = None,
                 stream: bool = True, window: int = 4, async_gen: bool = True,
                 group_bytes: int | str = "auto", start_slot: int = 0):
        import threading
        t0 = time.perf_counter()
        self.slot_plans = [TriplePlan(list(p.requests)) for p in slot_plans]
        self.seed = seed
        self.log = log if log is not None else CommLog()
        self.stream = bool(stream)
        # checkpoint resume: slots < start_slot were consumed by the
        # interrupted run — never generated here; each class stream starts
        # advanced past their requests (canonical slot order fixes the
        # offsets), so slot start_slot serves the EXACT words it would have
        self.start_slot = int(start_slot)
        if not 0 <= self.start_slot <= len(self.slot_plans):
            raise IndexError(f"start_slot {start_slot} out of range "
                             f"({len(self.slot_plans)} slots planned)")
        self.n_matmul = 0
        self.n_mul = 0
        self.n_bin = 0
        self.gen_seconds = 0.0
        self.wait_seconds = 0.0      # online acquire() stalls
        self.pool_bytes = 0          # PEAK concurrent device residency
        self._live_bytes = 0
        self._live_slots = 0
        self._counts = [p.class_counts() for p in self.slot_plans]
        self._totals = [len(p) for p in self.slot_plans]
        keys = sorted({k for c in self._counts for k in c})
        skip: dict[tuple, int] = {}
        for counts in self._counts[:self.start_slot]:
            for key, c in counts.items():
                skip[key] = skip.get(key, 0) + c
        self._rngs = {key: _advanced_rng(seed, key, skip.get(key, 0))
                      for key in keys}
        # only the slots this dealer will actually generate hit its offline
        # books (a resumed fit's checkpoint already carries the full tallies)
        self.modelled_ot_seconds = _account_offline_plan(
            TriplePlan([r for p in self.slot_plans[self.start_slot:]
                        for r in p.requests]),
            self.log)
        if group_bytes == "auto":
            group_bytes = GROUP_TRANCHE_BYTES
        # partition the REMAINING slots into generation groups of
        # >= group_bytes each
        self._groups: list[tuple[int, int]] = []
        i = self.start_slot
        while i < len(self.slot_plans):
            j = i + 1
            b = 8 * self.slot_plans[i].pool_words()
            while j < len(self.slot_plans) and b < int(group_bytes):
                b += 8 * self.slot_plans[j].pool_words()
                j += 1
            self._groups.append((i, j))
            i = j
        self._ready: dict[int, tuple] = {}   # slot -> (pools, nbytes)
        self._acquired: set[int] = set(range(self.start_slot))
        self._served_class: dict[tuple, int] = dict(skip)
        self._cond = threading.Condition()
        self._closed = False
        self._error: BaseException | None = None
        self._next_group = 0
        self._max_requested = -1     # highest slot a caller is waiting on
        self._worker = None
        if not self.stream:
            # pooled: ONE merged generation pass over the remaining schedule
            for i, tr in enumerate(_gen_tranche_split(
                    self._rngs, self._counts[self.start_slot:]),
                    start=self.start_slot):
                self._ready[i] = tr
                self._live_bytes += tr[1]
                self._live_slots += 1
            self._next_group = len(self._groups)
            self.pool_bytes = self._live_bytes
        elif async_gen and self._groups:
            max_group = max(hi - lo for lo, hi in self._groups)
            self._window = max(int(window), max_group + 1)
            self._worker = threading.Thread(target=self._worker_loop,
                                            name="slot-dealer", daemon=True)
            self._worker.start()
        else:
            self._window = max(2, int(window))
        self.dealer_seconds = time.perf_counter() - t0

    # -- generation ------------------------------------------------------
    def _gen_group(self, gi: int) -> None:
        """Generate group gi's slots (caller holds no lock); fill _ready."""
        lo, hi = self._groups[gi]
        t0 = time.perf_counter()
        tranches = _gen_tranche_split(self._rngs, self._counts[lo:hi])
        with self._cond:
            self.gen_seconds += time.perf_counter() - t0
            for i, tr in zip(range(lo, hi), tranches):
                self._ready[i] = tr
                self._live_slots += 1
                self._live_bytes += tr[1]
            self.pool_bytes = max(self.pool_bytes, self._live_bytes)
            self._cond.notify_all()

    def _worker_loop(self) -> None:
        try:
            for gi, (lo, hi) in enumerate(self._groups):
                with self._cond:
                    # backpressure: hold generation at `window` live slots —
                    # unless a caller is already WAITING on a slot this
                    # group must be generated for (acquire can run ahead of
                    # consumption; stalling it here would deadlock)
                    while (self._live_slots + (hi - lo) > self._window
                           and lo > self._max_requested
                           and not self._closed):
                        self._cond.wait()
                    if self._closed:
                        return
                self._gen_group(gi)
        except BaseException as e:             # surface on the next acquire
            with self._cond:
                self._error = e
                self._cond.notify_all()

    # -- acquisition -----------------------------------------------------
    def acquire(self, i: int) -> _SlotView:
        """Slot i's tranche as a dealer view (blocking until generated).
        Each slot can be acquired exactly once; out-of-order acquisition is
        fine within the generation window — the words a slot serves are
        fixed at generation time."""
        if not 0 <= i < len(self.slot_plans):
            raise IndexError(f"slot {i} out of range "
                             f"({len(self.slot_plans)} slots planned)")
        with _trace.span("dealer.acquire", slot=i):
            return self._acquire(i)

    def _acquire(self, i: int) -> _SlotView:
        t0 = time.perf_counter()
        with self._cond:
            if i in self._acquired:
                raise PoolExhaustedError(
                    f"slot {i} was already acquired: each slot serves its "
                    "plan exactly once")
            if self._worker is None:
                # inline generation (pooled mode is pre-filled; streamed
                # sync mode generates groups on demand, in canonical order)
                while i not in self._ready \
                        and self._next_group < len(self._groups):
                    gi = self._next_group
                    self._next_group += 1
                    self._cond.release()
                    try:
                        self._gen_group(gi)
                    finally:
                        self._cond.acquire()
            else:
                self._max_requested = max(self._max_requested, i)
                self._cond.notify_all()
                while i not in self._ready and self._error is None \
                        and not self._closed:
                    self._cond.wait()
            if self._error is not None:
                raise RuntimeError("slot-dealer worker failed") \
                    from self._error
            if i not in self._ready:
                raise PoolExhaustedError(f"slot {i} unavailable "
                                         "(dealer closed or out of range)")
            pools, nbytes = self._ready.pop(i)
            self._acquired.add(i)
            self.wait_seconds += time.perf_counter() - t0
        view = _SlotView(self, i, pools, self._counts[i], self._totals[i],
                         nbytes)
        if self._totals[i] == 0:               # empty slot: nothing to serve
            self._release(i, nbytes)
        return view

    def _release(self, i: int, nbytes: int) -> None:
        with self._cond:
            for key, c in self._counts[i].items():
                self._served_class[key] = self._served_class.get(key, 0) + c
            self._live_slots -= 1
            self._live_bytes -= nbytes
            self._cond.notify_all()

    def remaining(self) -> dict:
        """{class_key: unserved across unacquired + unconsumed slots} —
        surplus after e.g. a tol early-stop."""
        total: dict[tuple, int] = {}
        for counts in self._counts:
            for key, c in counts.items():
                total[key] = total.get(key, 0) + c
        return {key: c - self._served_class.get(key, 0)
                for key, c in total.items()}

    def close(self) -> None:
        """Early-stop cleanup: stop the worker and drop generated-but-
        unacquired tranches (idempotent)."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        if self._worker is not None:
            self._worker.join()
            self._worker = None
        with self._cond:
            for i, (_pools, nbytes) in self._ready.items():
                self._live_slots -= 1
                self._live_bytes -= nbytes
            self._ready.clear()


# ---------------------------------------------------------------------------
# TripleBank — persistent cross-fit pool: provision once, serve many
# ---------------------------------------------------------------------------

def _key_to_str(key: tuple) -> str:
    return repr(tuple(key))


def _key_from_str(s: str) -> tuple:
    import ast
    return tuple(ast.literal_eval(s))


_SLOTS = {"matmul": 6, "mul": 6, "bin": 6, "rand": 1, "seed": 1}


def _provision_items(counts: dict, workers: int) -> list:
    """Deterministic split of a bulk-generation request into `(class_key,
    start, count)` chunks: whole classes, the heavy ones subdivided by word
    volume so no worker idles behind one giant class. A pure function of
    `(counts, workers)` — scheduling and completion order cannot influence
    which words a chunk draws, because each chunk re-derives its stream
    position from (class stream start, request offset) alone."""
    total = sum(int(c) * _class_words(k) for k, c in counts.items())
    target = max(1, -(-total // max(1, int(workers))))
    items = []
    for key in sorted(counts):
        count = int(counts[key])
        if count <= 0:
            continue
        wpr = _class_words(key)
        nchunks = max(1, min(count, -(-(count * wpr) // target)))
        base, extra = divmod(count, nchunks)
        start = 0
        for i in range(nchunks):
            cnt = base + (1 if i < extra else 0)
            items.append((key, start, cnt))
            start += cnt
    return items


def _gen_provision_item(states: dict, item: tuple) -> tuple:
    """Generate one chunk from a PRIVATE clone of its class stream, advanced
    to the chunk's request offset. `ring.rand_np` draws exactly
    `_class_words(key)` PCG64 words per request, so advance(start*words)
    lands the clone on the words request `start` of the serial stacked draw
    would consume — chunked generation concatenates to the serial draw
    bit-for-bit."""
    key, start, count = item
    rng = np.random.default_rng(0)
    rng.bit_generator.state = states[key]
    if start:
        rng.bit_generator.advance(int(start) * _class_words(key))
    kind = key[0]
    shape = key[1:] if kind == "matmul" else key[1]
    return _put_class(_key_to_str(key), _gen_class(rng, kind, shape, count),
                      count)


def _run_provision_items(items: list, states: dict, workers: int) -> list:
    """Run the chunks on a thread pool; results come back in ITEM order
    regardless of completion order (positional assembly), which together
    with per-chunk stream derivation makes the whole pass order-oblivious."""
    from concurrent.futures import ThreadPoolExecutor
    with ThreadPoolExecutor(max_workers=int(workers)) as ex:
        return list(ex.map(lambda it: _gen_provision_item(states, it), items))


class TripleBank:
    """A persistent correlated-randomness store serving MANY protocol runs
    (fits, predict batches, scoring services) from one provisioning pass.

    Structure: one FIFO queue of per-request tensor tuples per shape-class,
    fed by the same per-class PCG64 streams as every dealer — so a freshly
    provisioned bank serves bit-identical words to a same-seeded
    `TrustedDealer` for any request sequence with matching per-class order
    (the PooledDealer property, lifted across runs). Plans are registered
    under a lookup key (`SecureKMeans.plan_predict`'s key — the predict-plan
    cache key — by convention) via `provision`; `dealer(key)` hands out a
    `BankDealer` view that draws from the shared class queues.

    Exhaustion: where PooledDealer raises `PoolExhaustedError` (the fit
    trace/online mismatch is a bug), a serving bank treats an empty class as
    *stock-out*, not corruption — with `auto_replenish` it synchronously
    generates one more tranche of the requesting key's registered plan
    (stream-continuous: the class streams simply advance) and keeps serving;
    the stall is counted in `replenish_events`/`replenish_seconds` so a
    service can size `copies` to keep replenishment off the online path.

    Persistence: `save`/`load` round-trip the unserved tranches AND the
    per-class RNG states via one `np.savez` archive, so a reloaded bank
    serves the exact words the original would have — and replenishes from
    the same stream positions.

    Thread safety: a standing `BankReplenisher` daemon may top the bank up
    while serving threads draw from it. Two locks split the contention:
    `_gen_lock` serializes every per-class STREAM advance (two concurrent
    generations from the same snapshot would fork a class stream and serve
    the same mask words twice — a correctness *and* privacy bug), while
    the short-critical-section `_lock` guards the queues and counters so
    the hot-path pop never waits behind a long generation unless the shelf
    is actually empty. Lock order is `_gen_lock` → `_lock`; nothing
    acquires `_gen_lock` while holding `_lock`.
    """

    def __init__(self, seed: int = 0, auto_replenish: bool = True,
                 log: CommLog | None = None):
        self.seed = int(seed)
        self.auto_replenish = auto_replenish
        self.log = log if log is not None else CommLog()
        self._rngs: dict[tuple, np.random.Generator] = {}
        self._queues: dict[tuple, list] = {}
        self._plans: dict[tuple, TriplePlan] = {}
        self._lock = threading.RLock()       # queues + counters
        self._gen_lock = threading.RLock()   # per-class stream advance
        self.modelled_ot_seconds = 0.0
        self.gen_seconds = 0.0
        self.replenish_seconds = 0.0
        self.replenish_events = 0
        self.pool_bytes = 0              # live (unserved) device bytes
        self.served_requests = 0
        self.consumed_class: dict[tuple, int] = {}   # lifetime pops per class

    # -- provisioning ----------------------------------------------------
    def _gen(self, counts: dict, workers: int = 1) -> None:
        t0 = time.perf_counter()
        with self._gen_lock:
            for key in counts:
                self._rngs.setdefault(key, _class_rng(self.seed, key))
            if workers <= 1 or len(counts) == 0:
                pools, nbytes = _gen_tranche(self._rngs, counts)
                with self._lock:
                    for key, entries in pools.items():
                        self._queues.setdefault(key, []).extend(entries)
                    self.pool_bytes += nbytes
            else:
                items = _provision_items(counts, workers)
                # snapshot the CURRENT stream positions: chunks are offsets
                # relative to where the serial draw would start
                states = {key: self._rngs[key].bit_generator.state
                          for key in counts}
                results = _run_provision_items(items, states, workers)
                with self._lock:
                    for (key, _start, _cnt), (entries, nbytes) in zip(
                            items, results):
                        self._queues.setdefault(key, []).extend(entries)
                        self.pool_bytes += nbytes
                # master streams end exactly where one stacked draw would
                for key, count in counts.items():
                    self._rngs[key].bit_generator.advance(
                        int(count) * _class_words(key))
            self.gen_seconds += time.perf_counter() - t0

    def provision(self, key, plan: TriplePlan, copies: int = 1,
                  workers: int = 1) -> None:
        """Register `plan` under the lookup `key` and bulk-generate
        `copies` executions' worth of it into the class queues (one stacked
        draw + one batched ring op per class, like PooledDealer). Calling
        again with the same key re-registers (a changed plan replaces the
        old one) and tops the stock up.

        `workers > 1` fans the generation out over a thread pool — the bulk
        of the work is GIL-releasing numpy RNG + einsum — split by
        shape-class (the per-class PCG64 streams make classes order-
        independent) and, within a heavy class, by `advance`-offset chunks.
        The produced words are bit-identical to the serial draw for ANY
        worker count and completion order (property-tested)."""
        key = tuple(key)
        with self._lock:
            self._plans[key] = TriplePlan(list(plan.requests))
        if copies > 0:
            with _trace.span("bank.provision", key=str(key),
                             copies=int(copies), workers=int(workers)):
                counts = {ck: c * int(copies)
                          for ck, c in plan.class_counts().items()}
                self._gen(counts, workers=workers)
                self.modelled_ot_seconds += _account_offline_plan(
                    plan.repeat(copies), self.log)

    def keys(self) -> list:
        with self._lock:
            return list(self._plans)

    def stock(self) -> dict:
        """{class_key: unserved request count} across the whole bank."""
        with self._lock:
            return {k: len(q) for k, q in self._queues.items()}

    def stock_copies(self, key) -> int:
        """Complete executions of `key`'s registered plan in stock: the
        min over its classes of shelf depth // per-execution count."""
        key = tuple(key)
        with self._lock:
            plan = self._plans[key]
            counts = plan.class_counts()
            if not counts:
                return 0
            return min(len(self._queues.get(ck, ())) // c
                       for ck, c in counts.items())

    def dealer(self, key, log: CommLog | None = None) -> "BankDealer":
        key = tuple(key)
        if key not in self._plans:
            raise KeyError(f"TripleBank has no plan registered under "
                           f"{key!r}; call provision() first")
        return BankDealer(self, key, log=log)

    # -- serving ---------------------------------------------------------
    def _pop(self, class_key: tuple, plan_key: tuple) -> tuple:
        while True:
            with self._lock:
                q = self._queues.get(class_key)
                if q:
                    out = q.pop(0)
                    self.pool_bytes -= sum(int(np.asarray(a).size) * 8
                                           for a in out)
                    self.served_requests += 1
                    self.consumed_class[class_key] = \
                        self.consumed_class.get(class_key, 0) + 1
                    return out
            # shelf empty: regenerate OUTSIDE the queue lock (generation is
            # long), then retry — a racing daemon top-up may beat us to it
            self._replenish(class_key, plan_key)

    def _replenish(self, class_key: tuple, plan_key: tuple) -> None:
        """Stock-out handling: regenerate the requesting key's whole plan
        (keeping its classes aligned for the next request) — or, for a
        class the plan never mentions, a single emergency request. Raises
        `PoolExhaustedError` only when replenishment is disabled.

        Serialized on `_gen_lock` against daemon top-ups: by the time the
        lock is held, a concurrent generation may already have restocked
        the shelf — then the wait was the whole stall (counted, no event)
        and no words are drawn."""
        if not self.auto_replenish:
            raise PoolExhaustedError(
                f"TripleBank stock-out for {class_key}: provisioned pool "
                "consumed and auto_replenish=False")
        t0 = time.perf_counter()
        with _trace.span("bank.replenish", class_key=str(class_key)), \
                self._gen_lock:
            with self._lock:
                restocked = bool(self._queues.get(class_key))
                plan = self._plans.get(tuple(plan_key))
            if restocked:
                self.replenish_seconds += time.perf_counter() - t0
                return
            if plan is not None and class_key in plan.class_counts():
                self._gen(plan.class_counts())
                self.modelled_ot_seconds += _account_offline_plan(
                    plan, self.log)
            else:
                self._gen({class_key: 1})
            self.replenish_events += 1
            self.replenish_seconds += time.perf_counter() - t0

    def consumed_counts(self) -> dict:
        """Cumulative per-class consumed-request counts (a copy) — what a
        `ServeCheckpointer` journals so a restart can `discard` its way
        back to the exact stream positions."""
        with self._lock:
            return dict(self.consumed_class)

    def discard(self, counts: dict) -> None:
        """Pop and DROP `counts[class_key]` requests per class — restart
        realignment. A reloaded bank's FIFOs sit at the provision-time
        snapshot; the requests a previous incarnation already consumed
        (journaled as cumulative per-class counts) are drained here before
        serving resumes, so no word is ever served twice across a crash.
        Exact because a class's served words are always the same stream
        prefix regardless of when (or under which plan) generation ran —
        popping past the journaled counts lands every stream exactly where
        the dead process left it."""
        for class_key in sorted(counts):
            n = int(counts[class_key])
            if n <= 0:
                continue
            with self._lock:
                plan_key = next(
                    (pk for pk, plan in self._plans.items()
                     if class_key in plan.class_counts()), class_key)
            for _ in range(n):
                self._pop(class_key, plan_key)

    # -- persistence -----------------------------------------------------
    BANK_FORMAT = "repro.triplebank"
    BANK_VERSION = 2

    def save(self, path: str) -> None:
        """One `np.savez` archive: per class, the unserved requests stacked
        per tensor slot, plus a JSON manifest carrying the class keys, RNG
        states (stream positions), registered plans, a format marker +
        version, and a CRC32 per array — so `load` can refuse a truncated,
        bit-flipped, or foreign file instead of serving garbage correlated
        randomness. The path is used VERBATIM (np.savez's silent '.npz'
        suffixing is bypassed by writing through a file handle), so
        save(p) -> load(p) always pairs up."""
        import json
        import zlib
        classes = []
        arrays = {}
        with self._gen_lock, self._lock:
            # every class with an RNG is saved, queued stock or not: stream
            # position is state even when the shelf is empty; both locks
            # make the (queues, stream positions) snapshot consistent
            # against a concurrent daemon top-up
            all_keys = set(self._rngs) | set(self._queues)
            for i, key in enumerate(sorted(all_keys)):
                q = self._queues.get(key, [])
                rng = self._rngs.get(key) or _class_rng(self.seed, key)
                n_slots = _SLOTS[key[0]]
                for s in range(n_slots):
                    if q:
                        arrays[f"c{i}_s{s}"] = np.stack(
                            [np.asarray(t[s], np.uint64) for t in q])
                classes.append({"key": _key_to_str(key), "count": len(q),
                                "rng_state": rng.bit_generator.state})
            plans = {
                _key_to_str(k): [[r.kind, list(r.shape) if r.kind != "matmul"
                                  else [list(r.shape[0]), list(r.shape[1])],
                                  r.tag] for r in plan.requests]
                for k, plan in self._plans.items()}
        checksums = {name: zlib.crc32(np.ascontiguousarray(a).tobytes())
                     for name, a in arrays.items()}
        manifest = {"format": self.BANK_FORMAT, "version": self.BANK_VERSION,
                    "seed": self.seed, "classes": classes, "plans": plans,
                    "checksums": checksums}
        with open(path, "wb") as f:
            np.savez(f, manifest=np.frombuffer(
                json.dumps(manifest).encode(), np.uint8), **arrays)

    @classmethod
    def load(cls, path: str, auto_replenish: bool = True,
             log: CommLog | None = None) -> "TripleBank":
        """Load a `save`d bank, validating format, version, and per-array
        checksums. Any structural damage — truncation, bit flips, a foreign
        npz, an unreadable manifest — raises `ValueError` naming the
        problem; a corrupt bank must never silently serve wrong words."""
        import json
        import zipfile
        import zlib

        def bad(reason: str) -> ValueError:
            return ValueError(f"not a valid TripleBank file {path!r}: "
                              f"{reason}")
        try:
            z = np.load(path)
        except (zipfile.BadZipFile, OSError, EOFError, ValueError) as e:
            raise bad(f"unreadable archive ({e})") from e
        with z:
            if "manifest" not in getattr(z, "files", ()):
                raise bad("no manifest (foreign or pre-format npz)")
            try:
                manifest = json.loads(bytes(z["manifest"]).decode())
            except (zipfile.BadZipFile, OSError, EOFError, KeyError,
                    UnicodeDecodeError, json.JSONDecodeError,
                    ValueError) as e:
                raise bad(f"manifest unreadable ({e})") from e
            if not isinstance(manifest, dict) \
                    or manifest.get("format") != cls.BANK_FORMAT:
                raise bad("manifest format marker missing or foreign")
            if manifest.get("version") != cls.BANK_VERSION:
                raise bad(f"format version {manifest.get('version')!r}, "
                          f"expected {cls.BANK_VERSION}")
            try:
                checksums = manifest["checksums"]
                expected_names = set(checksums)
                stored = set(z.files) - {"manifest"}
                if stored != expected_names:
                    raise bad("archive arrays do not match the manifest "
                              f"(missing {sorted(expected_names - stored)}, "
                              f"unexpected {sorted(stored - expected_names)})")
                loaded = {}
                for name in sorted(expected_names):
                    try:
                        a = z[name]
                    except (zipfile.BadZipFile, OSError, EOFError,
                            ValueError) as e:
                        raise bad(f"array {name!r} unreadable — truncated "
                                  f"archive? ({e})") from e
                    crc = zlib.crc32(np.ascontiguousarray(a).tobytes())
                    if crc != int(checksums[name]):
                        raise bad(f"checksum mismatch on array {name!r} "
                                  "(bit rot or tampering)")
                    loaded[name] = a
                bank = cls(seed=manifest["seed"],
                           auto_replenish=auto_replenish, log=log)
                for i, entry in enumerate(manifest["classes"]):
                    key = _key_from_str(entry["key"])
                    rng = np.random.default_rng(0)
                    rng.bit_generator.state = entry["rng_state"]
                    bank._rngs[key] = rng
                    count = int(entry["count"])
                    if count:
                        slots = [jnp.asarray(loaded[f"c{i}_s{s}"])
                                 for s in range(_SLOTS[key[0]])]
                        if any(a.shape[0] != count for a in slots):
                            raise bad(f"class {entry['key']} declares "
                                      f"{count} requests but arrays "
                                      "disagree")
                        bank._queues[key] = [tuple(a[j] for a in slots)
                                             for j in range(count)]
                        bank.pool_bytes += sum(int(a.size) * 8
                                               for a in slots)
                plans_raw = manifest["plans"]
            except ValueError:
                raise
            except (KeyError, IndexError, TypeError, SyntaxError) as e:
                raise bad(f"malformed manifest structure ({e})") from e
        for kstr, reqs in plans_raw.items():
            reqs = [PlanRequest(kind,
                                (tuple(shape[0]), tuple(shape[1]))
                                if kind == "matmul" else tuple(shape), tag)
                    for kind, shape, tag in reqs]
            bank._plans[_key_from_str(kstr)] = TriplePlan(reqs)
        return bank


class BankReplenisher:
    """Standing top-up daemon for a `TripleBank`: a background thread that
    watches per-plan stock and regenerates BEFORE the hot path runs dry,
    so steady-state replenishment leaves the online path entirely.

    Policy: whenever a registered plan key's complete-execution stock
    (`bank.stock_copies`) falls to `low_water` or below, provision enough
    copies to restore `high_water`. Generation happens on this thread
    under the bank's `_gen_lock`, so a top-up can never fork a class
    stream against a hot-path synchronous replenish — and because every
    class FIFO is only ever extended with its own stream's next words,
    the words SERVED are bit-exact with a purely synchronous bank no
    matter how daemon and hot-path generation interleave (property-
    tested). If the daemon falls behind, `TripleBank._pop` still degrades
    gracefully to the PR-4 synchronous replenish with its stall
    accounting intact.

    A generation failure is recorded (`errors`, `last_error`) and the
    daemon keeps polling — the service must keep serving off the
    synchronous path rather than die with its supervisor."""

    def __init__(self, bank: TripleBank, *, low_water: int = 1,
                 high_water: int | None = None, poll_s: float = 0.002,
                 workers: int = 1, keys=None):
        self.bank = bank
        self.low_water = max(0, int(low_water))
        self.high_water = int(high_water) if high_water is not None \
            else max(self.low_water + 1, 2 * self.low_water)
        if self.high_water <= self.low_water:
            raise ValueError(
                f"high_water ({self.high_water}) must exceed low_water "
                f"({self.low_water}) or the daemon top-up never gains stock")
        self.poll_s = float(poll_s)
        self.workers = int(workers)
        self._keys = None if keys is None else [tuple(k) for k in keys]
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self.topups = 0                 # top-up passes that generated
        self.topup_copies = 0           # plan executions generated
        self.topup_seconds = 0.0        # daemon-side generation wall
        self.errors = 0
        self.last_error: BaseException | None = None

    # -- one scan over the registered plans ------------------------------
    def poll_once(self) -> int:
        """Scan every watched key; top up those at/below the low-water
        mark. Returns the number of plan copies generated."""
        made = 0
        keys = self._keys if self._keys is not None else self.bank.keys()
        for key in keys:
            if self._stop.is_set():
                break
            with self.bank._lock:
                plan = self.bank._plans.get(tuple(key))
            if plan is None:
                continue
            have = self.bank.stock_copies(key)
            if have > self.low_water:
                continue
            need = self.high_water - have
            t0 = time.perf_counter()
            with _trace.span("bank.topup", key=str(key), copies=need,
                             stock=have):
                self.bank.provision(key, plan, copies=need,
                                    workers=self.workers)
            self.topup_seconds += time.perf_counter() - t0
            self.topups += 1
            self.topup_copies += need
            made += need
        return made

    def _loop(self) -> None:
        while not self._stop.is_set():
            try:
                self.poll_once()
            except Exception as e:        # noqa: BLE001 — daemon must live
                self.errors += 1
                self.last_error = e
            self._stop.wait(self.poll_s)

    # -- lifecycle -------------------------------------------------------
    def start(self) -> "BankReplenisher":
        if self._thread is None:
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._loop, name="bank-replenisher", daemon=True)
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def __enter__(self) -> "BankReplenisher":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()


class BankDealer(_TripleServing):
    """Dealer-interface view over a `TripleBank` for one plan key —
    interface-compatible with `TrustedDealer` (same methods and counters),
    so it drops into `SecureKMeans.predict(..., dealer=...)` and
    `materialize_offline`. `dealer_seconds` counts only replenishment
    stalls incurred while THIS view was serving (online time); provisioned
    generation stays on the bank's offline clock."""

    def __init__(self, bank: TripleBank, key: tuple,
                 log: CommLog | None = None):
        self.bank = bank
        self.key = tuple(key)
        self.log = log if log is not None else CommLog()
        self.dealer_seconds = 0.0
        self.modelled_ot_seconds = 0.0
        self.n_matmul = 0
        self.n_mul = 0
        self.n_bin = 0

    def _next(self, kind: str, shape) -> tuple:
        r0 = self.bank.replenish_seconds
        out = self.bank._pop(_class_key(kind, shape), self.key)
        self.dealer_seconds += self.bank.replenish_seconds - r0
        return out

    def skip(self, plan, reps: int = 1) -> None:
        """Drain `reps` executions of `plan` without serving them — resume
        support: realigns the bank's FIFO queues past the requests an
        earlier (checkpointed) run already consumed."""
        for _ in range(int(reps)):
            for r in plan.requests:
                self.bank._pop(_class_key(r.kind, r.shape), self.key)


class BankSlotDealer:
    """SlotDealer-compatible view over a provisioned `TripleBank` for the
    minibatch fit path: slot tranches for the pipelined executor, PINNED
    from the bank's class queues eagerly in canonical slot order at
    construction. The pipelined executor may `acquire` slots ahead of
    consumption; because the bank's FIFO positions were fixed at PROVISION
    time and this view drains every slot's words before the loop starts,
    acquisition order can never perturb a served word — the same contract
    `SlotDealer` gets from generation order, inherited from the bank.

    Bit-exact with `SlotDealer(slot_plans, seed)` when the bank was
    provisioned under the same seed with the concatenated slot plans: the
    bank's one stacked draw per class IS the SlotDealer's canonical-order
    per-class concatenation (test-enforced)."""

    def __init__(self, bank: TripleBank, key: tuple, slot_plans,
                 log: CommLog | None = None, start_slot: int = 0):
        t0 = time.perf_counter()
        self.bank = bank
        self.key = tuple(key)
        self.slot_plans = [TriplePlan(list(p.requests)) for p in slot_plans]
        self.log = log if log is not None else CommLog()
        self.start_slot = int(start_slot)
        if not 0 <= self.start_slot <= len(self.slot_plans):
            raise IndexError(f"start_slot {start_slot} out of range "
                             f"({len(self.slot_plans)} slots planned)")
        self.n_matmul = 0
        self.n_mul = 0
        self.n_bin = 0
        self.gen_seconds = 0.0
        self.wait_seconds = 0.0
        # provisioning's modelled OT cost lives on the bank's offline books
        self.modelled_ot_seconds = 0.0
        self._counts = [p.class_counts() for p in self.slot_plans]
        self._totals = [len(p) for p in self.slot_plans]
        self._served_class: dict[tuple, int] = {}
        self._acquired: set[int] = set()
        self._slots: list[tuple] = []
        self.pool_bytes = 0
        for si, plan in enumerate(self.slot_plans):
            if si < self.start_slot:
                # checkpoint resume against a FRESHLY provisioned bank:
                # the interrupted run consumed these slots' words, so drain
                # (and discard) them to keep the FIFO positions aligned —
                # slot start_slot then pops the exact entries it would have
                for r in plan.requests:
                    bank._pop(_class_key(r.kind, r.shape), self.key)
                for ck, c in self._counts[si].items():
                    self._served_class[ck] = self._served_class.get(ck, 0) + c
                self._acquired.add(si)
                self._slots.append((None, 0))
                continue
            pools: dict[tuple, list] = {}
            nbytes = 0
            for r in plan.requests:
                ck = _class_key(r.kind, r.shape)
                out = bank._pop(ck, self.key)
                pools.setdefault(ck, []).append(out)
                nbytes += sum(int(np.asarray(a).size) * 8 for a in out)
            self._slots.append((pools, nbytes))
            self.pool_bytes += nbytes
        # the pin (pops + any replenish stall) is offline-side dealer time
        self.dealer_seconds = time.perf_counter() - t0

    def acquire(self, i: int) -> _SlotView:
        if not 0 <= i < len(self.slot_plans):
            raise IndexError(f"slot {i} out of range "
                             f"({len(self.slot_plans)} slots planned)")
        with _trace.span("dealer.acquire", slot=i):
            return self._acquire(i)

    def _acquire(self, i: int) -> _SlotView:
        if i in self._acquired:
            raise PoolExhaustedError(
                f"slot {i} was already acquired: each slot serves its "
                "plan exactly once")
        self._acquired.add(i)
        pools, nbytes = self._slots[i]
        self._slots[i] = (None, nbytes)          # hand ownership to the view
        view = _SlotView(self, i, pools, self._counts[i], self._totals[i],
                         nbytes)
        if self._totals[i] == 0:                 # empty slot: nothing to serve
            self._release(i, nbytes)
        return view

    def _release(self, i: int, nbytes: int) -> None:
        for key, c in self._counts[i].items():
            self._served_class[key] = self._served_class.get(key, 0) + c

    def remaining(self) -> dict:
        total: dict[tuple, int] = {}
        for counts in self._counts:
            for key, c in counts.items():
                total[key] = total.get(key, 0) + c
        return {key: c - self._served_class.get(key, 0)
                for key, c in total.items()}

    def close(self) -> None:
        self._slots = [(None, nb) for _pools, nb in self._slots]
