"""The paper's online Lloyd iteration as pjit-able pure programs.

The offline phase (Beaver triples, B2A randomness) is materialized as
*function inputs*: a RecordingDealer first traces the protocol to enumerate
every correlated-randomness tensor the iteration consumes (their shapes are
data-independent — that's WHY the offline phase exists), then the real
lowering consumes them from the argument list via a ListDealer.

Program split (DESIGN.md §9): one online iteration is TWO compiled programs
with an optional host-side exchange between them —

  S1  distances + tournament argmin, ending at the assignment shares. The
      joint public-x-share products are Beaver matmuls inside the program
      (dense) or Protocol-2 HE results entering as share INPUTS (sparse);
      the distance-phase HE results depend only on the centroid shares, so
      the host computes them before launching S1.
  S2  (sparse only, not a program) the mid-iteration Protocol-2 exchange:
      the update-phase joint products need the assignment shares S1 just
      produced, so the host runs `core/sparse.secure_sparse_matmul` on them
      between the launches — a first-class callback, not a re-trace.
  S3  centroid update: C^T X assembly, empty-cluster guard, Newton-Raphson
      division, MUX — consuming the S2 results as inputs (sparse) or Beaver
      matmuls (dense).

Every partition x sparsity combo lowers through the same two bodies,
parameterized by a `FitGeometry`; `fit_programs` AOT-compiles and caches the
pair per (geometry, backend).

Sharding: sample-major tensors (n, ...) are sharded over ('pod','data') —
each MPC *party* owns a slice of the pod in production, and its sample rows
are data-parallel within it. Centroid-sized tensors replicate. C^T X lowers
to a psum over the sample axis: the paper's vectorized F_SCU is literally a
data-parallel reduction.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import protocol as P
from repro.core import ring
from repro.core.channel import CommLog
from repro.core.sharing import AShare, BShare
from repro.core.triples import BinTriple, MatmulTriple, MulTriple


class RecordingDealer:
    """Enumerates the offline-phase tensors (kind, shape) in consumption
    order; hands back zeros so tracing proceeds."""

    def __init__(self):
        self.requests: list[tuple[str, tuple]] = []

    def _z(self, shape):
        return jnp.zeros(shape, ring.DTYPE)

    def matmul_triple(self, sa, sb, *, tag="x"):
        self.requests.append(("matmul", (tuple(sa), tuple(sb))))
        n, d = sa
        _, k = sb
        return MatmulTriple(AShare(self._z((n, d)), self._z((n, d))),
                            AShare(self._z((d, k)), self._z((d, k))),
                            AShare(self._z((n, k)), self._z((n, k))))

    def mul_triple(self, shape, *, tag="x"):
        self.requests.append(("mul", tuple(shape)))
        z = self._z(shape)
        return MulTriple(AShare(z, z), AShare(z, z), AShare(z, z))

    def bin_triple(self, shape, *, tag="x"):
        self.requests.append(("bin", tuple(shape)))
        z = self._z(shape)
        return BinTriple(BShare(z, z), BShare(z, z), BShare(z, z))

    def rand(self, shape):
        self.requests.append(("rand", tuple(shape)))
        return self._z(shape)


class ListDealer:
    """Consumes pre-materialized offline tensors (jnp arrays) in order."""

    def __init__(self, flat: list):
        self.flat = list(flat)
        self.i = 0

    def _pop(self):
        v = self.flat[self.i]
        self.i += 1
        return v

    def matmul_triple(self, sa, sb, *, tag="x"):
        return MatmulTriple(AShare(self._pop(), self._pop()),
                            AShare(self._pop(), self._pop()),
                            AShare(self._pop(), self._pop()))

    def mul_triple(self, shape, *, tag="x"):
        return MulTriple(AShare(self._pop(), self._pop()),
                         AShare(self._pop(), self._pop()),
                         AShare(self._pop(), self._pop()))

    def bin_triple(self, shape, *, tag="x"):
        return BinTriple(BShare(self._pop(), self._pop()),
                         BShare(self._pop(), self._pop()),
                         BShare(self._pop(), self._pop()))

    def rand(self, shape):
        return self._pop()


# ---------------------------------------------------------------------------
# FitGeometry — static shape info of one partition x sparsity combo
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FitGeometry:
    """Shapes of one secure-fit combo. Vertical: X = [X_A | X_B]; horizontal:
    X = [X_A ; X_B]. Hashable — it keys the compiled-program cache."""

    partition: str     # "vertical" | "horizontal"
    sparse: bool
    shape_a: tuple     # party A's encoded-data shape
    shape_b: tuple
    k: int

    def __post_init__(self):
        if self.partition not in ("vertical", "horizontal"):
            raise ValueError(f"unknown partition {self.partition!r}")
        if self.partition == "vertical" and self.shape_a[0] != self.shape_b[0]:
            raise ValueError("vertical partition requires equal sample counts")
        if self.partition == "horizontal" and self.shape_a[1] != self.shape_b[1]:
            raise ValueError("horizontal partition requires equal feature counts")

    @property
    def n(self) -> int:
        return self.shape_a[0] if self.partition == "vertical" \
            else self.shape_a[0] + self.shape_b[0]

    @property
    def d(self) -> int:
        return self.shape_a[1] + self.shape_b[1] \
            if self.partition == "vertical" else self.shape_a[1]

    @property
    def d_a(self) -> int:
        return self.shape_a[1]

    def he_shapes_s1(self) -> list:
        """Protocol-2 result shapes entering S1 (the X mu^T joint blocks)."""
        if not self.sparse:
            return []
        if self.partition == "vertical":
            return [(self.n, self.k), (self.n, self.k)]
        return [(self.shape_a[0], self.k), (self.shape_b[0], self.k)]

    def he_shapes_s3(self) -> list:
        """Protocol-2 result shapes entering S3 (the C^T X joint blocks)."""
        if not self.sparse:
            return []
        if self.partition == "vertical":
            return [(self.k, self.shape_a[1]), (self.k, self.shape_b[1])]
        return [(self.k, self.d), (self.k, self.d)]


def _zero_he(shapes):
    if not shapes:
        return None
    return tuple(AShare(jnp.zeros(s, ring.DTYPE), jnp.zeros(s, ring.DTYPE))
                 for s in shapes)


def _split_he(flat, shapes):
    """(he tuple | None, remaining flat) from a program's trailing args."""
    flat = list(flat)
    if not shapes:
        return None, flat
    n_he = 2 * len(shapes)
    he = tuple(AShare(flat[2 * i], flat[2 * i + 1])
               for i in range(len(shapes)))
    return he, flat[n_he:]


# ---------------------------------------------------------------------------
# Program bodies — ONE implementation per online stage, all combos
# ---------------------------------------------------------------------------

def _s1_body(ctx, geo: FitGeometry, xa, xb, mu: AShare, he,
             return_min: bool = False):
    """S1: vectorized distances D' = U - 2 X mu^T + tournament argmin,
    up to the Protocol-2 boundary. Returns the (n, k) assignment shares
    (plus, with return_min, the (n,) share of the winning D' value — the
    scoring path's distance-to-assigned-centroid, free from the tournament).

    he=None  -> dense: the joint public-x-share blocks are Beaver matmuls
    consuming pool triples inside the program.
    he=(j1, j2) -> sparse: the blocks were computed host-side by Protocol 2
    from the centroid shares (they depend on nothing else) and enter as
    fresh share inputs — the nnz-independent n*d Beaver traffic and its
    triple matmuls vanish from the accelerator roofline."""
    mm = ctx.backend.ring_mm
    mu_sq = P.smul(ctx, mu, mu)
    u = AShare(mu_sq.s0.sum(1), mu_sq.s1.sum(1))
    mut = AShare(mu.s0.T, mu.s1.T)
    if geo.partition == "vertical":
        da = geo.d_a
        loc_a = mm(xa, mut.s0[:da])
        loc_b = mm(xb, mut.s1[da:])
        if he is None:
            j1 = P.smatmul(ctx, AShare(xa, jnp.zeros_like(xa)),
                           AShare(jnp.zeros_like(mut.s1[:da]), mut.s1[:da]))
            j2 = P.smatmul(ctx, AShare(jnp.zeros_like(xb), xb),
                           AShare(mut.s0[da:], jnp.zeros_like(mut.s0[da:])))
        else:
            j1, j2 = he
        xmu = AShare(loc_a + j1.s0 + j2.s0, loc_b + j1.s1 + j2.s1)
    else:
        # horizontal: rows split; each party's rows hit BOTH mu shares
        loc_a = mm(xa, mut.s0)
        loc_b = mm(xb, mut.s1)
        if he is None:
            j_a = P.smatmul(ctx, AShare(xa, jnp.zeros_like(xa)),
                            AShare(jnp.zeros_like(mut.s1), mut.s1))
            j_b = P.smatmul(ctx, AShare(jnp.zeros_like(xb), xb),
                            AShare(mut.s0, jnp.zeros_like(mut.s0)))
        else:
            j_a, j_b = he
        xmu = AShare(jnp.concatenate([loc_a + j_a.s0, j_b.s0], 0),
                     jnp.concatenate([j_a.s1, loc_b + j_b.s1], 0))
    d2 = P.sub(AShare(u.s0[None, :], u.s1[None, :]), P.lshift(xmu, 1))
    dist = P.trunc(d2, ring.F)
    if return_min:
        return P.argmin_onehot(ctx, dist, return_min=True)
    return P.argmin_onehot(ctx, dist)


def _s3_partial_body(ctx, geo: FitGeometry, xa, xb, c: AShare, he):
    """S3 head: the (k, d) numerator C^T X and (k,) denominator 1^T C sums
    of one batch — pure local/Beaver products, no division. These are the
    secret-shared running-sum accumulators of the minibatch mode: partial
    sums from several batch launches ADD (share addition is free), and one
    `_s3_final_body` launch per iteration closes the update. The full-batch
    `_s3_body` is partial + final composed, so the minibatch path at
    batch_size >= n is the same trace.

    he=None -> dense Beaver joint blocks; he=(ja, jb) -> the Protocol-2
    results of the MID-ITERATION host exchange on the assignment shares S1
    produced (the S2 callback)."""
    mm = ctx.backend.ring_mm
    k = geo.k
    ct = AShare(c.s0.T, c.s1.T)
    if geo.partition == "vertical":
        da, db = geo.shape_a[1], geo.shape_b[1]
        za = AShare(mm(ct.s0, xa), jnp.zeros((k, da), ring.DTYPE))
        zb = AShare(jnp.zeros((k, db), ring.DTYPE), mm(ct.s1, xb))
        if he is None:
            ja = P.smatmul(ctx, AShare(jnp.zeros_like(ct.s1), ct.s1),
                           AShare(xa, jnp.zeros_like(xa)))
            jb = P.smatmul(ctx, AShare(ct.s0, jnp.zeros_like(ct.s0)),
                           AShare(jnp.zeros_like(xb), xb))
        else:
            ja, jb = he
        num = AShare(jnp.concatenate([za.s0 + ja.s0, zb.s0 + jb.s0], 1),
                     jnp.concatenate([za.s1 + ja.s1, zb.s1 + jb.s1], 1))
    else:
        na = geo.shape_a[0]
        ct_a = AShare(ct.s0[:, :na], ct.s1[:, :na])
        ct_b = AShare(ct.s0[:, na:], ct.s1[:, na:])
        loc_a = mm(ct_a.s0, xa)
        if he is None:
            ja = P.smatmul(ctx, AShare(jnp.zeros_like(ct_a.s1), ct_a.s1),
                           AShare(xa, jnp.zeros_like(xa)))
        else:
            ja = he[0]
        za = AShare(loc_a + ja.s0, ja.s1)
        loc_b = mm(ct_b.s1, xb)
        if he is None:
            jb = P.smatmul(ctx, AShare(ct_b.s0, jnp.zeros_like(ct_b.s0)),
                           AShare(jnp.zeros_like(xb), xb))
        else:
            jb = he[1]
        zb = AShare(jb.s0, loc_b + jb.s1)
        num = P.add(za, zb)
    den = AShare(c.s0.sum(0), c.s1.sum(0))
    return num, den


def _s3_final_body(ctx, k: int, n: int, mu: AShare, num: AShare,
                   den: AShare):
    """S3 tail: mu' = num / den with the empty-cluster MUX guard and
    balanced-split division (see core/kmeans.py for the numerics) on the
    (possibly cross-batch accumulated) sums. `n` is the TOTAL sample count
    — it sizes the division constants, which is what keeps the minibatch
    update bit-exact with the full-batch S3 at batch_size >= n."""
    one = AShare(jnp.full((k,), 1, ring.DTYPE), jnp.zeros((k,), ring.DTYPE))
    is_empty = P.cmp_lt(ctx, den, one)
    den_safe = P.mux(ctx, is_empty, one, den)
    # balanced-split division (see core/kmeans.py for the derivation)
    m = int(np.ceil(np.log2(max(2, n))))
    s = m // 2
    num_s = P.trunc(num, s)
    r = P.reciprocal(ctx, den_safe, max_den=n, f=ring.F, extra_bits=s)
    mu_new = P.smul(ctx, num_s, AShare(r.s0[:, None], r.s1[:, None]),
                    trunc_f=ring.F)
    guard = AShare(is_empty.s0[:, None], is_empty.s1[:, None])
    return P.mux(ctx, guard, mu, mu_new)


def _s3_body(ctx, geo: FitGeometry, xa, xb, mu: AShare, c: AShare, he):
    """S3: centroid update mu' = C^T X / 1^T C — the partial-sum head and
    the finalize tail composed back to back (the full-batch form)."""
    num, den = _s3_partial_body(ctx, geo, xa, xb, c, he)
    return _s3_final_body(ctx, geo.k, geo.n, mu, num, den)


def _iteration(xa_enc, xb_enc, mu: AShare, dealer, n: int, k: int,
               d_a: int, he_results: tuple | None = None,
               backend=None, return_assignment: bool = False):
    """One vertical-partition online Lloyd iteration on shares (Alg. 3) —
    S1 and S3 bodies composed back to back over ONE dealer. Kept as the
    single-launch legacy form behind `online_iteration_fn`; the production
    fast path uses the split `fit_programs` pair."""
    d_b = xb_enc.shape[1]
    geo = FitGeometry("vertical", he_results is not None,
                      (n, d_a), (n, d_b), k)
    ctx = P.Ctx(dealer=dealer, log=CommLog(), backend=backend)
    he1 = he3 = None
    if he_results is not None:
        he1, he3 = tuple(he_results[:2]), tuple(he_results[2:])
    c = _s1_body(ctx, geo, xa_enc, xb_enc, mu, he1)
    out = _s3_body(ctx, geo, xa_enc, xb_enc, mu, c, he3)
    return (out, c) if return_assignment else out


def materialize_offline(requests, dealer) -> list:
    """Flat jnp tensor list the ListDealer consumes, in recorded order.
    `dealer` is any triple provider (TrustedDealer on demand, PooledDealer
    or StreamingPooledDealer for the planned offline phase)."""
    flat = []
    for kind, shape in requests:
        if kind == "matmul":
            t = dealer.matmul_triple(*shape)
        elif kind == "mul":
            t = dealer.mul_triple(shape)
        elif kind == "bin":
            t = dealer.bin_triple(shape)
            flat += [t.u.b0, t.u.b1, t.v.b0, t.v.b1, t.z.b0, t.z.b1]
            continue
        else:  # rand
            flat.append(dealer.rand(shape))
            continue
        flat += [t.u.s0, t.u.s1, t.v.s0, t.v.s1, t.z.s0, t.z.s1]
    return flat


def pooled_offline_arrays(requests, seed: int, iters: int = 1,
                          tag: str = "launch"):
    """True offline phase for the pjit path: bulk-generate `iters`
    iterations' worth of the recorded schedule with ONE stacked draw and one
    batched ring op per shape-class, and return ([flat_per_iteration...],
    dealer). Each flat list feeds one jit'd iteration via its ListDealer;
    the arrays are preallocated device slices, so consuming them adds no
    host work to the online step. Bit-exact with `materialize_offline`
    against a same-seeded TrustedDealer (tests/test_triples_pool.py)."""
    from repro.core.triples import PlanRequest, PooledDealer, TriplePlan
    plan = TriplePlan([PlanRequest(kind, tuple(shape) if kind != "matmul"
                                   else shape, tag)
                       for kind, shape in requests]).repeat(iters)
    dealer = PooledDealer(plan, seed=seed)
    return [materialize_offline(requests, dealer) for _ in range(iters)], dealer


def record_offline_shapes(n: int, d: int, k: int, d_a: int,
                          sparse: bool = False):
    """Trace the iteration once to enumerate the offline tensor list.
    sparse=True enumerates the Protocol-2 variant (the four joint-product
    Beaver matmul triples are replaced by HE-result share inputs)."""
    dealer = RecordingDealer()

    def run():
        z = jnp.zeros((n, d_a), ring.DTYPE)
        zb = jnp.zeros((n, d - d_a), ring.DTYPE)
        mu = AShare(jnp.zeros((k, d), ring.DTYPE),
                    jnp.zeros((k, d), ring.DTYPE))
        he = None
        if sparse:
            he = tuple(AShare(jnp.zeros(s, ring.DTYPE),
                              jnp.zeros(s, ring.DTYPE))
                       for s in [(n, k), (n, k), (k, d_a), (k, d - d_a)])
        return _iteration(z, zb, mu, dealer, n, k, d_a, he_results=he)

    jax.eval_shape(run)
    return dealer.requests


def offline_tensor_specs(requests, n: int):
    """Flat list of ShapeDtypeStructs mirroring ListDealer consumption."""
    flat = []
    for kind, shape in requests:
        if kind == "matmul":
            (nn, d), (d2, k) = shape
            flat += [jax.ShapeDtypeStruct(s, ring.NP_DTYPE)
                     for s in [(nn, d), (nn, d), (d, k), (d, k),
                               (nn, k), (nn, k)]]
        elif kind in ("mul", "bin"):
            flat += [jax.ShapeDtypeStruct(shape, ring.NP_DTYPE)] * 6
        else:  # rand
            flat.append(jax.ShapeDtypeStruct(shape, ring.NP_DTYPE))
    return flat


# ---------------------------------------------------------------------------
# fit_programs — the per-iteration S1/S3 compiled pair, ALL fit shapes
# ---------------------------------------------------------------------------

class FitPrograms(NamedTuple):
    """AOT-compiled S1/S3 pair plus the offline schedule each launch
    consumes. Per online iteration:

        he1 = host Protocol-2 on the centroid shares        (sparse only)
        c   = s1(xa, xb, mu0, mu1, *he1, *flat_s1)          launch 1
        he3 = host Protocol-2 on the assignment shares      (sparse only,
                                                             the S2 callback)
        mu' = s3(xa, xb, mu0, mu1, c0, c1, *he3, *flat_s3)  launch 2

    where flat_s1/flat_s3 = materialize_offline(s{1,3}_requests, pool)."""

    geo: FitGeometry
    s1: Any
    s3: Any
    s1_requests: list
    s3_requests: list


_PROGRAM_CACHE: dict[tuple, FitPrograms] = {}


def _sds(shape):
    return jax.ShapeDtypeStruct(tuple(shape), ring.NP_DTYPE)


def _he_specs(shapes):
    out = []
    for s in shapes:
        out += [_sds(s), _sds(s)]
    return out


def fit_programs(partition: str, sparse: bool, shape_a, shape_b, k: int,
                 backend: str = "auto") -> FitPrograms:
    """Build (or fetch from the cross-fit cache) the compiled S1/S3 pair for
    one fit combo. Hardcodes f = ring.F like the rest of the launch path;
    the request schedules consume the same per-class dealer streams as the
    eager loop, so pooled serving is bit-exact by construction."""
    from repro.core.backend import get_backend
    ring_backend = get_backend(backend)
    geo = FitGeometry(partition, bool(sparse),
                      tuple(int(s) for s in shape_a),
                      tuple(int(s) for s in shape_b), int(k))
    key = (geo, ring_backend.name)
    hit = _PROGRAM_CACHE.get(key)
    if hit is not None:
        return hit

    n, d = geo.n, geo.d
    base = (_sds(geo.shape_a), _sds(geo.shape_b), _sds((k, d)), _sds((k, d)))

    def zero_inputs():
        xa = jnp.zeros(geo.shape_a, ring.DTYPE)
        xb = jnp.zeros(geo.shape_b, ring.DTYPE)
        mu = AShare(jnp.zeros((k, d), ring.DTYPE),
                    jnp.zeros((k, d), ring.DTYPE))
        return xa, xb, mu

    # ---- S1: distances + argmin -> assignment shares ---------------------
    rec1 = RecordingDealer()

    def trace1():
        xa, xb, mu = zero_inputs()
        ctx = P.Ctx(dealer=rec1, log=CommLog(), backend=ring_backend)
        return _s1_body(ctx, geo, xa, xb, mu, _zero_he(geo.he_shapes_s1()))

    jax.eval_shape(trace1)
    s1_requests = list(rec1.requests)

    def s1_fn(xa, xb, mu0, mu1, *rest):
        he, flat = _split_he(rest, geo.he_shapes_s1())
        ctx = P.Ctx(dealer=ListDealer(flat), log=CommLog(),
                    backend=ring_backend)
        c = _s1_body(ctx, geo, xa, xb, AShare(mu0, mu1), he)
        return c.s0, c.s1

    s1_args = base + tuple(_he_specs(geo.he_shapes_s1())) \
        + tuple(offline_tensor_specs(s1_requests, n))
    s1 = jax.jit(s1_fn).lower(*s1_args).compile()

    # ---- S3: centroid update --------------------------------------------
    rec3 = RecordingDealer()

    def trace3():
        xa, xb, mu = zero_inputs()
        c = AShare(jnp.zeros((n, k), ring.DTYPE),
                   jnp.zeros((n, k), ring.DTYPE))
        ctx = P.Ctx(dealer=rec3, log=CommLog(), backend=ring_backend)
        return _s3_body(ctx, geo, xa, xb, mu, c, _zero_he(geo.he_shapes_s3()))

    jax.eval_shape(trace3)
    s3_requests = list(rec3.requests)

    def s3_fn(xa, xb, mu0, mu1, c0, c1, *rest):
        he, flat = _split_he(rest, geo.he_shapes_s3())
        ctx = P.Ctx(dealer=ListDealer(flat), log=CommLog(),
                    backend=ring_backend)
        out = _s3_body(ctx, geo, xa, xb, AShare(mu0, mu1), AShare(c0, c1), he)
        return out.s0, out.s1

    s3_args = base + (_sds((n, k)), _sds((n, k))) \
        + tuple(_he_specs(geo.he_shapes_s3())) \
        + tuple(offline_tensor_specs(s3_requests, n))
    s3 = jax.jit(s3_fn).lower(*s3_args).compile()

    progs = FitPrograms(geo, s1, s3, s1_requests, s3_requests)
    _PROGRAM_CACHE[key] = progs
    return progs


# ---------------------------------------------------------------------------
# Minibatch programs — S1 + S3-partial per batch geometry, one finalize
# ---------------------------------------------------------------------------

class BatchPrograms(NamedTuple):
    """Compiled (S1, S3-partial) pair for ONE minibatch geometry plus the
    offline schedule each launch consumes. Per batch t of an iteration:

        he1 = host Protocol-2 on the centroid shares            (sparse)
        c   = s1(xa_t, xb_t, mu0, mu1, *he1, *flat1)            launch 1
        he3 = host Protocol-2 on the assignment shares          (sparse,
                                                                 S2 callback)
        n0, n1, d0, d1 = s3p(xa_t, xb_t, c0, c1, *he3, *flat3)  launch 2

    The (k, d) numerator and (k,) denominator partials accumulate across
    batches by share addition; the iteration closes with one
    `finalize_program` launch. One cached pair serves every batch of its
    geometry — a fit needs at most a handful of entries (full batch shape
    + remainder)."""

    geo: FitGeometry
    s1: Any
    s3p: Any
    s1_requests: list
    s3p_requests: list


_BATCH_PROGRAM_CACHE: dict[tuple, BatchPrograms] = {}


def fit_batch_programs(partition: str, sparse: bool, shape_a, shape_b,
                       k: int, backend: str = "auto") -> BatchPrograms:
    """Build (or fetch from the cross-fit cache) the compiled S1/S3-partial
    pair for one BATCH geometry. The S1 body is the same one `fit_programs`
    compiles — a batch is just a fit geometry with the batch rows in place
    of the training rows; S3-partial stops at the running sums."""
    from repro.core.backend import get_backend
    ring_backend = get_backend(backend)
    geo = FitGeometry(partition, bool(sparse),
                      tuple(int(s) for s in shape_a),
                      tuple(int(s) for s in shape_b), int(k))
    key = (geo, ring_backend.name)
    hit = _BATCH_PROGRAM_CACHE.get(key)
    if hit is not None:
        return hit

    n, d = geo.n, geo.d
    base = (_sds(geo.shape_a), _sds(geo.shape_b), _sds((k, d)), _sds((k, d)))

    rec1 = RecordingDealer()

    def trace1():
        xa = jnp.zeros(geo.shape_a, ring.DTYPE)
        xb = jnp.zeros(geo.shape_b, ring.DTYPE)
        mu = AShare(jnp.zeros((k, d), ring.DTYPE),
                    jnp.zeros((k, d), ring.DTYPE))
        ctx = P.Ctx(dealer=rec1, log=CommLog(), backend=ring_backend)
        return _s1_body(ctx, geo, xa, xb, mu, _zero_he(geo.he_shapes_s1()))

    jax.eval_shape(trace1)
    s1_requests = list(rec1.requests)

    def s1_fn(xa, xb, mu0, mu1, *rest):
        he, flat = _split_he(rest, geo.he_shapes_s1())
        ctx = P.Ctx(dealer=ListDealer(flat), log=CommLog(),
                    backend=ring_backend)
        c = _s1_body(ctx, geo, xa, xb, AShare(mu0, mu1), he)
        return c.s0, c.s1

    s1_args = base + tuple(_he_specs(geo.he_shapes_s1())) \
        + tuple(offline_tensor_specs(s1_requests, n))
    s1 = jax.jit(s1_fn).lower(*s1_args).compile()

    rec3 = RecordingDealer()

    def trace3():
        xa = jnp.zeros(geo.shape_a, ring.DTYPE)
        xb = jnp.zeros(geo.shape_b, ring.DTYPE)
        c = AShare(jnp.zeros((n, k), ring.DTYPE),
                   jnp.zeros((n, k), ring.DTYPE))
        ctx = P.Ctx(dealer=rec3, log=CommLog(), backend=ring_backend)
        return _s3_partial_body(ctx, geo, xa, xb, c,
                                _zero_he(geo.he_shapes_s3()))

    jax.eval_shape(trace3)
    s3p_requests = list(rec3.requests)

    def s3p_fn(xa, xb, c0, c1, *rest):
        he, flat = _split_he(rest, geo.he_shapes_s3())
        ctx = P.Ctx(dealer=ListDealer(flat), log=CommLog(),
                    backend=ring_backend)
        num, den = _s3_partial_body(ctx, geo, xa, xb, AShare(c0, c1), he)
        return num.s0, num.s1, den.s0, den.s1

    s3p_args = (_sds(geo.shape_a), _sds(geo.shape_b),
                _sds((n, k)), _sds((n, k))) \
        + tuple(_he_specs(geo.he_shapes_s3())) \
        + tuple(offline_tensor_specs(s3p_requests, n))
    s3p = jax.jit(s3p_fn).lower(*s3p_args).compile()

    progs = BatchPrograms(geo, s1, s3p, s1_requests, s3p_requests)
    _BATCH_PROGRAM_CACHE[key] = progs
    return progs


class FinalizeProgram(NamedTuple):
    """Compiled per-iteration S3 tail: one launch on the accumulated sums.

        mu'0, mu'1 = fn(mu0, mu1, num0, num1, den0, den1, *flat)

    where flat = materialize_offline(requests, dealer)."""

    fn: Any
    requests: list


_FINALIZE_CACHE: dict[tuple, FinalizeProgram] = {}


def finalize_program(k: int, d: int, n: int,
                     backend: str = "auto") -> FinalizeProgram:
    """Build (or fetch) the compiled minibatch finalize launch. `n` is the
    TOTAL sample count (division constants), so every batch layout of one
    fit shares a single entry — and the batch_size >= n fit runs the exact
    algebra of the full-batch S3 program's tail."""
    from repro.core.backend import get_backend
    ring_backend = get_backend(backend)
    key = (int(k), int(d), int(n), ring_backend.name)
    hit = _FINALIZE_CACHE.get(key)
    if hit is not None:
        return hit

    rec = RecordingDealer()

    def trace():
        z = lambda s: jnp.zeros(s, ring.DTYPE)  # noqa: E731
        ctx = P.Ctx(dealer=rec, log=CommLog(), backend=ring_backend)
        return _s3_final_body(ctx, k, n, AShare(z((k, d)), z((k, d))),
                              AShare(z((k, d)), z((k, d))),
                              AShare(z((k,)), z((k,))))

    jax.eval_shape(trace)
    requests = list(rec.requests)

    def finalize_fn(mu0, mu1, num0, num1, den0, den1, *flat):
        ctx = P.Ctx(dealer=ListDealer(list(flat)), log=CommLog(),
                    backend=ring_backend)
        out = _s3_final_body(ctx, k, n, AShare(mu0, mu1),
                             AShare(num0, num1), AShare(den0, den1))
        return out.s0, out.s1

    args = (_sds((k, d)), _sds((k, d)), _sds((k, d)), _sds((k, d)),
            _sds((k,)), _sds((k,))) \
        + tuple(offline_tensor_specs(requests, n))
    prog = FinalizeProgram(jax.jit(finalize_fn).lower(*args).compile(),
                           requests)
    _FINALIZE_CACHE[key] = prog
    return prog


# ---------------------------------------------------------------------------
# predict_program — the S1 body alone, serving new batches against a model
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PredictGeometry:
    """Shapes of one secure-scoring batch against a fitted (k, d) model.
    Vertical: both parties hold the same batch rows' column slices,
    shape_a = (m, d_a), shape_b = (m, d_b). Horizontal: each party owns
    whole arrival rows, shape_a = (m_a, d), shape_b = (m_b, d); outputs are
    ordered [A rows; B rows]. Hashable — it keys the compiled-program
    cache and (through the predict-plan key) the TripleBank lookup."""

    partition: str
    sparse: bool
    shape_a: tuple
    shape_b: tuple
    k: int
    with_scores: bool = False

    def fit_geometry(self) -> FitGeometry:
        """The S1 body is geometry-parameterized by FitGeometry; a predict
        batch is the same geometry with the batch rows in place of the
        training rows (validation included)."""
        return FitGeometry(self.partition, self.sparse,
                           self.shape_a, self.shape_b, self.k)


class PredictProgram(NamedTuple):
    """AOT-compiled batched scoring launch plus the offline schedule one
    call consumes. Per request:

        he1  = host Protocol-2 on the centroid shares          (sparse only)
        outs = fn(xa, xb, mu0, mu1, *he1, *flat)               ONE launch
        (c0, c1) = outs[:2]; (v0, v1) = outs[2:]               (with_scores)

    where flat = materialize_offline(requests, dealer). The min-distance
    shares v are D'(x, mu_c) = ||mu_c||^2 - 2 x.mu_c at scale f; the caller
    adds the locally-computable ||x||^2 share to get the true squared
    distance (core/kmeans.SecureKMeans.score)."""

    geo: PredictGeometry
    fn: Any
    requests: list


_PREDICT_PROGRAM_CACHE: dict[tuple, PredictProgram] = {}


def predict_program(partition: str, sparse: bool, shape_a, shape_b, k: int,
                    with_scores: bool = False,
                    backend: str = "auto") -> PredictProgram:
    """Build (or fetch from the cross-request cache) the compiled scoring
    launch for one batch geometry — the S1 body of `fit_programs` extracted
    and parameterized by `PredictGeometry`. Dense combos consume pool
    triples inside the program; sparse combos take the Protocol-2 joint
    products (computable from the centroid shares alone, so the host runs
    the exchange BEFORE the launch) as share inputs. Hardcodes f = ring.F
    like the rest of the launch path."""
    from repro.core.backend import get_backend
    ring_backend = get_backend(backend)
    geo = PredictGeometry(partition, bool(sparse),
                          tuple(int(s) for s in shape_a),
                          tuple(int(s) for s in shape_b), int(k),
                          bool(with_scores))
    key = (geo, ring_backend.name)
    hit = _PREDICT_PROGRAM_CACHE.get(key)
    if hit is not None:
        return hit

    fgeo = geo.fit_geometry()
    n, d = fgeo.n, fgeo.d
    rec1 = RecordingDealer()

    def trace():
        xa = jnp.zeros(geo.shape_a, ring.DTYPE)
        xb = jnp.zeros(geo.shape_b, ring.DTYPE)
        mu = AShare(jnp.zeros((k, d), ring.DTYPE),
                    jnp.zeros((k, d), ring.DTYPE))
        ctx = P.Ctx(dealer=rec1, log=CommLog(), backend=ring_backend)
        return _s1_body(ctx, fgeo, xa, xb, mu, _zero_he(fgeo.he_shapes_s1()),
                        return_min=with_scores)

    jax.eval_shape(trace)
    requests = list(rec1.requests)

    def predict_fn(xa, xb, mu0, mu1, *rest):
        he, flat = _split_he(rest, fgeo.he_shapes_s1())
        ctx = P.Ctx(dealer=ListDealer(flat), log=CommLog(),
                    backend=ring_backend)
        out = _s1_body(ctx, fgeo, xa, xb, AShare(mu0, mu1), he,
                       return_min=with_scores)
        if with_scores:
            c, v = out
            return c.s0, c.s1, v.s0, v.s1
        return out.s0, out.s1

    args = (_sds(geo.shape_a), _sds(geo.shape_b),
            _sds((k, d)), _sds((k, d))) \
        + tuple(_he_specs(fgeo.he_shapes_s1())) \
        + tuple(offline_tensor_specs(requests, n))
    prog = PredictProgram(geo, jax.jit(predict_fn).lower(*args).compile(),
                          requests)
    _PREDICT_PROGRAM_CACHE[key] = prog
    return prog


def clear_program_cache() -> None:
    _PROGRAM_CACHE.clear()
    _PREDICT_PROGRAM_CACHE.clear()
    _BATCH_PROGRAM_CACHE.clear()
    _FINALIZE_CACHE.clear()


def online_iteration_fn(n: int, d: int, k: int, d_a: int,
                        sparse: bool = False, backend: str = "auto"):
    """(fn, arg ShapeDtypeStructs) with fn(xa, xb, mu0, mu1, *he, *flat).
    sparse=True adds the 8 Protocol-2 result shares as inputs and drops the
    joint Beaver matmuls (paper Sec 4.3 on-mesh). `backend` picks the
    ring-compute implementation (core/backend.py) baked into the lowering.

    Legacy single-launch form (S1+S3 fused, no mid-iteration callback) kept
    for the mesh/perf harnesses; `fit_programs` is the production split."""
    from repro.core.backend import get_backend
    ring_backend = get_backend(backend)
    n_he = 0
    he_shapes = []
    if sparse:
        he_shapes = [(n, k), (n, k), (k, d_a), (k, d - d_a)]
        n_he = 8  # 4 AShares = 8 tensors

    def _he_args(flat):
        if not sparse:
            return None, flat
        he = [AShare(flat[2 * i], flat[2 * i + 1]) for i in range(4)]
        return tuple(he), flat[n_he:]

    dealer = RecordingDealer()

    def run():
        z = jnp.zeros((n, d_a), ring.DTYPE)
        zb = jnp.zeros((n, d - d_a), ring.DTYPE)
        mu = AShare(jnp.zeros((k, d), ring.DTYPE),
                    jnp.zeros((k, d), ring.DTYPE))
        he = tuple(AShare(jnp.zeros(s, ring.DTYPE), jnp.zeros(s, ring.DTYPE))
                   for s in he_shapes) if sparse else None
        return _iteration(z, zb, mu, dealer, n, k, d_a, he_results=he,
                          backend=ring_backend)

    jax.eval_shape(run)
    flat_specs = offline_tensor_specs(dealer.requests, n)

    def fn(xa_enc, xb_enc, mu_s0, mu_s1, *flat):
        he, rest = _he_args(list(flat))
        out = _iteration(xa_enc, xb_enc, AShare(mu_s0, mu_s1),
                         ListDealer(rest), n, k, d_a, he_results=he,
                         backend=ring_backend)
        return out.s0, out.s1

    he_specs = []
    for s in he_shapes:
        he_specs += [jax.ShapeDtypeStruct(s, ring.NP_DTYPE)] * 2
    args = (jax.ShapeDtypeStruct((n, d_a), ring.NP_DTYPE),
            jax.ShapeDtypeStruct((n, d - d_a), ring.NP_DTYPE),
            jax.ShapeDtypeStruct((k, d), ring.NP_DTYPE),
            jax.ShapeDtypeStruct((k, d), ring.NP_DTYPE)) \
        + tuple(he_specs) + tuple(flat_specs)
    return fn, args


def fit_iteration_fn(n: int, d: int, k: int, d_a: int,
                     backend: str = "auto"):
    """`online_iteration_fn` variant that also exposes the assignment
    shares: fn(xa, xb, mu0, mu1, *flat) -> (mu0', mu1', c0, c1), plus the
    offline schedule one call consumes. Superseded by `fit_programs` (the
    S1/S3 split) on SecureKMeans' pooled fast path; kept for callers that
    want the fused single-launch dense-vertical iteration."""
    from repro.core.backend import get_backend
    ring_backend = get_backend(backend)
    dealer = RecordingDealer()

    def run():
        z = jnp.zeros((n, d_a), ring.DTYPE)
        zb = jnp.zeros((n, d - d_a), ring.DTYPE)
        mu = AShare(jnp.zeros((k, d), ring.DTYPE),
                    jnp.zeros((k, d), ring.DTYPE))
        return _iteration(z, zb, mu, dealer, n, k, d_a,
                          backend=ring_backend, return_assignment=True)

    jax.eval_shape(run)
    requests = list(dealer.requests)
    flat_specs = offline_tensor_specs(requests, n)

    def fn(xa_enc, xb_enc, mu_s0, mu_s1, *flat):
        mu, c = _iteration(xa_enc, xb_enc, AShare(mu_s0, mu_s1),
                           ListDealer(list(flat)), n, k, d_a,
                           backend=ring_backend, return_assignment=True)
        return mu.s0, mu.s1, c.s0, c.s1

    args = (jax.ShapeDtypeStruct((n, d_a), ring.NP_DTYPE),
            jax.ShapeDtypeStruct((n, d - d_a), ring.NP_DTYPE),
            jax.ShapeDtypeStruct((k, d), ring.NP_DTYPE),
            jax.ShapeDtypeStruct((k, d), ring.NP_DTYPE)) + tuple(flat_specs)
    return fn, args, requests


def arg_shardings(mesh, args, n: int):
    """Shard the sample axis over ('pod','data') WHEREVER it appears —
    including dim-1 of the transposed (k, n) Beaver triples. (§Perf
    iteration 1: leaving those replicated made GSPMD reconstruct E
    replicated and ALL-GATHER the 4 GB F operands of C^T X instead of
    partial-summing — 8.6 GB/device/step of pure waste.)"""
    from jax.sharding import NamedSharding, PartitionSpec as Pspec
    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    out = []
    for a in args:
        spec = [None] * len(a.shape)
        for dim, sz in enumerate(a.shape):
            if sz == n:
                spec[dim] = axes
                break
        out.append(NamedSharding(mesh, Pspec(*spec)))
    return tuple(out)
