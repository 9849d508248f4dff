"""Offline planner/pool tests: the PooledDealer must be a bit-exact,
zero-host-work replacement for the on-demand TrustedDealer.

The load-bearing property: bulk per-class generation (one stacked RNG draw
+ one batched ring op per shape-class) serves the SAME uint64 words as the
on-demand dealer under the same seed — at the single-triple level, at the
pjit flat-tensor level, and through a full SecureKMeans.fit for all four
partition x sparsity combinations. Each class reaches the device in ONE put
of its per-request arrays, never one device op a request."""
import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import triples
from repro.core.kmeans import KMeansConfig, SecureKMeans
from repro.core.triples import (PlanningDealer, PlanRequest, PooledDealer,
                                PoolExhaustedError, StreamingPooledDealer,
                                TriplePlan, TrustedDealer)
from repro.launch.kmeans_step import (materialize_offline,
                                      pooled_offline_arrays,
                                      record_offline_shapes)
from repro.obs import trace as _trace

RNG = np.random.default_rng(77)


def _consume(dealer, requests):
    """Serve a request schedule, returning every share word as numpy."""
    out = []
    for r in requests:
        if r.kind == "matmul":
            t = dealer.matmul_triple(*r.shape, tag=r.tag)
            out += [t.u.s0, t.u.s1, t.v.s0, t.v.s1, t.z.s0, t.z.s1]
        elif r.kind == "mul":
            t = dealer.mul_triple(r.shape, tag=r.tag)
            out += [t.u.s0, t.u.s1, t.v.s0, t.v.s1, t.z.s0, t.z.s1]
        elif r.kind == "bin":
            t = dealer.bin_triple(r.shape, tag=r.tag)
            out += [t.u.b0, t.u.b1, t.v.b0, t.v.b1, t.z.b0, t.z.b1]
        elif r.kind == "rand":
            out.append(dealer.rand(r.shape))
        else:
            out.append(np.uint64(dealer.mask_seed()))
    return [np.asarray(a, np.uint64) for a in out]


@given(st.lists(st.sampled_from(["matmul", "mul", "bin", "rand", "seed"]),
                min_size=1, max_size=24),
       st.integers(0, 2**31 - 1))
@settings(deadline=None, max_examples=20)
def test_pooled_replays_trusted_dealer_bit_exact(kinds, seed):
    """Random interleaved schedules over a few shape-classes: every served
    word identical between on-demand and bulk generation."""
    shapes = {"matmul": ((5, 3), (3, 2)), "mul": (4, 3), "bin": (2, 7),
              "rand": (6,), "seed": ()}
    requests = [PlanRequest(k, shapes[k], "t") for k in kinds]
    plan = TriplePlan(requests)
    a = _consume(TrustedDealer(seed=seed), requests)
    b = _consume(PooledDealer(plan, seed=seed), requests)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


def test_pooled_mixed_shapes_same_kind():
    """Two shape-classes of the same kind keep separate streams/cursors."""
    requests = [PlanRequest("mul", (3, 3), "a"), PlanRequest("mul", (5,), "b"),
                PlanRequest("mul", (3, 3), "a"), PlanRequest("mul", (3, 3), "c")]
    plan = TriplePlan(requests)
    a = _consume(TrustedDealer(seed=9), requests)
    b = _consume(PooledDealer(plan, seed=9), requests)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


# ---------------------------------------------------------------------------
# fit-level property (satellite): all four partition x sparsity combos
# ---------------------------------------------------------------------------

def _blobs(n, d, k, seed, sparse_frac=0.0):
    rng = np.random.default_rng(seed)
    centers = rng.uniform(-4, 4, (k, d))
    lab = rng.integers(0, k, n)
    x = centers[lab] + rng.normal(0, 0.3, (n, d))
    if sparse_frac:
        x = x * (rng.random((n, d)) >= sparse_frac)
    return x


@pytest.mark.parametrize("partition", ["vertical", "horizontal"])
@pytest.mark.parametrize("sparse", [False, True])
def test_fit_pooled_bit_exact_vs_on_demand(partition, sparse):
    """Same seed -> identical share words, dealer counts, and offline
    CommLog tallies, whether triples are synthesized on demand inside the
    loop, planned + bulk-generated + pooled up front, or streamed per-
    iteration tranche. ALL four partition x sparsity combos take the
    compiled S1/S3 split-launch fast path in pooled/streamed mode (the
    sparse ones with Protocol 2 as a host callback between the launches),
    so this is the end-to-end parity guarantee of the split."""
    n, d, k = 48, 4, 2
    x = _blobs(n, d, k, seed=11, sparse_frac=0.5 if sparse else 0.0)
    if partition == "vertical":
        a, b = x[:, :2], x[:, 2:]
    else:
        a, b = x[:24], x[24:]
    res = {}
    for off in ("on_demand", "pooled", "streamed"):
        cfg = KMeansConfig(k=k, iters=2, partition=partition, sparse=sparse,
                           seed=5, backend="xla", offline=off)
        res[off] = SecureKMeans(cfg).fit(a, b)
    r0 = res["on_demand"]
    for r1 in (res["pooled"], res["streamed"]):
        for field in ("centroids", "assignment"):
            np.testing.assert_array_equal(
                np.asarray(getattr(r0, field).s0, np.uint64),
                np.asarray(getattr(r1, field).s0, np.uint64))
            np.testing.assert_array_equal(
                np.asarray(getattr(r0, field).s1, np.uint64),
                np.asarray(getattr(r1, field).s1, np.uint64))
        assert (r0.dealer.n_matmul, r0.dealer.n_mul, r0.dealer.n_bin) == \
               (r1.dealer.n_matmul, r1.dealer.n_mul, r1.dealer.n_bin)
        assert r0.log.by_tag("offline") == r1.log.by_tag("offline")
        assert r0.log.by_tag("online") == r1.log.by_tag("online")
    # the streaming dealer consumed every planned tranche exactly
    assert res["streamed"].dealer.served_iters == 2
    assert all(v == 0 for v in res["streamed"].dealer.remaining().values())


def test_fit_pooled_nondefault_f_falls_back_bit_exact():
    """The compiled fast path hardcodes f = ring.F; a custom precision must
    take the eager pooled loop and still replay bit-exact."""
    x = _blobs(40, 4, 2, seed=3)
    res = {}
    for off in ("on_demand", "pooled"):
        cfg = KMeansConfig(k=2, iters=2, seed=5, f=16, backend="xla",
                           offline=off)
        res[off] = SecureKMeans(cfg).fit(x[:, :2], x[:, 2:])
    np.testing.assert_array_equal(
        np.asarray(res["on_demand"].centroids.s0, np.uint64),
        np.asarray(res["pooled"].centroids.s0, np.uint64))
    np.testing.assert_allclose(res["pooled"].centroids_plain(f=16),
                               res["on_demand"].centroids_plain(f=16))


def test_fit_pooled_with_tol_leaves_surplus():
    """A tol early-stop only leaves pool surplus — never an error."""
    x = _blobs(200, 4, 3, seed=4)
    cfg = KMeansConfig(k=3, iters=50, seed=5, tol=1e-6, backend="xla",
                       offline="pooled")
    res = SecureKMeans(cfg).fit(x[:, :2], x[:, 2:])
    assert res.iters_run < 50
    assert all(v >= 0 for v in res.dealer.remaining().values())
    assert any(v > 0 for v in res.dealer.remaining().values())


# ---------------------------------------------------------------------------
# pool exhaustion / shape-mismatch semantics
# ---------------------------------------------------------------------------

def test_pool_exhaustion_raises():
    plan = TriplePlan([PlanRequest("mul", (2, 2), "t")])
    dealer = PooledDealer(plan, seed=1)
    dealer.mul_triple((2, 2))
    with pytest.raises(PoolExhaustedError, match="exhausted"):
        dealer.mul_triple((2, 2))


def test_pool_unplanned_class_raises():
    plan = TriplePlan([PlanRequest("mul", (2, 2), "t")])
    dealer = PooledDealer(plan, seed=1)
    with pytest.raises(PoolExhaustedError, match="never"):
        dealer.mul_triple((3, 3))
    with pytest.raises(PoolExhaustedError):
        dealer.bin_triple((2, 2))


def test_matmul_triple_shape_mismatch_raises_value_error():
    """Planner bugs must surface under `python -O` too (no bare asserts)."""
    for dealer in (TrustedDealer(seed=0), PlanningDealer(),
                   PooledDealer(TriplePlan([]), seed=0),
                   StreamingPooledDealer(TriplePlan([]), 1, seed=0)):
        with pytest.raises(ValueError, match=r"inner dims disagree.*\(2, 4\)"):
            dealer.matmul_triple((2, 4), (3, 5))


def test_mul_bin_triple_bad_shape_raises_value_error():
    """mul/bin triples take ONE flat tensor shape; a matmul-style nested
    pair, floats, or negative dims are planner bugs -> ValueError (matching
    the matmul inner-dim check)."""
    dealers = (TrustedDealer(seed=0), PlanningDealer(),
               PooledDealer(TriplePlan([]), seed=0),
               StreamingPooledDealer(TriplePlan([]), 1, seed=0))
    for dealer in dealers:
        with pytest.raises(ValueError, match="flat tuple of ints"):
            dealer.mul_triple(((2, 3), (3, 4)))     # nested matmul-style
        with pytest.raises(ValueError, match="flat tuple of ints"):
            dealer.bin_triple((2, 3.5))
        with pytest.raises(ValueError, match="negative"):
            dealer.mul_triple((2, -3))
        with pytest.raises(ValueError, match="iterable"):
            dealer.bin_triple(7)


# ---------------------------------------------------------------------------
# pjit path consumes the pool
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# StreamingPooledDealer — per-iteration tranches, O(1) residency
# ---------------------------------------------------------------------------

_SHAPES = {"matmul": ((5, 3), (3, 2)), "mul": (4, 3), "bin": (2, 7),
           "rand": (6,), "seed": ()}


@given(st.lists(st.sampled_from(["matmul", "mul", "bin", "rand", "seed"]),
                min_size=1, max_size=12),
       st.integers(1, 5), st.integers(0, 2**31 - 1))
@settings(deadline=None, max_examples=15)
def test_streaming_replays_pooled_bit_exact(kinds, iters, seed):
    """StreamingPooledDealer ≡ PooledDealer(iter_plan.repeat(iters)): every
    served word identical, for random per-iteration schedules — the chunked
    per-class draws concatenate to the single stacked draw."""
    requests = [PlanRequest(k, _SHAPES[k], "t") for k in kinds]
    iter_plan = TriplePlan(requests)
    full = requests * iters
    a = _consume(PooledDealer(iter_plan.repeat(iters), seed=seed), full)
    stream = StreamingPooledDealer(iter_plan, iters, seed=seed)
    b = _consume(stream, full)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert stream.served_iters == iters
    assert all(v == 0 for v in stream.remaining().values())


@given(st.lists(st.sampled_from(["matmul", "mul", "bin", "rand", "seed"]),
                min_size=1, max_size=8),
       st.integers(1, 6), st.integers(1, 7), st.integers(0, 2**31 - 1))
@settings(deadline=None, max_examples=15)
def test_streaming_grouped_tranches_equal_ungrouped(kinds, iters, group,
                                                    seed):
    """Tranche grouping (several iterations per generation wakeup) serves
    the SAME words as group=1 — the grouped stacked draw is the
    concatenation of the per-iteration draws. Any group size, including
    group > iters and a ragged tail group."""
    requests = [PlanRequest(k, _SHAPES[k], "t") for k in kinds]
    iter_plan = TriplePlan(requests)
    full = requests * iters
    a = _consume(StreamingPooledDealer(iter_plan, iters, seed=seed), full)
    grouped = StreamingPooledDealer(iter_plan, iters, seed=seed, group=group)
    b = _consume(grouped, full)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert grouped.served_iters == iters
    assert all(v == 0 for v in grouped.remaining().values())


def test_streaming_auto_group_sizes_to_tranche_bytes():
    """group="auto" groups tiny per-iteration tranches (amortizing worker
    wakeups) but never more than the fit has iterations; a big tranche
    stays ungrouped."""
    small = TriplePlan([PlanRequest("mul", (2, 2), "t")])
    d = StreamingPooledDealer(small, 5, seed=1, group="auto",
                              async_gen=False)
    assert d.group == 5                      # tiny tranche: one wakeup
    big = TriplePlan([PlanRequest("matmul", ((512, 256), (256, 64)), "t")])
    d2 = StreamingPooledDealer(big, 5, seed=1, group="auto",
                               async_gen=False)
    assert d2.group == 1                     # ~7 MB/iteration: no grouping
    d2.close()
    d.close()


def test_streaming_sync_mode_matches_async():
    """async_gen=False (generation inline at dispatch) serves the same
    words — the worker thread is an overlap optimization, not semantics."""
    requests = [PlanRequest("mul", (3, 3), "a"), PlanRequest("bin", (2,), "b")]
    plan = TriplePlan(requests)
    full = requests * 3
    a = _consume(StreamingPooledDealer(plan, 3, seed=4, async_gen=False), full)
    b = _consume(StreamingPooledDealer(plan, 3, seed=4, async_gen=True), full)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


def test_streaming_peak_residency_independent_of_iters():
    """The headline streaming property: peak device residency is bounded by
    `prefetch` tranches, not by the fit length — while the bulk pool grows
    linearly with iters. async_gen=False makes the observed peak exact (with
    the worker it depends on generate/consume interleaving, so the async
    case asserts the structural prefetch bound instead of equality)."""
    requests = [PlanRequest("mul", (32, 8), "t"), PlanRequest("bin", (16,), "t")]
    plan = TriplePlan(requests)
    tranche_bytes = PooledDealer(plan, seed=1).pool_bytes
    peaks = {}
    for iters in (2, 8):
        s = StreamingPooledDealer(plan, iters, seed=1, async_gen=False)
        _consume(s, requests * iters)
        peaks[iters] = s.pool_bytes
    assert peaks[2] == peaks[8] == 2 * tranche_bytes
    assert peaks[8] < PooledDealer(plan.repeat(8), seed=1).pool_bytes
    s = StreamingPooledDealer(plan, 8, seed=1)          # async worker
    _consume(s, requests * 8)
    assert s.pool_bytes <= 2 * tranche_bytes


def test_streaming_exhaustion_and_unplanned_raise():
    plan = TriplePlan([PlanRequest("mul", (2, 2), "t")])
    dealer = StreamingPooledDealer(plan, 2, seed=1)
    dealer.mul_triple((2, 2))
    dealer.mul_triple((2, 2))
    with pytest.raises(PoolExhaustedError, match="exhausted"):
        dealer.mul_triple((2, 2))
    dealer2 = StreamingPooledDealer(plan, 1, seed=1)
    with pytest.raises(PoolExhaustedError, match="never"):
        dealer2.bin_triple((2, 2))


def test_streaming_early_stop_leaves_surplus_and_closes():
    """Stopping mid-schedule (the tol case) leaves counted surplus; undis-
    patched tranches are never generated. close() is idempotent."""
    requests = [PlanRequest("mul", (2, 2), "t"), PlanRequest("rand", (3,), "t")]
    plan = TriplePlan(requests)
    dealer = StreamingPooledDealer(plan, 10, seed=2)
    _consume(dealer, requests * 2)       # 2 of 10 iterations
    dealer.mul_triple((2, 2))            # half of iteration 3
    rem = dealer.remaining()
    assert rem[("mul", (2, 2))] == 7
    assert rem[("rand", (3,))] == 8
    dealer.close()
    dealer.close()


def test_fit_streamed_with_tol_leaves_surplus():
    """A tol early-stop under the streaming dealer only leaves surplus —
    never an error — and peak residency stays at the prefetch bound."""
    x = _blobs(200, 4, 3, seed=4)
    cfg = KMeansConfig(k=3, iters=50, seed=5, tol=1e-6, backend="xla",
                       offline="streamed")
    res = SecureKMeans(cfg).fit(x[:, :2], x[:, 2:])
    assert res.iters_run < 50
    assert all(v >= 0 for v in res.dealer.remaining().values())
    assert any(v > 0 for v in res.dealer.remaining().values())


# ---------------------------------------------------------------------------
# plan cache: a second identical-shape fit must skip the dry-run trace
# ---------------------------------------------------------------------------

def test_plan_cache_skips_second_trace(monkeypatch):
    import repro.core.kmeans as KM
    KM.clear_plan_cache()
    x = _blobs(40, 4, 2, seed=3)
    cfg = KMeansConfig(k=2, iters=2, seed=5, backend="xla", offline="pooled")
    r1 = SecureKMeans(cfg).fit(x[:, :2], x[:, 2:])
    assert len(KM._PLAN_CACHE) == 1

    def boom(self, sa, sb):
        raise AssertionError("second identical fit re-traced the plan")

    monkeypatch.setattr(SecureKMeans, "_trace_iteration", boom)
    r2 = SecureKMeans(cfg).fit(x[:, :2], x[:, 2:])
    np.testing.assert_array_equal(np.asarray(r1.centroids.s0, np.uint64),
                                  np.asarray(r2.centroids.s0, np.uint64))
    # a DIFFERENT config key must re-trace (and here: blow up)
    cfg3 = KMeansConfig(k=2, iters=2, seed=5, backend="xla",
                        offline="pooled", tol=1e-9)
    with pytest.raises(AssertionError, match="re-traced"):
        SecureKMeans(cfg3).fit(x[:, :2], x[:, 2:])


def test_pooled_offline_arrays_match_trusted_dealer():
    """The launch-path bulk offline arrays equal the on-demand flat list,
    tensor for tensor, across multiple iterations from one pool."""
    n, d, k, d_a = 16, 4, 2, 2
    requests = record_offline_shapes(n, d, k, d_a)
    iters = 2
    flats, dealer = pooled_offline_arrays(requests, seed=23, iters=iters)
    assert len(flats) == iters
    trusted = TrustedDealer(seed=23)
    for flat in flats:
        want = materialize_offline(requests, trusted)
        assert len(flat) == len(want)
        for got, ref in zip(flat, want):
            np.testing.assert_array_equal(np.asarray(got, np.uint64),
                                          np.asarray(ref, np.uint64))
    assert all(v == 0 for v in dealer.remaining().values())


# -- one device put per shape-class ------------------------------------------

PUT_SHAPES = {"matmul": ((5, 3), (3, 2)), "mul": (4, 3), "bin": (2, 7),
              "rand": (6,), "seed": ()}


def _counting_put(monkeypatch):
    """Count `jax.device_put` calls and the leaves of each; fail on any
    `jnp.asarray`, the per-array upload the dealer no longer makes."""
    calls = []
    real = jax.device_put

    def put(x, *a, **kw):
        calls.append(len(jax.tree_util.tree_leaves(x)))
        return real(x, *a, **kw)

    def no_asarray(*a, **kw):
        raise AssertionError("per-array jnp.asarray in the dealer")

    monkeypatch.setattr(jax, "device_put", put)
    monkeypatch.setattr(triples.jnp, "asarray", no_asarray)
    return calls


@pytest.mark.parametrize("kind", sorted(PUT_SHAPES))
def test_put_class_serves_host_rows_word_for_word(kind):
    """Every served array is uint64 and row i of the host stacked draw, word
    for word; the traced upload counts the buffers its one put made."""
    count = 3
    key = triples._class_key(kind, PUT_SHAPES[kind])
    shape = key[1:] if kind == "matmul" else key[1]
    arrays = triples._gen_class(triples._class_rng(31, key), kind, shape,
                                count)
    t = _trace.get_tracer()
    was = t.enabled
    t.reset()
    _trace.configure(enabled=True)
    try:
        served, nbytes = triples._put_class("k", arrays, count)
        events = t.events()
    finally:
        _trace.configure(enabled=was)
        t.reset()
    assert len(served) == count
    for i, req in enumerate(served):
        assert len(req) == len(arrays) == triples._SLOTS[kind]
        for got, host in zip(req, arrays):
            assert got.dtype == np.uint64
            assert got.shape == host.shape[1:]
            np.testing.assert_array_equal(np.asarray(got), host[i])
    (up,) = [e["args"] for e in events if e["name"] == "dealer.upload"]
    (split,) = [e["args"] for e in events if e["name"] == "dealer.split"]
    assert up["arrays"] == count * len(arrays)
    assert up["h2d_bytes"] == nbytes == sum(a.nbytes for a in arrays)
    assert up["key"] == split["key"] == "k"
    assert up["count"] == split["count"] == count


def test_gen_tranche_puts_each_class_once(monkeypatch):
    counts = {triples._class_key(k, PUT_SHAPES[k]): c
              for k, c in zip(sorted(PUT_SHAPES), (3, 4, 5, 3, 6))}
    rngs = {key: triples._class_rng(9, key) for key in counts}
    calls = _counting_put(monkeypatch)
    pools, _ = triples._gen_tranche(rngs, counts)
    assert sorted(calls) == sorted(c * triples._SLOTS[key[0]]
                                   for key, c in counts.items())
    assert {key: len(v) for key, v in pools.items()} == counts


@pytest.mark.parametrize("kind", sorted(PUT_SHAPES))
def test_provision_item_puts_its_chunk_once(monkeypatch, kind):
    """A bank chunk takes the same one put, and serves the same words as
    the tranche path from the same stream offset."""
    key = triples._class_key(kind, PUT_SHAPES[kind])
    states = {key: triples._class_rng(5, key).bit_generator.state}
    want, _ = triples._gen_tranche({key: triples._class_rng(5, key)},
                                   {key: 7})
    calls = _counting_put(monkeypatch)
    entries, nbytes = triples._gen_provision_item(states, (key, 2, 4))
    assert calls == [4 * triples._SLOTS[kind]]
    assert nbytes == sum(int(a.size) * 8 for t in entries for a in t)
    for got, ref in zip(entries, want[key][2:6]):
        for g, r in zip(got, ref):
            assert g.dtype == np.uint64
            np.testing.assert_array_equal(np.asarray(g), np.asarray(r))
