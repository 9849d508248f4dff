"""Minibatch Lloyd + pipelined executor tests (DESIGN.md §11).

Load-bearing properties:
* minibatch fit at batch_size >= n is BIT-EXACT vs the existing full-batch
  pooled fast path for all four partition x sparsity combos (same share
  words, same dealer counters, same CommLog tallies);
* pipeline=True is stream-identical to pipeline=False (the executor only
  reorders host work into the device window — the SlotDealer pins every
  slot's randomness at generation time, in canonical order);
* batch geometries are reused — a many-batch fit compiles at most a
  handful of program pairs (full shape + remainder), never one per batch;
* SlotDealer serves the words PooledDealer would, for ANY acquisition
  order within the window, streamed or pregenerated, grouped or not.
"""
import numpy as np
import pytest

from repro.core.kmeans import (KMeansConfig, SecureKMeans,
                               _assemble_assignment, _minibatch_bounds)
from repro.core.triples import (PlanRequest, PooledDealer,
                                PoolExhaustedError, SlotDealer, TriplePlan)
from repro.launch import kmeans_step as K
from repro.launch.pipeline import StageTask, run_pipeline


def _blobs(n, d, k, seed, sparse_frac=0.0):
    rng = np.random.default_rng(seed)
    centers = rng.uniform(-4, 4, (k, d))
    lab = rng.integers(0, k, n)
    x = centers[lab] + rng.normal(0, 0.3, (n, d))
    if sparse_frac:
        x = x * (rng.random((n, d)) >= sparse_frac)
    return x


def _split(x, partition):
    n, d = x.shape
    if partition == "vertical":
        return x[:, :d // 2], x[:, d // 2:]
    return x[:n // 2], x[n // 2:]


def _assert_same_fit(r0, r1):
    for field in ("centroids", "assignment"):
        for s in ("s0", "s1"):
            np.testing.assert_array_equal(
                np.asarray(getattr(getattr(r0, field), s), np.uint64),
                np.asarray(getattr(getattr(r1, field), s), np.uint64))
    assert (r0.dealer.n_matmul, r0.dealer.n_mul, r0.dealer.n_bin) == \
           (r1.dealer.n_matmul, r1.dealer.n_mul, r1.dealer.n_bin)
    assert r0.log.by_tag("online") == r1.log.by_tag("online")


# ---------------------------------------------------------------------------
# minibatch fit vs the full-batch fast path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("partition", ["vertical", "horizontal"])
@pytest.mark.parametrize("sparse", [False, True])
def test_minibatch_full_batch_bit_exact(partition, sparse):
    """batch_size = n (one batch covering the fit) must replay the existing
    full-batch pooled path word for word: same shares, dealer counters, and
    online/offline CommLog tallies — the accumulator algebra composes to
    exactly the single-launch S3."""
    n, d, k = 48, 4, 2
    x = _blobs(n, d, k, seed=11, sparse_frac=0.5 if sparse else 0.0)
    a, b = _split(x, partition)
    base = dict(k=k, iters=2, partition=partition, sparse=sparse, seed=5,
                backend="xla")
    r_full = SecureKMeans(KMeansConfig(**base, offline="pooled")).fit(a, b)
    r_mb = SecureKMeans(KMeansConfig(**base, offline="pooled",
                                     batch_size=n)).fit(a, b)
    _assert_same_fit(r_full, r_mb)
    assert r_full.log.by_tag("offline") == r_mb.log.by_tag("offline")


@pytest.mark.parametrize("partition", ["vertical", "horizontal"])
@pytest.mark.parametrize("sparse", [False, True])
def test_minibatch_pipeline_on_off_stream_identical(partition, sparse):
    """pipeline=True == pipeline=False, multi-batch, with a remainder
    batch, across pooled and streamed dealers: identical shares, dealer
    counters, CommLog tallies — the overlap cannot change a single word."""
    n, d, k = 48, 4, 2
    x = _blobs(n, d, k, seed=9, sparse_frac=0.5 if sparse else 0.0)
    a, b = _split(x, partition)
    base = dict(k=k, iters=2, partition=partition, sparse=sparse, seed=5,
                backend="xla", batch_size=17)        # 17 -> ragged batches
    res = {}
    for pipe in (True, False):
        for off in ("pooled", "streamed"):
            cfg = KMeansConfig(**base, offline=off, pipeline=pipe)
            res[(pipe, off)] = SecureKMeans(cfg).fit(a, b)
    ref = res[(False, "pooled")]
    for key, r in res.items():
        _assert_same_fit(ref, r)
    # and the minibatch split agrees with the full-batch fit on the data
    # itself (well-separated blobs: truncation LSB noise flips nothing)
    full = SecureKMeans(KMeansConfig(k=k, iters=2, partition=partition,
                                     sparse=sparse, seed=5, backend="xla",
                                     offline="pooled")).fit(a, b)
    assert ref.labels_plain().tolist() == full.labels_plain().tolist()
    np.testing.assert_allclose(ref.centroids_plain(),
                               full.centroids_plain(), atol=1e-3)


def test_minibatch_remainder_geometry_reuse():
    """A many-batch fit compiles ONE program pair per distinct batch
    geometry (full + remainder) plus one finalize — never per batch."""
    K.clear_program_cache()
    n = 80
    x = _blobs(n, 4, 2, seed=3)
    cfg = KMeansConfig(k=2, iters=2, seed=5, backend="xla",
                       offline="pooled", batch_size=16)  # 5 equal batches
    SecureKMeans(cfg).fit(x[:, :2], x[:, 2:])
    assert len(K._BATCH_PROGRAM_CACHE) == 1
    assert len(K._FINALIZE_CACHE) == 1
    cfg2 = KMeansConfig(k=2, iters=2, seed=5, backend="xla",
                        offline="pooled", batch_size=32)  # 32,32,16
    SecureKMeans(cfg2).fit(x[:, :2], x[:, 2:])
    # the 16-row remainder reuses the FIRST fit's 16-row program: only the
    # 32-row geometry is new
    assert len(K._BATCH_PROGRAM_CACHE) == 2
    assert len(K._FINALIZE_CACHE) == 1        # finalize keyed by (k, d, n)


def test_minibatch_requires_planned_offline():
    x = _blobs(24, 4, 2, seed=1)
    with pytest.raises(ValueError, match="pooled"):
        SecureKMeans(KMeansConfig(k=2, iters=1, batch_size=8)) \
            .fit(x[:, :2], x[:, 2:])
    with pytest.raises(ValueError, match="fast path"):
        SecureKMeans(KMeansConfig(k=2, iters=1, batch_size=8,
                                  offline="pooled", backend="numpy")) \
            .fit(x[:, :2], x[:, 2:])
    with pytest.raises(ValueError, match="batch_size"):
        KMeansConfig(k=2, batch_size=0)


def test_minibatch_tol_early_stop_closes_cleanly():
    """A tol early-stop mid-schedule leaves SlotDealer surplus, never an
    error — undispatched slots are dropped by close()."""
    x = _blobs(120, 4, 3, seed=4)
    cfg = KMeansConfig(k=3, iters=40, seed=5, tol=1e-6, backend="xla",
                       offline="streamed", batch_size=48)
    res = SecureKMeans(cfg).fit(x[:, :2], x[:, 2:])
    assert res.iters_run < 40
    assert any(v > 0 for v in res.dealer.remaining().values())
    res.dealer.close()                      # idempotent


# ---------------------------------------------------------------------------
# _minibatch_bounds / assignment reassembly
# ---------------------------------------------------------------------------

def test_minibatch_bounds_vertical():
    assert _minibatch_bounds("vertical", 10, 10, 4) == \
        [((0, 4), (0, 4)), ((4, 8), (4, 8)), ((8, 10), (8, 10))]
    assert _minibatch_bounds("vertical", 10, 10, 100) == [((0, 10), (0, 10))]


def test_minibatch_bounds_horizontal_alignment():
    """Both parties get the same NUMBER of contiguous chunks, sizes within
    one of each other, covering all rows — even for uneven row counts."""
    for na, nb, bs in [(9, 7, 4), (10, 10, 4), (5, 29, 8), (3, 3, 100)]:
        bounds = _minibatch_bounds("horizontal", na, nb, bs)
        a_spans = [b[0] for b in bounds]
        b_spans = [b[1] for b in bounds]
        assert a_spans[0][0] == 0 and a_spans[-1][1] == na
        assert b_spans[0][0] == 0 and b_spans[-1][1] == nb
        for spans in (a_spans, b_spans):
            for (l0, h0), (l1, _h1) in zip(spans, spans[1:]):
                assert h0 == l1
            sizes = [h - l for l, h in spans]
            assert max(sizes) - min(sizes) <= 1
            assert min(sizes) >= 1


# ---------------------------------------------------------------------------
# SlotDealer: the acquisition-order-independence contract
# ---------------------------------------------------------------------------

_SHAPES = {"matmul": ((5, 3), (3, 2)), "mul": (4, 3), "bin": (2, 7),
           "rand": (6,), "seed": ()}


def _slot_plans(seed, n_slots=6, per_slot=3):
    rng = np.random.default_rng(seed)
    kinds = list(_SHAPES)
    return [TriplePlan([PlanRequest(k, _SHAPES[k], "t")
                        for k in rng.choice(kinds, per_slot)])
            for _ in range(n_slots)]


def _serve_slot(view, plan):
    out = []
    for r in plan.requests:
        if r.kind == "matmul":
            t = view.matmul_triple(*r.shape)
            out += [t.u.s0, t.u.s1, t.v.s0, t.v.s1, t.z.s0, t.z.s1]
        elif r.kind == "mul":
            t = view.mul_triple(r.shape)
            out += [t.u.s0, t.u.s1, t.v.s0, t.v.s1, t.z.s0, t.z.s1]
        elif r.kind == "bin":
            t = view.bin_triple(r.shape)
            out += [t.u.b0, t.u.b1, t.v.b0, t.v.b1, t.z.b0, t.z.b1]
        elif r.kind == "rand":
            out.append(view.rand(r.shape))
        else:
            out.append(np.uint64(view.mask_seed()))
    return [np.asarray(a, np.uint64) for a in out]


@pytest.mark.parametrize("stream", [False, True])
@pytest.mark.parametrize("group_bytes", [0, "auto"])
def test_slot_dealer_matches_pooled_any_order(stream, group_bytes):
    """Acquiring slots out of order (the pipelined lead) serves the same
    words as PooledDealer over the concatenated plan — streamed or
    pregenerated, grouped or per-slot generation."""
    plans = _slot_plans(seed=8)
    concat = TriplePlan([r for p in plans for r in p.requests])
    pooled = PooledDealer(concat, seed=13)
    want = {}
    cursor = []
    for i, p in enumerate(plans):
        want[i] = _serve_slot(pooled, p)
        cursor.append(p)
    order = [0, 2, 1, 4, 3, 5]          # the executor's S1-ahead pattern
    dealer = SlotDealer(plans, seed=13, stream=stream, async_gen=False,
                        group_bytes=group_bytes)
    for i in order:
        got = _serve_slot(dealer.acquire(i), plans[i])
        assert len(got) == len(want[i])
        for x, y in zip(got, want[i]):
            np.testing.assert_array_equal(x, y)
    dealer.close()


def test_slot_dealer_async_worker_matches_sync():
    plans = _slot_plans(seed=21, n_slots=8)
    serve = {}
    for async_gen in (False, True):
        dealer = SlotDealer(plans, seed=4, stream=True, async_gen=async_gen,
                            window=4)
        serve[async_gen] = [w for i in range(len(plans))
                            for w in _serve_slot(dealer.acquire(i),
                                                 plans[i])]
        dealer.close()
    for x, y in zip(serve[False], serve[True]):
        np.testing.assert_array_equal(x, y)


def test_slot_dealer_forward_acquire_past_window_no_deadlock():
    """acquire(i) far beyond the backpressure window must generate through
    to slot i (a waiting caller overrides the window) — and the words stay
    canonical."""
    plans = [TriplePlan([PlanRequest("mul", (8, 8), "t")])
             for _ in range(10)]
    dealer = SlotDealer(plans, seed=2, stream=True, window=2, group_bytes=0)
    got = dealer.acquire(7).mul_triple((8, 8))
    concat = TriplePlan([r for p in plans for r in p.requests])
    pooled = PooledDealer(concat, seed=2)
    for _ in range(8):                   # the 8th draw is slot 7's word
        want = pooled.mul_triple((8, 8))
    np.testing.assert_array_equal(np.asarray(got.u.s0, np.uint64),
                                  np.asarray(want.u.s0, np.uint64))
    dealer.close()


def test_slot_dealer_exhaustion_and_reacquire():
    plans = [TriplePlan([PlanRequest("mul", (2, 2), "t")])] * 2
    dealer = SlotDealer(plans, seed=1, stream=False)
    v = dealer.acquire(0)
    v.mul_triple((2, 2))
    with pytest.raises(PoolExhaustedError, match="exhausted"):
        v.mul_triple((2, 2))
    with pytest.raises(PoolExhaustedError, match="never"):
        dealer.acquire(1).bin_triple((2, 2))
    with pytest.raises(PoolExhaustedError, match="already"):
        dealer.acquire(0)
    with pytest.raises(IndexError):
        dealer.acquire(7)


# ---------------------------------------------------------------------------
# the executor itself
# ---------------------------------------------------------------------------

def test_run_pipeline_phase_order_and_results():
    """Pipelined execution returns the same results as sequential; the only
    reordering is pre(t+1) sliding before mid/post(t)."""
    for pipeline in (False, True):
        trace = []

        def mk(t):
            return StageTask(
                pre=lambda t=t: trace.append(("pre", t)) or t * 10,
                launch=lambda p, t=t: trace.append(("launch", t)) or p + 1,
                mid=lambda p, o, t=t: trace.append(("mid", t)) or o + p,
                post=lambda p, o, m, t=t: trace.append(("post", t)) or m)

        out = run_pipeline([mk(t) for t in range(3)], pipeline=pipeline)
        assert out == [1, 21, 41]
        # every phase ran exactly once per task, launch after its pre
        for t in range(3):
            assert trace.index(("pre", t)) < trace.index(("launch", t)) \
                < trace.index(("mid", t)) < trace.index(("post", t))
        if pipeline:
            assert trace.index(("pre", 1)) < trace.index(("mid", 0))
        else:
            assert trace.index(("pre", 1)) > trace.index(("post", 0))


def test_assemble_assignment_horizontal_order():
    """Horizontal reassembly restores [all A rows; all B rows] from per-
    batch [A chunk; B chunk] outputs."""
    import jax.numpy as jnp

    from repro.core.sharing import AShare
    parts = [AShare(jnp.asarray(np.array([[1], [2], [10]], np.uint64)),
                    jnp.asarray(np.array([[0], [0], [0]], np.uint64))),
             AShare(jnp.asarray(np.array([[3], [11]], np.uint64)),
                    jnp.asarray(np.array([[0], [0]], np.uint64)))]
    batches = [{"a_rows": 2}, {"a_rows": 1}]
    c = _assemble_assignment("horizontal", parts, batches)
    np.testing.assert_array_equal(np.asarray(c.s0, np.uint64).ravel(),
                                  [1, 2, 3, 10, 11])


# ---------------------------------------------------------------------------
# a horizontal consortium at a skewed split (the fraud-horizontal-skew
# deployment at a small size): minibatch, streamed, pipelined
# ---------------------------------------------------------------------------

def _init_split(seed, n, n_a, k):
    """How many of a fit's k initial rows lie among party A's n_a."""
    idx = np.random.default_rng(seed).choice(n, k, replace=False)
    return int((idx < n_a).sum())


# the finalize division errs by at most 2^(m - s - f) |x| (m = ceil(log2 n),
# s = m // 2), under 3e-4 for these rows and in practice about 1.5e-5; a
# secure fit encoded at f=10 misses it
# (test_horizontal_skewed_fit_f10_misses_tolerance)
CENTROID_ATOL = 1e-4

# (n, n_a, d, k, batch_size, seed): a 2.9:1 split; a split where party B
# has fewer rows than ceil(n / batch_size), so the batches are clamped to
# n_b; and k=3 with a seed for each split 0..3 of the initial rows
SKEWED_FITS = [(2000, 1489, 16, 2, 256, 5), (2000, 1995, 16, 2, 256, 82)] \
    + [(800, 596, 8, 3, 128, s) for s in (13, 4, 1, 6)]


@pytest.mark.parametrize("n,n_a,d,k,batch_size,seed", SKEWED_FITS,
                         ids=["2.9to1", "b_clamped", "k3_split0",
                              "k3_split1", "k3_split2", "k3_split3"])
def test_horizontal_skewed_fit_matches_plaintext(n, n_a, d, k, batch_size,
                                                 seed):
    """A secure horizontal minibatch fit at a skewed split agrees with
    plain Lloyd on the stacked [A rows; B rows] from the same initial
    rows."""
    from repro.core.kmeans import plaintext_kmeans
    x = _blobs(n, d, k, seed=11)
    bounds = _minibatch_bounds("horizontal", n_a, n - n_a, batch_size)
    assert len(bounds) == min(-(-n // batch_size), n - n_a)
    cfg = KMeansConfig(k=k, iters=1, seed=seed, partition="horizontal",
                       backend="xla", offline="streamed",
                       batch_size=batch_size, pipeline=True)
    res = SecureKMeans(cfg).fit(x[:n_a], x[n_a:])
    mu_ref, lab_ref = plaintext_kmeans(x, k, 1, seed=seed)
    assert (res.labels_plain() == lab_ref).mean() > 0.99
    np.testing.assert_allclose(res.centroids_plain(), mu_ref, rtol=0,
                               atol=CENTROID_ATOL)
    if k == 3:
        assert _init_split(seed, n, n_a, k) == SKEWED_FITS.index(
            (n, n_a, d, k, batch_size, seed)) - 2


@pytest.mark.parametrize("f", [20, 10])
def test_horizontal_skewed_fit_f10_misses_tolerance(f):
    """CENTROID_ATOL is tight enough to fail a fit encoded at f=10: the
    2.9:1 case, fitted whole-batch (the minibatch programs are compiled for
    f=20 alone), agrees with plain Lloyd within it at f=20 and misses it
    at f=10."""
    from repro.core.kmeans import plaintext_kmeans
    n, n_a, d, k, _batch_size, seed = SKEWED_FITS[0]
    x = _blobs(n, d, k, seed=11)
    cfg = KMeansConfig(k=k, iters=1, seed=seed, partition="horizontal",
                       backend="xla", f=f)
    res = SecureKMeans(cfg).fit(x[:n_a], x[n_a:])
    mu_ref, _ = plaintext_kmeans(x, k, 1, seed=seed)
    close = np.abs(res.centroids_plain(f) - mu_ref).max() <= CENTROID_ATOL
    assert close == (f == 20)


def _init_centroids_before(rng, x_a, x_b, k, f):
    """The horizontal initial centroids as the program made them before it
    joined the parties' rows on the host: each party's shares on the
    device, joined by a concatenation shaped by the split, then permuted
    back to the negotiated order by a gather."""
    import jax.numpy as jnp

    from repro.core.kmeans import _encode_np
    from repro.core.sharing import AShare, share
    idx = rng.choice(x_a.shape[0] + x_b.shape[0], k, replace=False)
    mask = idx < x_a.shape[0]
    parts = [share(_encode_np(x_a[idx[mask]], f), rng),
             share(_encode_np(x_b[idx[~mask] - x_a.shape[0]], f), rng)]
    s0 = jnp.concatenate([p.s0 for p in parts], 0)
    s1 = jnp.concatenate([p.s1 for p in parts], 0)
    inv = np.argsort(np.concatenate([np.where(mask)[0],
                                     np.where(~mask)[0]]))
    return AShare(s0[inv], s1[inv])


@pytest.mark.parametrize("k,seeds", [(2, (10, 2, 1)), (3, (13, 4, 1, 8))],
                         ids=["k2", "k3"])
def test_horizontal_init_centroids_words_unchanged(k, seeds):
    """For every split 0..k of the initial rows, the host-side join gives
    the share words the device-side join gave, from the same draws."""
    from repro.core import protocol as P
    n, n_a = 40, 30
    x = _blobs(n, 4, k, seed=3)
    for split, seed in enumerate(seeds):
        assert _init_split(seed, n, n_a, k) == split
        cfg = KMeansConfig(k=k, iters=1, seed=seed, partition="horizontal",
                           backend="xla")
        got = SecureKMeans(cfg)._init_centroids(
            P.make_ctx(seed, backend="xla"), np.random.default_rng(seed),
            x[:n_a], x[n_a:])
        want = _init_centroids_before(np.random.default_rng(seed),
                                      x[:n_a], x[n_a:], k, cfg.f)
        for s in ("s0", "s1"):
            np.testing.assert_array_equal(
                np.asarray(getattr(got, s), np.uint64),
                np.asarray(getattr(want, s), np.uint64))


def _assemble_before(c_parts, batches):
    """The horizontal reassembly as four eager device slices a batch."""
    import jax.numpy as jnp

    from repro.core.sharing import AShare
    a0 = [p.s0[:b["a_rows"]] for p, b in zip(c_parts, batches)]
    a1 = [p.s1[:b["a_rows"]] for p, b in zip(c_parts, batches)]
    b0 = [p.s0[b["a_rows"]:] for p, b in zip(c_parts, batches)]
    b1 = [p.s1[b["a_rows"]:] for p, b in zip(c_parts, batches)]
    return AShare(jnp.concatenate(a0 + b0, 0), jnp.concatenate(a1 + b1, 0))


@pytest.mark.parametrize("n_a,n_b,batch_size",
                         [(1489, 511, 256), (1995, 5, 256), (7, 93, 10),
                          (300, 300, 1000)])
def test_horizontal_assemble_words_unchanged(n_a, n_b, batch_size):
    """The one-program reassembly gives the words the per-batch slices
    gave, on skewed geometries."""
    import jax.numpy as jnp

    from repro.core.sharing import AShare
    rng = np.random.default_rng(n_a)
    bounds = _minibatch_bounds("horizontal", n_a, n_b, batch_size)
    batches = [{"a_rows": ahi - alo} for (alo, ahi), _ in bounds]
    parts = [AShare(*(jnp.asarray(rng.integers(
        0, 2 ** 64, (ahi - alo + bhi - blo, 3), dtype=np.uint64))
        for _ in range(2))) for (alo, ahi), (blo, bhi) in bounds]
    got = _assemble_assignment("horizontal", parts, batches)
    want = _assemble_before(parts, batches)
    for s in ("s0", "s1"):
        np.testing.assert_array_equal(np.asarray(getattr(got, s), np.uint64),
                                      np.asarray(getattr(want, s), np.uint64))


def test_horizontal_fits_compile_nothing_by_split():
    """After one warm-up fit, fits whose initial rows split otherwise
    between the parties compile nothing."""
    import jax
    n, n_a = 200, 149
    x = _blobs(n, 8, 2, seed=7)

    def fit(seed):
        cfg = KMeansConfig(k=2, iters=1, seed=seed, partition="horizontal",
                           backend="xla", offline="streamed", batch_size=64,
                           pipeline=True)
        res = SecureKMeans(cfg).fit(x[:n_a], x[n_a:])
        res.assignment.s0.block_until_ready()
    assert [_init_split(s, n, n_a, 2) for s in (1, 2, 10)] == [2, 1, 0]
    fit(1)
    compiles = []

    def count(event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)
    jax.monitoring.register_event_duration_secs_listener(count)
    try:
        fit(2)
        fit(10)
    finally:
        jax.monitoring.unregister_event_duration_listener(count)
    assert compiles == []


def test_horizontal_join_spans_in_a_traced_fit():
    """A traced horizontal minibatch fit opens `fit.join` twice, inside
    `fit.init_centroids` (the k initial rows) and inside `fit.assemble`
    (every row of each party)."""
    from repro.obs import trace as obs_trace
    n, n_a, d, k, batch_size, seed = SKEWED_FITS[0]
    x = _blobs(n, d, k, seed=11)
    cfg = KMeansConfig(k=k, iters=1, seed=seed, partition="horizontal",
                       backend="xla", offline="streamed",
                       batch_size=batch_size, pipeline=True)
    tracer = obs_trace.get_tracer()
    tracer.reset()
    obs_trace.configure(enabled=True)
    try:
        res = SecureKMeans(cfg).fit(x[:n_a], x[n_a:])
        res.assignment.s0.block_until_ready()
    finally:
        obs_trace.configure(enabled=False)
    ev = [e for e in tracer.events() if e.get("ph") == "X"]
    joins = [e for e in ev if e["name"] == "fit.join"]
    assert len(joins) == 2
    assert sum(joins[0]["args"][r] for r in ("rows_a", "rows_b")) == k
    assert (joins[1]["args"]["rows_a"], joins[1]["args"]["rows_b"]) == \
        (n_a, n - n_a)
    for join, outer in zip(joins, ("fit.init_centroids", "fit.assemble")):
        o = next(e for e in ev if e["name"] == outer)
        assert o["ts"] <= join["ts"] and \
            join["ts"] + join["dur"] <= o["ts"] + o["dur"]
