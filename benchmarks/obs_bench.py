"""Observability suite: the tracer's overhead budget, made measured.

DESIGN.md §15 promises the span tracer is free when disabled and <=5%
on the hot online walls when enabled. Both claims are asserted here, not
just reported:

* **Disabled**: a `span()` call on a disabled tracer is one attribute
  check returning a shared no-op context manager — measured here in
  ns/call next to a bare function call for scale.
* **Enabled**: the traced online-fit wall and serve-drain wall stay
  within `OVERHEAD_BUDGET` (1.05x) of the untraced runs, min-of-reps on
  both sides so a shared-CPU container hiccup doesn't fake a regression.
  The run asserts the budget — a tracer that leaks real time into the
  online path fails the suite.
* **Coverage**: the traced fit + drain must actually hit the
  instrumented seams — the span names recorded are reported and the
  load-bearing ones (fit, serve.drain, serve.request, bank.provision)
  asserted present.

Also exports the traced run's Chrome-trace JSON to
benchmarks/trace_sample.json — the CI artifact you can drop straight
into ui.perfetto.dev. Writes benchmarks/BENCH_obs.json. Wired as
`python -m benchmarks.run --only obs --quick`.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

from benchmarks.common import make_blobs
from repro.core.kmeans import KMeansConfig, SecureKMeans
from repro.core.triples import TripleBank, serve_seed
from repro.obs import trace as _trace
from repro.serve import ScoringService

BENCH_PATH = os.path.join(os.path.dirname(__file__), "BENCH_obs.json")
TRACE_PATH = os.path.join(os.path.dirname(__file__), "trace_sample.json")
OVERHEAD_BUDGET = 1.05          # traced wall / untraced wall, asserted


def _noop_ns_per_call(calls: int = 200_000) -> dict:
    """ns/call of span() on a DISABLED tracer, with a bare function call
    timed the same way for scale."""
    t = _trace.Tracer(enabled=False)
    span = t.span
    t0 = time.perf_counter_ns()
    for _ in range(calls):
        with span("x"):
            pass
    disabled_ns = (time.perf_counter_ns() - t0) / calls

    def f():
        return None

    t0 = time.perf_counter_ns()
    for _ in range(calls):
        f()
    bare_ns = (time.perf_counter_ns() - t0) / calls
    return {"workload": "noop_span", "calls": calls,
            "disabled_span_ns": round(disabled_ns, 1),
            "bare_call_ns": round(bare_ns, 1)}


def _fit_once(a, b, k, iters, bs):
    cfg = KMeansConfig(k=k, iters=iters, seed=3, backend="auto",
                       sparse=True, batch_size=bs, offline="pooled",
                       pipeline=True)
    return SecureKMeans(cfg).fit(a, b)


def _drain_once(km, res, stream, d, rung, requests):
    svc = ScoringService(km, res,
                         bank=TripleBank(seed=serve_seed(km.cfg.seed)),
                         rungs=(rung,), with_scores=True,
                         d_a=d // 2, d_b=d // 2,
                         provision_copies=requests, pipeline=True)
    svc.warm()
    for i in range(requests):
        q = stream[i * rung:(i + 1) * rung]
        svc.submit(q[:, :d // 2], q[:, d // 2:])
    t0 = svc.stats.online_seconds
    out = svc.drain()
    return out, svc.stats.online_seconds - t0


def run(quick: bool = False):
    # walls must be long enough that min-of-reps beats shared-CPU noise:
    # the budget is asserted, so a 40ms drain with +-20% jitter won't do
    n, bs, iters, reps = (2048, 512, 2, 5) if quick else (8192, 1024, 3, 5)
    k, d = 5, 24
    rung, requests = (64, 12) if quick else (128, 16)
    x = make_blobs(n, d, k, seed=4, sparse_frac=0.8)
    a, b = x[:, :d // 2], x[:, d // 2:]
    stream = make_blobs(rung * requests, d, k, seed=9, sparse_frac=0.8)

    tracer = _trace.get_tracer()
    was_enabled = tracer.enabled
    _trace.configure(enabled=False)
    _fit_once(a, b, k, iters, bs)               # warmup: compile + plans
    # one shared fitted model for every drain rep, plus one untimed
    # warmup drain so lazy predict-plan caches fill before timing
    km = SecureKMeans(KMeansConfig(k=k, iters=iters, seed=3,
                                   backend="auto", sparse=True,
                                   batch_size=bs, offline="pooled",
                                   pipeline=True))
    res_serve = km.fit(a, b)
    _drain_once(km, res_serve, stream, d, rung, requests)

    fit_walls = {False: [], True: []}
    drain_walls = {False: [], True: []}
    res_by = {}
    out_by = {}
    for _ in range(reps):
        for enabled in (False, True):
            _trace.configure(enabled=enabled)
            tracer.reset()
            res = _fit_once(a, b, k, iters, bs)
            fit_walls[enabled].append(res.online_seconds)
            out, secs = _drain_once(km, res_serve, stream, d, rung,
                                    requests)
            drain_walls[enabled].append(secs)
            res_by[enabled] = res
            out_by[enabled] = out
    # tracing must not change a single output bit
    np.testing.assert_array_equal(
        np.asarray(res_by[False].centroids.s0, np.uint64),
        np.asarray(res_by[True].centroids.s0, np.uint64))
    for r0, r1 in zip(out_by[False], out_by[True]):
        np.testing.assert_array_equal(r0.labels, r1.labels)

    # min-of-reps both sides: least-perturbed observation of each mode
    fit_off, fit_on = min(fit_walls[False]), min(fit_walls[True])
    dr_off, dr_on = min(drain_walls[False]), min(drain_walls[True])
    fit_ratio = fit_on / max(fit_off, 1e-9)
    dr_ratio = dr_on / max(dr_off, 1e-9)
    assert fit_ratio <= OVERHEAD_BUDGET, \
        f"traced fit overhead x{fit_ratio:.3f} > {OVERHEAD_BUDGET}"
    assert dr_ratio <= OVERHEAD_BUDGET, \
        f"traced drain overhead x{dr_ratio:.3f} > {OVERHEAD_BUDGET}"

    # coverage: the traced runs must have hit the instrumented seams
    counts = tracer.span_counts()
    for need in ("fit", "serve.drain", "serve.request", "bank.provision"):
        assert counts.get(need, 0) > 0, f"span {need!r} never recorded"
    tracer.export_chrome(TRACE_PATH)
    noop = _noop_ns_per_call()
    _trace.configure(enabled=was_enabled)

    rows = [
        {"workload": "fit_online", "n": n, "d": d, "k": k, "iters": iters,
         "batch_size": bs, "reps": reps,
         "untraced_s": round(fit_off, 4), "traced_s": round(fit_on, 4),
         "overhead_x": round(fit_ratio, 3), "budget_x": OVERHEAD_BUDGET},
        {"workload": "serve_drain", "rung": rung, "requests": requests,
         "reps": reps,
         "untraced_s": round(dr_off, 4), "traced_s": round(dr_on, 4),
         "overhead_x": round(dr_ratio, 3), "budget_x": OVERHEAD_BUDGET},
        noop,
        {"workload": "coverage", "spans_recorded": sum(counts.values()),
         "distinct_span_names": len(counts),
         "span_counts": dict(sorted(counts.items())),
         "trace_artifact": os.path.basename(TRACE_PATH)},
    ]
    with open(BENCH_PATH, "w") as f:
        json.dump({"rows": rows,
                   "note": "overhead_x = min-of-reps traced wall over "
                           "min-of-reps untraced wall, asserted <= "
                           f"{OVERHEAD_BUDGET}x on both the online fit "
                           "and the serve drain; outputs asserted "
                           "bit-identical traced vs untraced. "
                           "disabled_span_ns is the cost of leaving the "
                           "instrumentation in a hot loop with tracing "
                           "off. trace_sample.json is the traced run's "
                           "Chrome-trace export (ui.perfetto.dev)."},
                  f, indent=1)
    return rows


def derived(rows):
    fit = [r for r in rows if r["workload"] == "fit_online"][0]
    dr = [r for r in rows if r["workload"] == "serve_drain"][0]
    cov = [r for r in rows if r["workload"] == "coverage"][0]
    return (f"fit x{fit['overhead_x']} drain x{dr['overhead_x']} "
            f"spans {cov['spans_recorded']}")
