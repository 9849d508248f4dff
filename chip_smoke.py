#!/usr/bin/env python3
"""Smoke test of the secure K-means system on one TPU chip.

    python3 chip_smoke.py                   # on a machine with one TPU
    JAX_PLATFORMS=cpu python3 chip_smoke.py --cpu-rehearsal   # tiny, no TPU

It drives the main path once, through the entry points a user calls
(`SecureKMeans.fit`, `SecureKMeans.score` behind `serve.ScoringService`), at
the widths of the paper's fraud deployment (`configs/kmeans_fraud.py`:
d=1,024 with d_a=512 per party, k=16, 90% sparse), with n cut to what one
chip and the run's time limit hold:

  (a) dense vertical minibatch fit: n=131,072 (of 1,048,576), batch 16,384,
      2 iterations, offline="streamed". Then the bit-exactness check: a
      full-batch fit at n=4,096, d=64 on the chip's backend and again with
      backend="numpy" (the host reference); the shares must be identical.
  (b) sparse vertical fit (Protocol 2, host exchange between launches) at
      90% sparsity: n=32,768, batch 16,384, 2 iterations.
  (c) a ScoringService on model (a) with rungs (256, 1024) answers 8
      requests of mixed sizes.

The fits must agree with `plaintext_kmeans`, and the scores with the
nearest revealed centroid, within what the fixed-point encoding allows
(l=64, f=20): near-ties inside the precision bound, plus at most the
expected number of SecureML truncation failures (probability |x| / 2^64 per
truncated lane). Each phase prints one line. The last line of standard
output is {"ok": true, "device": {...}}, printed only when every phase
passed on a TPU. With no TPU, or outside a checkout of the repository, the
script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "src")

CHIP = dict(d=1024, d_a=512, k=16, iters=2, n_dense=131_072, batch=16_384,
            n_sparse=32_768, sparsity=0.9, n_exact=4096, d_exact=64,
            rungs=(256, 1024), requests=(1, 37, 256, 300, 1024, 1500, 64,
                                         700))
TINY = dict(d=16, d_a=8, k=4, iters=2, n_dense=1024, batch=256,
            n_sparse=512, sparsity=0.9, n_exact=128, d_exact=8,
            rungs=(16, 64), requests=(1, 5, 16, 20, 64, 90, 3, 40))


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def blobs(rng, n: int, d: int, k: int, sparsity: float = 0.0):
    """Min-max-scaled-like blobs: centres in [0, 1)^d, row noise 0.05."""
    centres = rng.random((k, d))
    x = centres[rng.integers(0, k, n)] + rng.normal(0.0, 0.05, (n, d))
    if sparsity:
        x *= rng.random((n, d)) >= sparsity
    return x


def dprime(x, mu):
    """The distance the protocol compares: ||mu||^2 - 2 x.mu."""
    return (mu ** 2).sum(1)[None, :] - 2.0 * x @ mu.T


def trunc_failures_expected(dps, f: int) -> float:
    """Expected SecureML truncation failures over the lanes of D' tensors
    truncated at scale 2f (one per assignment round)."""
    return float(sum(abs(d).sum() for d in dps)) * 2.0 ** (2 * f - 64)


def allowance(expected: float) -> int:
    return int(math.ceil(expected + 4.0 * math.sqrt(expected) + 3.0))


def reveal_centroids(res, f: int):
    import numpy as np
    from repro.core.sharing import rec
    words = np.asarray(rec(res.centroids), np.uint64)
    return words.astype(np.int64).astype(np.float64) / float(1 << f)


def label_mismatches(got, ref, dp, tol_gap: float) -> tuple:
    """(mismatched rows, of which outside the near-tie band) for labels
    `got` against the argmin labels `ref` of distances `dp`."""
    import numpy as np
    bad = np.flatnonzero(got != ref)
    gap = dp[bad, got[bad]] - dp[bad, ref[bad]]
    return len(bad), int((gap > tol_gap).sum())


def fit_and_check(name, x, sizes, *, sparse: bool, seed: int, f: int,
                  caches) -> tuple:
    """One minibatch vertical fit through SecureKMeans.fit, checked against
    plaintext_kmeans. Returns (KMeansConfig, SecureKMeans, result, line)."""
    import numpy as np
    from repro.core.kmeans import KMeansConfig, SecureKMeans, \
        plaintext_kmeans
    from repro.launch import kmeans_step as K

    k, iters, d_a = sizes["k"], sizes["iters"], sizes["d_a"]
    n, d = x.shape
    cfg = KMeansConfig(k=k, iters=iters, seed=seed, sparse=sparse,
                       offline="streamed", batch_size=sizes["batch"])
    before = caches()
    t0 = time.perf_counter()
    km = SecureKMeans(cfg)
    res = km.fit(x[:, :d_a], x[:, d_a:])
    labels = res.labels_plain()
    wall = time.perf_counter() - t0
    after = caches()
    check(len(after["batch"]) > len(before["batch"])
          and len(after["finalize"]) > len(before["finalize"]),
          f"{name}: the compiled S1/S3-partial/finalize programs did not "
          "run")
    mu = reveal_centroids(res, f)
    check(np.isfinite(mu).all() and mu.shape == (k, d),
          f"{name}: centroids not finite or of shape {mu.shape}")

    # the plaintext trajectory: the centroids each assignment round used
    hist = [plaintext_kmeans(x, k, i, seed=seed)[0] for i in range(iters)]
    mu_ref, lab_ref = plaintext_kmeans(x, k, iters, seed=seed)
    dps = [dprime(x, m) for m in hist]
    # division precision of the update: reciprocal at scale f + s - m
    m_bits = int(np.ceil(np.log2(max(2, n))))
    tol_mu = 4.0 * 2.0 ** -(f + m_bits // 2 - m_bits) * np.abs(x).max() \
        + 2.0 ** -(f - 4)
    tol_gap = 2.0 * (np.abs(x).sum(1).max() + np.abs(mu_ref).sum(1).max()) \
        * tol_mu
    expected = trunc_failures_expected(dps, f)
    n_bad, n_far = label_mismatches(labels, lab_ref, dps[-1], tol_gap)
    check(n_far <= allowance(expected),
          f"{name}: {n_far} labels differ from plaintext_kmeans beyond the "
          f"near-tie band; truncation allows {allowance(expected)}")
    # the revealed centroids are the update of the revealed assignment
    worst = 0.0
    for j in range(k):
        rows = labels == j
        if rows.any():
            worst = max(worst, float(np.abs(mu[j] - x[rows].mean(0)).max()))
    check(worst <= tol_mu, f"{name}: centroid off its cluster mean by "
          f"{worst:.3g} > {tol_mu:.3g}")
    line = (f"n={n} d={d} d_a={d_a} k={k} iters={iters} "
            f"batch={sizes['batch']} sparse={sparse} "
            f"offline=streamed wall_s={wall:.3f} "
            f"label_agreement={1 - n_bad / n:.6f} mismatches={n_bad} "
            f"beyond_near_tie={n_far} allowed={allowance(expected)} "
            f"mu_vs_plain_max={np.abs(mu - mu_ref).max():.3g} "
            f"mu_vs_cluster_mean_max={worst:.3g} tol_mu={tol_mu:.3g} "
            f"batch_programs={len(after['batch'])} "
            f"finalize_programs={len(after['finalize'])}")
    return cfg, km, res, line


def bit_exact_check(sizes, seed: int, caches) -> str:
    """Full-batch fit twice on one seed: on the chip's backend (compiled
    S1/S3 programs) and with backend="numpy" (host ring ops, eager loop).
    Every centroid and assignment share word must be identical."""
    import numpy as np
    import jax.numpy as jnp
    from repro.core.kmeans import KMeansConfig, SecureKMeans

    rng = np.random.default_rng(seed + 1)
    n, d, k = sizes["n_exact"], sizes["d_exact"], sizes["k"]
    x = blobs(rng, n, d, k)
    out = {}
    before = caches()
    t0 = time.perf_counter()
    for backend in ("auto", "numpy"):
        cfg = KMeansConfig(k=k, iters=sizes["iters"], seed=seed,
                           offline="pooled", backend=backend)
        res = SecureKMeans(cfg).fit(x[:, :d // 2], x[:, d // 2:])
        out[backend] = [np.asarray(jnp.asarray(t), np.uint64) for t in
                        (res.centroids.s0, res.centroids.s1,
                         res.assignment.s0, res.assignment.s1)]
    wall = time.perf_counter() - t0
    fit_programs = len(caches()["fit"])
    check(fit_programs > len(before["fit"]),
          "bit-exact: the compiled full-batch S1/S3 programs did not run")
    words = 0
    for a, b in zip(out["auto"], out["numpy"]):
        check(a.shape == b.shape and np.array_equal(a, b),
              "bit-exact: chip shares differ from the numpy reference")
        words += a.size
    return (f"n={n} d={d} k={k} iters={sizes['iters']} "
            f"backends=xla,numpy identical_words={words} "
            f"fit_programs={fit_programs} wall_s={wall:.3f}")


def scoring_check(cfg, km, res, sizes, seed: int, f: int) -> str:
    import numpy as np
    from repro.launch import kmeans_step as K
    from repro.serve import ScoringService

    d, d_a, k = sizes["d"], sizes["d_a"], sizes["k"]
    rng = np.random.default_rng(seed + 2)
    reqs = [blobs(rng, m, d, k) for m in sizes["requests"]]
    svc = ScoringService(km, res, rungs=sizes["rungs"], with_scores=True,
                         d_a=d_a, d_b=d - d_a, provision_copies=4)
    check(svc._compiled(), "scoring: the service would run the eager path")
    t0 = time.perf_counter()
    svc.warm()
    warm = time.perf_counter() - t0
    t0 = time.perf_counter()
    rids = [svc.submit(x[:, :d_a], x[:, d_a:]) for x in reqs]
    resp = {r.request_id: r for r in svc.drain()}
    wall = time.perf_counter() - t0
    check(sorted(resp) == sorted(rids), "scoring: a request went missing")
    errors = [r.error for r in resp.values() if r.error is not None]
    check(not errors, f"scoring: error responses {errors}")
    check(svc.stats.launches > 0, "scoring: no compiled launch ran")

    mu = reveal_centroids(res, f)
    x = np.concatenate(reqs)
    labels = np.concatenate([resp[r].labels for r in rids])
    scores = np.concatenate([resp[r].scores for r in rids])
    check(labels.shape == (x.shape[0],) and np.isfinite(scores).all(),
          "scoring: malformed labels or scores")
    dp = dprime(x, mu)
    tol = 2.0 ** -(f - 2)
    tol_gap = 2.0 * (np.abs(x).sum(1).max() + np.abs(mu).sum(1).max()) * tol
    n_bad, n_far = label_mismatches(labels, dp.argmin(1), dp, tol_gap)
    want = ((x - mu[labels]) ** 2).sum(1)
    off = np.abs(scores - want) > tol_gap + 1e-6 * np.abs(want)
    allowed = allowance(trunc_failures_expected([dp], f))
    check(n_far + int(off.sum()) <= allowed,
          f"scoring: {n_far} labels off the nearest revealed centroid and "
          f"{int(off.sum())} scores off; truncation allows {allowed}")
    return (f"requests={len(reqs)} rows={x.shape[0]} "
            f"rungs={','.join(map(str, sizes['rungs']))} "
            f"launches={svc.stats.launches} errors=0 "
            f"predict_programs={len(K._PREDICT_PROGRAM_CACHE)} "
            f"warm_s={warm:.3f} "
            f"drain_s={wall:.3f} label_mismatches={n_bad} "
            f"beyond_near_tie={n_far} score_off={int(off.sum())} "
            f"allowed={allowed} "
            f"score_err_max={np.abs(scores - want).max():.3g}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run every phase at a tiny size on whatever "
                         "device JAX has; still exits non-zero with no "
                         "result when that is not a TPU")
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print("chip_smoke: FAIL: no src/repro next to this script; run it "
              "from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    from repro.launch.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()

    import jax
    import numpy as np
    from repro.core import backend as B
    from repro.core import ring
    from repro.launch import kmeans_step as K

    devices = jax.devices()
    dev = devices[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.cpu_rehearsal:
        print(f"chip_smoke: FAIL: JAX found no TPU (default device "
              f"{dev.platform}:{dev.device_kind})", file=sys.stderr)
        return 1
    sizes = TINY if args.cpu_rehearsal else CHIP
    compiles = [0]
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _s, **_kw: compiles.__setitem__(
            0, compiles[0] + (event == "/jax/core/compile/backend_compile_"
                                       "duration")))

    def caches():
        return {"fit": dict(K._PROGRAM_CACHE),
                "batch": dict(K._BATCH_PROGRAM_CACHE),
                "finalize": dict(K._FINALIZE_CACHE),
                "predict": dict(K._PREDICT_PROGRAM_CACHE)}

    def phase(name, fn):
        c0, t0 = compiles[0], time.perf_counter()
        line = fn()
        print(f"phase={name} backend={backend} {line} "
              f"compiles={compiles[0] - c0} "
              f"phase_s={time.perf_counter() - t0:.3f}", flush=True)

    backend = B.get_backend("auto").name
    print(f"device platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} backend={backend} cache={cache_dir}",
          flush=True)
    check(backend == "xla", f"auto resolved to {backend}, not xla")
    f = ring.F
    rng = np.random.default_rng(args.seed)
    x_dense = blobs(rng, sizes["n_dense"], sizes["d"], sizes["k"])
    x_sparse = blobs(rng, sizes["n_sparse"], sizes["d"], sizes["k"],
                     sparsity=sizes["sparsity"])
    model = {}

    def phase_a():
        cfg, km, res, line = fit_and_check("a", x_dense, sizes,
                                           sparse=False, seed=args.seed,
                                           f=f, caches=caches)
        model.update(cfg=cfg, km=km, res=res)
        return line

    phase("a_dense_fit", phase_a)
    phase("a_bit_exact", lambda: bit_exact_check(sizes, args.seed, caches))
    phase("b_sparse_fit", lambda: fit_and_check(
        "b", x_sparse, sizes, sparse=True, seed=args.seed, f=f,
        caches=caches)[3])
    phase("c_scoring", lambda: scoring_check(
        model["cfg"], model["km"], model["res"], sizes, args.seed, f))
    check("pallas" not in B._INSTANCES,
          "a pallas backend was built: its kernels run in interpret mode")
    print("no eager fallback and no pallas backend: every phase ran the "
          "compiled programs on the xla backend", flush=True)
    if not on_tpu:
        print("chip_smoke: rehearsal passed on "
              f"{dev.platform}; no TPU, so no result", file=sys.stderr)
        return 3
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr, flush=True)
        sys.exit(1)
