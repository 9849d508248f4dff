"""Offline cold-start suite: fit-plan bank, batched HE exchange, workers.

Measures the three legs of the PR that kills the offline cold start, per
partition x sparsity combo:

* **cold / warm / provisioned fit offline** — `offline_cold_s` is a
  first-of-its-shape pooled fit's offline wall (plan trace + bulk dealer
  generation + S1/S3 AOT compile); `offline_warm_s` a second identical fit
  (caches hot, generation still online-adjacent); `offline_provisioned_s`
  a fit served from a pre-provisioned fit-plan `TripleBank` — the fit-time
  offline work collapses to the plan lookup because ALL generation moved
  to `provision()` (whose wall is reported separately as the true offline
  cost, serial and 2-worker). All three fits are bit-exact (asserted).

* **HE exchange accounting** — modelled OU-2048 seconds of one Protocol-2
  exchange on the combo's own geometry, column-batched vs the legacy
  per-ciphertext loop (whose n*k `ct + int` mask additions are priced
  honestly as encryptions). The batched/legacy ratio is the sparse `he_s`
  headline.

* **real-Paillier wall** — measured wall-clock of the batched vs legacy
  exchange paths on a real 512-bit Paillier key (small geometry; bigint
  exponentiation, so minutes not microseconds at paper scale).

* **provisioning workers** — wall of `provision(workers=1)` vs
  `workers=2/4`. NOTE: this host may be single-core (the JSON records
  `cpu_count`); thread-pool scaling is only observable with >= 2 cores,
  the bit-exactness of the parallel split is what the tests enforce.

Writes benchmarks/BENCH_offline.json. Reference config (full): n=1024,
k=8, d=32, 3 iterations; --quick drops to n=256 (the CI smoke:
`python -m benchmarks.run --only offline --quick`).
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

from benchmarks.common import make_blobs
from repro.core import protocol as P
from repro.core.backend import get_backend
from repro.core.he import OU_COST_S, Paillier, SimulatedPHE
from repro.core.kmeans import KMeansConfig, SecureKMeans
from repro.core.sparse import (CSRMatrix, default_value_bits, he2ss_layout,
                               he2ss_op_counts, secure_sparse_matmul)
from repro.core.triples import TripleBank

BENCH_PATH = os.path.join(os.path.dirname(__file__), "BENCH_offline.json")

COMBOS = (("vertical", False), ("vertical", True),
          ("horizontal", False), ("horizontal", True))


def _split(x, partition):
    n, d = x.shape
    if partition == "vertical":
        return x[:, :d // 2], x[:, d // 2:]
    return x[:n // 2], x[n // 2:]


def _assert_bit_exact(r0, r1):
    np.testing.assert_array_equal(np.asarray(r0.centroids.s0, np.uint64),
                                  np.asarray(r1.centroids.s0, np.uint64))
    np.testing.assert_array_equal(np.asarray(r0.assignment.s1, np.uint64),
                                  np.asarray(r1.assignment.s1, np.uint64))


def _legacy_he_seconds(n, d, k, nnz, nrows_ne):
    """Modelled OU time of the per-ciphertext loop: d*k forward encrypts,
    nnz*k scalar pmuls, (nnz-rows)*k + n*k adds, n*k mask encryptions (the
    step-3 `ct + int` re-randomization the old accounting hid) and n*k
    decrypts."""
    return ((d * k + n * k) * OU_COST_S["enc"]
            + nnz * k * OU_COST_S["pmul"]
            + ((nnz - nrows_ne) * k + n * k) * OU_COST_S["add"]
            + n * k * OU_COST_S["dec"])


def _he_model_row(x_csr, k):
    n, d = x_csr.shape
    nrows_ne = int(np.count_nonzero(np.diff(x_csr.indptr)))
    lay = he2ss_layout(k, SimulatedPHE().plain_bits, default_value_bits(d))
    ops = he2ss_op_counts(n, d, x_csr.nnz, nrows_ne, lay)
    batched = sum(ops[o] * OU_COST_S[o] for o in OU_COST_S)
    legacy = _legacy_he_seconds(n, d, k, x_csr.nnz, nrows_ne)
    return {"he_batched_model_s": round(batched, 4),
            "he_legacy_model_s": round(legacy, 4),
            "he_model_speedup": round(legacy / max(batched, 1e-12), 2)}


def _combo_row(partition, sparse, n, k, d, iters):
    x = make_blobs(n, d, k, seed=4, sparse_frac=0.8 if sparse else 0.0)
    a, b = _split(x, partition)
    base = dict(k=k, iters=iters, seed=3, backend="auto",
                partition=partition, sparse=sparse)

    # cold: first-of-its-shape fit pays trace + bulk gen + AOT compile
    cold = SecureKMeans(KMeansConfig(**base, offline="pooled")).fit(a, b)
    # warm: identical fit, plan/program caches hot — generation remains
    warm = SecureKMeans(KMeansConfig(**base, offline="pooled")).fit(a, b)

    # provisioned: ALL generation happens in provision() (the true offline
    # phase); the fit itself starts with a full bank
    km = SecureKMeans(KMeansConfig(**base, offline="pooled"))
    t0 = time.perf_counter()
    key, plan, _ = km.plan_fit(a.shape, b.shape)
    plan_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    bank = TripleBank(seed=3)
    bank.provision(key, plan)
    provision_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    bank2 = TripleBank(seed=3)
    bank2.provision(key, plan, workers=2)
    provision_2w_s = time.perf_counter() - t0
    prov = km.fit(a, b, dealer=bank.dealer(key))
    _assert_bit_exact(warm, prov)
    _assert_bit_exact(cold, prov)

    row = {
        "partition": partition, "sparse": sparse,
        "n": n, "k": k, "d": d, "iters": iters,
        "backend": get_backend("auto").name,
        "offline_cold_s": round(cold.offline_dealer_seconds, 4),
        "offline_warm_s": round(warm.offline_dealer_seconds, 4),
        "plan_fit_s": round(plan_s, 4),
        "provision_serial_s": round(provision_s, 4),
        "provision_2workers_s": round(provision_2w_s, 4),
        "offline_provisioned_s": round(
            prov.offline_dealer_seconds + prov.offline_plan_seconds, 4),
        "provisioned_vs_cold": round(
            (prov.offline_dealer_seconds + prov.offline_plan_seconds)
            / max(cold.offline_dealer_seconds, 1e-9), 4),
        "online_s": round(prov.online_seconds, 4),
        "he_s": round(prov.he_seconds, 4),
    }
    if sparse:
        # one Protocol-2 exchange on this combo's own forward geometry
        row.update(_he_model_row(CSRMatrix.from_dense_real(a), k))
    return row


def _paillier_wall_row():
    """Measured batched vs legacy wall on a real 512-bit key (shares are
    asserted identical, so the speedup is pure exchange mechanics)."""
    rng = np.random.default_rng(17)
    n, d, k = 24, 16, 4
    xr = rng.uniform(-2, 2, (n, d)) * (rng.random((n, d)) > 0.7)
    x = CSRMatrix.from_dense_real(xr)
    yb = rng.integers(0, 1 << 63, (d, k)).astype(np.uint64)
    he = Paillier(512)
    t0 = time.perf_counter()
    zb = secure_sparse_matmul(P.make_ctx(5), x, yb, he, batched=True)
    batched_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    zl = secure_sparse_matmul(P.make_ctx(5), x, yb, he, batched=False)
    legacy_s = time.perf_counter() - t0
    np.testing.assert_array_equal(np.asarray(zb.s0), np.asarray(zl.s0))
    return {"n": n, "d": d, "k": k, "key_bits": 512,
            "nnz": int(x.nnz),
            "paillier_batched_s": round(batched_s, 3),
            "paillier_legacy_s": round(legacy_s, 3),
            "paillier_speedup": round(legacy_s / max(batched_s, 1e-9), 2)}


def _worker_scaling_row(n, k, d):
    x = make_blobs(n, d, k, seed=4, sparse_frac=0.8)
    a, b = _split(x, "vertical")
    km = SecureKMeans(KMeansConfig(k=k, iters=3, seed=3, sparse=True,
                                   backend="auto", offline="pooled"))
    key, plan, _ = km.plan_fit(a.shape, b.shape)
    TripleBank(seed=3).provision(key, plan)   # warmup: dispatch caches etc.
    walls = {}
    for w in (1, 2, 4):
        t0 = time.perf_counter()
        bank = TripleBank(seed=3)
        bank.provision(key, plan, copies=2, workers=w)
        walls[w] = time.perf_counter() - t0
    return {"plan_requests": len(plan), "copies": 2,
            "cpu_count": os.cpu_count(),
            "provision_1w_s": round(walls[1], 4),
            "provision_2w_s": round(walls[2], 4),
            "provision_4w_s": round(walls[4], 4),
            "scaling_2w": round(walls[1] / max(walls[2], 1e-9), 2),
            "note": "even on one core (cpu_count=1) workers overlap "
                    "GIL-released buffer copies with python-side draw "
                    "bookkeeping, so >1x is real; full linear scaling "
                    "needs >= 2 cores. Bit-exactness of the parallel "
                    "split is test-enforced (tests/test_offline_bank.py)"}


def run(quick: bool = False):
    n, k, d, iters = (256, 4, 16, 2) if quick else (1024, 8, 32, 3)
    rows = [_combo_row(part, sp, n, k, d, iters) for part, sp in COMBOS]
    he_row = _paillier_wall_row()
    worker_row = _worker_scaling_row(n, k, d)
    with open(BENCH_PATH, "w") as f:
        json.dump({"rows": rows, "paillier_wall": he_row,
                   "worker_scaling": worker_row,
                   "note": "offline_cold_s = plan trace + bulk gen + AOT "
                           "compile on a first-of-its-shape pooled fit; "
                           "offline_provisioned_s = fit-time offline work "
                           "when the fit is served from a pre-provisioned "
                           "fit-plan TripleBank (generation moved to "
                           "provision_serial_s, the true offline wall). "
                           "All fits bit-exact, same seed. he_*_model_s "
                           "price ONE Protocol-2 exchange on the combo's "
                           "forward geometry under OU-2048 costs; the "
                           "legacy model now counts the loop's hidden "
                           "per-cell mask encryptions."},
                  f, indent=1)
    return rows + [he_row, worker_row]


def derived(rows):
    """Headline: worst provisioned-fit offline fraction of the cold fit
    (acceptance: <= 0.1), and the worst sparse HE model speedup."""
    combo = [r for r in rows if "provisioned_vs_cold" in r]
    he = [r["he_model_speedup"] for r in rows if "he_model_speedup" in r]
    worst = max(r["provisioned_vs_cold"] for r in combo)
    return f"prov/cold<={worst}; he_speedup>={min(he) if he else 'n/a'}"
