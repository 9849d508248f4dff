"""Traffic kind `fit_stream`: whole secure fits back to back.

Set-up makes the cell's data from the seed and runs one whole fit, which
compiles every program (or loads it from the cache) and fills the plan
caches. The window then
runs fits through `SecureKMeans.fit` on the same data until `--seconds`
have passed; no fit starts after that. Fit i is seeded by
`data.derived_seed(seed, "fit", i)`, so its initial rows, triples and masks
differ from fit to fit while its work does not.

End-to-end numbers:
  fit_rows_per_s                 sum of n * iterations over the summed wall
                                 of the window's fits, each from `fit`
                                 entry to its centroid and assignment
                                 shares being ready
  fit_online_bytes_per_row_iter  the fits' online protocol bytes over the
                                 same row-iterations

The check compares every fit of the window with `reference.lloyd` from the
same initial rows (`fit_numbers`).
"""
from __future__ import annotations

import time

import numpy as np

from bench import data, reference, work


def reveal(shares, f: int) -> np.ndarray:
    """The plain values of (s0, s1) ring shares at scale 2^-f."""
    s0, s1 = (np.asarray(s, np.uint64) for s in shares)
    return (s0 + s1).astype(np.int64).astype(np.float64) / float(1 << f)


def fit_numbers(mu, ref_mu) -> dict:
    """The number the check compares for one fit.

    centroid_err  worst |centroid - the reference's centroid| over every
                  cluster and feature. The reference runs plain Lloyd from
                  the same initial rows, so a row put in the wrong cluster
                  and a wrong update both show. The protocol's own SecureML
                  truncation failures (probability about |x| / 2^64 per
                  truncated lane) put about one row in 10^5 in a farther
                  cluster, which moves a centroid by about 1 / (rows in
                  the cluster); rounding at f=20 moves it by less.
    """
    return {"centroid_err": float(np.abs(mu - ref_mu).max())}


class Driver:
    def __init__(self, cfg: dict, traffic: dict, seed: int, seconds: float,
                 *, log, annotate):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.seconds = float(seconds)
        self.log, self.annotate = log, annotate
        self.fits: list = []
        self.failures: list = []

    # -- set-up -----------------------------------------------------------
    def _config(self, fit_seed: int):
        from repro.core.kmeans import KMeansConfig
        c = self.cfg
        return KMeansConfig(k=c["k"], iters=c["iters"], seed=fit_seed,
                            partition=c["partition"], sparse=c["sparse"],
                            offline=c["offline"],
                            batch_size=c["batch_size"],
                            pipeline=c["pipeline"])

    def setup(self) -> None:
        c = self.cfg
        rng = data.rng_for(self.seed, "data")
        self.x = data.blobs(rng, data.centres(rng, c["k"], c["d"]), c["n"],
                            c["noise"], c["sparsity"], c.get("onehot_groups"))
        self.x_a = np.ascontiguousarray(self.x[:, :c["d_a"]])
        self.x_b = np.ascontiguousarray(self.x[:, c["d_a"]:])
        t0 = time.monotonic()
        self._fit(data.derived_seed(self.seed, "warmup", 0))
        self.log(f"warmup fit wall_s={time.monotonic() - t0:.3f}")

    def _fit(self, fit_seed: int):
        from repro.core.kmeans import SecureKMeans
        res = SecureKMeans(self._config(fit_seed)).fit(self.x_a, self.x_b)
        for a in (res.centroids.s0, res.centroids.s1,
                  res.assignment.s0, res.assignment.s1):
            a.block_until_ready()
        return res

    # -- the measured window -----------------------------------------------
    def window(self) -> None:
        t_end = time.monotonic() + self.seconds
        i = 0
        while time.monotonic() < t_end:
            fit_seed = data.derived_seed(self.seed, "fit", i)
            t0 = time.monotonic()
            try:
                with self.annotate("bench.fit"):
                    res = self._fit(fit_seed)
            except Exception as e:               # noqa: BLE001 — counted
                self.failures.append(f"{type(e).__name__}: {e}")
                self.log(f"fit {i} failed: {e!r}")
                break
            wall = time.monotonic() - t0
            d = res.dealer
            self.fits.append({
                "seed": fit_seed, "wall_s": wall,
                "row_iters": self.cfg["n"] * res.iters_run,
                "online_bytes": res.log.total_bytes("online"),
                "dealer_wait_s": float(getattr(d, "wait_seconds", 0.0)),
                "dealer_gen_s": float(getattr(d, "gen_seconds", 0.0)),
                "result": res})
            self.log(f"fit {i} wall_s={wall:.3f} "
                     f"dealer_wait_s={self.fits[-1]['dealer_wait_s']:.3f}")
            i += 1

    def finish(self) -> None:
        """Reveal the window's answers to the host and drop the program's
        device state; the check then runs on the host alone."""
        f = self.cfg["frac_bits"]
        for fit in self.fits:
            res = fit.pop("result")
            fit["mu"] = reveal((res.centroids.s0, res.centroids.s1), f)

    # -- numbers -------------------------------------------------------------
    def end_to_end(self) -> dict:
        if not self.fits:
            return {}
        row_iters = sum(f["row_iters"] for f in self.fits)
        wall = sum(f["wall_s"] for f in self.fits)
        return {"fit_rows_per_s": row_iters / wall,
                "fit_online_bytes_per_row_iter":
                    sum(f["online_bytes"] for f in self.fits) / row_iters}

    def _batch_programs(self):
        from repro.launch import kmeans_step as K
        c = self.cfg
        rows = sorted({min(c["batch_size"], c["n"] - lo)
                       for lo in range(0, c["n"], c["batch_size"])})
        progs = {r: K.fit_batch_programs(c["partition"], c["sparse"],
                                         (r, c["d_a"]),
                                         (r, c["d"] - c["d_a"]), c["k"])
                 for r in rows}
        fin = K.finalize_program(c["k"], c["d"], c["n"])
        return progs, fin

    def counters(self) -> dict:
        c = self.cfg
        progs, fin = self._batch_programs()
        per_iter = work.triple_bytes(fin.requests)
        for lo in range(0, c["n"], c["batch_size"]):
            p = progs[min(c["batch_size"], c["n"] - lo)]
            per_iter += work.triple_bytes(p.s1_requests) \
                + work.triple_bytes(p.s3p_requests)
        return {
            "fits_wall_s": sum(f["wall_s"] for f in self.fits),
            "dealer_wait_s": sum(f["dealer_wait_s"] for f in self.fits),
            "dealer_gen_s": sum(f["dealer_gen_s"] for f in self.fits),
            "triple_bytes_per_row_iter": per_iter / c["n"],
        }

    def hlo_texts(self) -> list:
        progs, fin = self._batch_programs()
        out = [fin.fn.as_text()]
        for p in progs.values():
            out += [p.s1.as_text(), p.s3p.as_text()]
        return out

    # -- the check ---------------------------------------------------------
    def check(self, control: bool = False):
        """(attempted, failed, {number: worst value over the fits})."""
        c = self.cfg
        worst = {"centroid_err": 0.0}
        for fit in self.fits:
            init = reference.init_rows(fit["seed"], c["n"], c["k"])
            _hist, ref_mu, _labels = reference.lloyd(self.x, init,
                                                     c["iters"])
            mu = fit["mu"]
            if control:
                _h, mu, _l = reference.lloyd_fixed(self.x, init, c["iters"])
            nums = fit_numbers(mu, ref_mu)
            for k_, v in nums.items():
                worst[k_] = max(worst[k_], v)
            self.log(f"check fit seed={fit['seed']} " + " ".join(
                f"{k_}={v:.6g}" for k_, v in nums.items()))
        return len(self.fits) + len(self.failures), len(self.failures), \
            worst
