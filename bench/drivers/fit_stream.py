"""Traffic kind `fit_stream`: whole secure fits back to back.

Set-up makes the cell's data from the seed and runs one whole fit, which
compiles every program (or loads it from the cache) and fills the plan
caches. The window then
runs fits through `SecureKMeans.fit` on the same data until `--seconds`
have passed; no fit starts after that. Fit i is seeded by
`data.derived_seed(seed, "fit", i)`, so its initial rows, triples and masks
differ from fit to fit while its work does not.

The parties' inputs are cut from one seeded matrix x as the configuration's
partition states: vertical, party A holds the columns x[:, :d_a] and party
B the rest; horizontal, party A holds the rows x[:n_a] and party B the
rows x[n_a:], every feature of each. The program draws a horizontal fit's
initial rows from the two parties' rows stacked [A rows; B rows], which is
x itself, so `reference.init_rows` over x names the same rows in either
partition and the check is the same for both.

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


def column_blocks(cfg: dict) -> list:
    """[(lo, hi)] of each party's columns: vertical, party A's first d_a
    features and party B's rest; horizontal, both parties hold every
    feature, one block."""
    if cfg["partition"] == "vertical":
        return [(0, cfg["d_a"]), (cfg["d_a"], cfg["d"])]
    return [(0, cfg["d"])]


def fit_numbers(mu, ref_mu, blocks) -> dict:
    """The number the check compares for one fit.

    centroid_median_err  the largest, over clusters and the parties' column
                  blocks, of the median over the block's features of
                  |centroid - the reference's centroid|. The reference
                  runs plain Lloyd from the same initial rows. A wrong
                  update, rows left out of it or many rows in the wrong
                  cluster move every feature of a centroid; an error in
                  one party's columns shows in that party's block; the
                  control's fixed point errs on every feature. The median
                  is steady where the largest error is not: a SecureML
                  truncation failure (about one row in 10^5) puts a row
                  in a farther cluster and moves each feature of its
                  centroids by |x_j - mu_j| / (rows in the cluster), which
                  in a cluster of a few thousand rows takes the largest
                  feature's error near the control's; a failed lane of the
                  update errs on one element alone.
    """
    err = np.abs(mu - ref_mu)
    return {"centroid_median_err": float(max(
        np.median(err[:, lo:hi], axis=1).max() for lo, hi in blocks))}


def batch_rows(cfg: dict) -> list:
    """[(party A's rows, party B's rows)] of each minibatch, in the order a
    fit runs them.

    Vertical: both parties hold columns of the same rows, cut into shared
    chunks of `batch_size` rows, the last one shorter. Horizontal: each
    party's rows are cut into the same number of batches,
    B = max(1, min(ceil(n / batch_size), n_a, n_b)), contiguous and as near
    equal as can be (the first n_p mod B of party p's chunks one row
    longer), so each batch holds a chunk of each party's rows."""
    n, bs = cfg["n"], cfg["batch_size"]
    if cfg["partition"] == "vertical":
        return [(min(bs, n - lo),) * 2 for lo in range(0, n, bs)]
    n_a = cfg["n_a"]
    n_b = n - n_a
    b = max(1, min(-(-n // bs), n_a, n_b))
    return [(n_a // b + (i < n_a % b), n_b // b + (i < n_b % b))
            for i in range(b)]


def batch_shapes(cfg: dict) -> list:
    """[(party A's (rows, features), party B's)] of each minibatch, as the
    fit's batch programs take them."""
    d = cfg["d"]
    if cfg["partition"] == "vertical":
        return [((r, cfg["d_a"]), (r, d - cfg["d_a"]))
                for r, _ in batch_rows(cfg)]
    return [((ra, d), (rb, d)) for ra, rb in batch_rows(cfg)]


# the window's fits whose shapes set-up warms: far more than a window holds
WARM_FITS = 10_000


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
        if c["partition"] == "vertical":
            self.x_a = np.ascontiguousarray(self.x[:, :c["d_a"]])
            self.x_b = np.ascontiguousarray(self.x[:, c["d_a"]:])
        else:
            self.x_a = np.ascontiguousarray(self.x[:c["n_a"]])
            self.x_b = np.ascontiguousarray(self.x[c["n_a"]:])
        for fit_seed in self.warmup_seeds():
            t0 = time.monotonic()
            self._fit(fit_seed)
            self.log(f"warmup fit wall_s={time.monotonic() - t0:.3f}")

    def warmup_seeds(self) -> list:
        """The seeds of set-up's warm-up fits: one fit, and in a horizontal
        fit one more for each split of the initial rows between the parties
        (how many of them lie among party A's n_a) that the first
        WARM_FITS fits of the window take and the warm-up has not. The
        program puts each party's initial rows together in a small program
        whose shape is that split, so a split not warmed would compile
        inside the window."""
        c = self.cfg
        seeds = [data.derived_seed(self.seed, "warmup", 0)]
        if c["partition"] == "vertical":
            return seeds

        def split(fit_seed):
            init = reference.init_rows(fit_seed, c["n"], c["k"])
            return int((init < c["n_a"]).sum())
        possible = set(range(max(0, c["k"] - (c["n"] - c["n_a"])),
                             min(c["k"], c["n_a"]) + 1))
        splits = {split(seeds[0])}
        for i in range(WARM_FITS):
            if splits == possible:
                break
            fit_seed = data.derived_seed(self.seed, "fit", i)
            if (s := split(fit_seed)) not in splits:
                splits.add(s)
                seeds.append(fit_seed)
        return seeds

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
        """({(shape_a, shape_b): programs} of the batch geometries a fit
        runs, the batches' shapes in the order the fit runs them, and the
        finalize program)."""
        from repro.launch import kmeans_step as K
        c = self.cfg
        shapes = batch_shapes(c)
        progs = {s: K.fit_batch_programs(c["partition"], c["sparse"], *s,
                                         c["k"])
                 for s in sorted(set(shapes))}
        fin = K.finalize_program(c["k"], c["d"], c["n"])
        return progs, shapes, fin

    def counters(self) -> dict:
        progs, shapes, fin = self._batch_programs()
        per_iter = work.triple_bytes(fin.requests)
        for s in shapes:
            per_iter += work.triple_bytes(progs[s].s1_requests) \
                + work.triple_bytes(progs[s].s3p_requests)
        return {
            "fits_wall_s": sum(f["wall_s"] for f in self.fits),
            "dealer_wait_s": sum(f["dealer_wait_s"] for f in self.fits),
            "dealer_gen_s": sum(f["dealer_gen_s"] for f in self.fits),
            "triple_bytes_per_row_iter": per_iter / self.cfg["n"],
        }

    def hlo_texts(self) -> list:
        progs, _shapes, fin = self._batch_programs()
        out = [fin.fn.as_text()]
        for p in progs.values():
            out += [p.s1.as_text(), p.s3p.as_text()]
        return out

    # -- the check ---------------------------------------------------------
    def check(self, control: bool = False):
        """(attempted, failed, {number: worst value over the fits})."""
        c = self.cfg
        blocks = column_blocks(c)
        worst = {"centroid_median_err": 0.0}
        for fit in self.fits:
            init = reference.init_rows(fit["seed"], c["n"], c["k"])
            _hist, ref_mu, labels = reference.lloyd(self.x, init,
                                                    c["iters"])
            mu = fit["mu"]
            if control:
                _h, mu, _l = reference.lloyd_fixed(self.x, init, c["iters"])
            nums = fit_numbers(mu, ref_mu, blocks)
            for k_, v in nums.items():
                worst[k_] = max(worst[k_], v)
            # the largest single error and the clusters' sizes are logged
            # beside it, not compared
            self.log(f"check fit seed={fit['seed']} " + " ".join(
                f"{k_}={v:.6g}" for k_, v in nums.items())
                + f" centroid_err={np.abs(mu - ref_mu).max():.6g} rows="
                + ",".join(map(str, np.bincount(labels, minlength=c["k"]))))
        return len(self.fits) + len(self.failures), len(self.failures), \
            worst
