"""Privacy-preserving (sparse) K-means — paper Algorithm 3, both partitions.

Secure Lloyd iteration (Sec 4.2):
  S1 F_ESD  — vectorized distances  D' = U - 2 X mu^T  (Eq. 3-5); the
              ||X_i||^2 term is dropped (constant per row under argmin) and
              U is computed once per iteration with ONE batched SMUL.
  S2 F_min  — tournament argmin over k (Fig. 1), vectorized over all n.
  S3 F_SCU  — mu = C^T X / 1^T C with Newton-Raphson secure division and a
              secure empty-cluster guard (CMP + MUX keep the old centroid).
  F_CSC     — secure convergence check, only the stop bit is revealed.

Vertical:   X = [X_A | X_B]   (Eq. 4, Alg. 3)      n x (dA + dB)
Horizontal: X = [X_A ; X_B]   (Eq. 5)              (nA + nB) x d

`sparse=True` swaps every joint public-x-share product for Protocol 2
(HE + HE2SS, core/sparse.py) — X never leaves its owner, traffic is
independent of nnz and of the big n*d dimension.

`vectorized=False` keeps results identical but *accounts* communication the
way the pre-vectorization protocol would ship it (one interaction per scalar
product / per comparison — "the total number of interactions in each
iteration is nk", Sec 4.2). This is the Fig. 3 baseline and the M-Kmeans
cost proxy; wall-clock on a real WAN is dominated by rounds x RTT which the
NetModel turns into Fig. 3's curves.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import faultpoints as _fp
from repro.core import protocol as P
from repro.core import ring
from repro.core.channel import CommLog, NetModel
from repro.core.he import OU_COST_S, SimulatedPHE
from repro.core.sharing import AShare, rec, rec_real, share, share_np
from repro.core.sparse import CSRMatrix, secure_sparse_matmul
from repro.core.triples import (BankSlotDealer, PlanningDealer, PooledDealer,
                                SlotDealer, StreamingPooledDealer, TriplePlan,
                                TrustedDealer, serve_seed)
from repro.obs import metrics as _metrics
from repro.obs import trace as _trace


def _h_iter_seconds():
    return _metrics.get_registry().histogram(
        "repro_fit_iteration_seconds",
        buckets=_metrics.log_buckets(1e-3, 100.0))


@dataclasses.dataclass
class KMeansConfig:
    k: int
    iters: int = 10
    partition: Literal["vertical", "horizontal"] = "vertical"
    sparse: bool = False
    vectorized: bool = True
    f: int = ring.F
    seed: int = 0
    init: Literal["random_data", "random_uniform"] = "random_data"
    tol: float | None = None        # if set, F_CSC early-stops
    he_backend: object | None = None  # default: SimulatedPHE()
    backend: str = "auto"           # ring-compute backend (core/backend.py)
    # "pooled": derive the data-independent triple schedule up front and run
    # the online loop against a PooledDealer (the paper's true offline/online
    # split). "streamed": same split, but each iteration's pool tranche is
    # generated on a background worker while the previous iteration runs —
    # peak pool residency is O(1 iteration) instead of O(iters).
    # "on_demand": synthesize triples inside the loop (baseline).
    offline: Literal["on_demand", "pooled", "streamed"] = "on_demand"
    # Minibatch Lloyd: each iteration is still one full pass over the data,
    # but processed as ceil(n / batch_size)-row batches whose S3 partial
    # sums accumulate in secret-shared running-sum/count accumulators —
    # peak launch/pool memory becomes O(batch), and the per-batch host
    # exchanges can overlap device launches (`pipeline`). None = full batch
    # (the unchanged single-pass path). batch_size >= n is bit-exact with
    # the full-batch pooled fast path. Requires offline="pooled"/"streamed"
    # and a compilable config (vectorized, f=ring.F, traceable backend).
    batch_size: int | None = None
    # With batch_size set: run batch t+1's Protocol-2 exchange + tranche pin
    # on the host while batch t's S1 launch is on device (launch/pipeline).
    # pipeline=False is the stream-identical sequential escape hatch — same
    # shares, same CommLog, same dealer words.
    pipeline: bool = True

    def __post_init__(self):
        if self.iters < 1:
            raise ValueError(
                f"KMeansConfig.iters must be >= 1, got {self.iters}: the "
                "secure Lloyd loop must run at least once to produce an "
                "assignment")
        if self.offline not in ("on_demand", "pooled", "streamed"):
            raise ValueError(
                f"KMeansConfig.offline must be 'on_demand', 'pooled' or "
                f"'streamed', got {self.offline!r}")
        if self.batch_size is not None and self.batch_size < 1:
            raise ValueError(
                f"KMeansConfig.batch_size must be None (full batch) or "
                f">= 1, got {self.batch_size}")


@dataclasses.dataclass
class KMeansResult:
    centroids: AShare                 # (k, d) shares, scale f
    assignment: AShare                # (n, k) one-hot shares, scale 1
    iters_run: int
    log: CommLog
    dealer: "TrustedDealer | PooledDealer | StreamingPooledDealer | SlotDealer"
    online_seconds: float             # loop wall minus in-loop dealer work
    offline_dealer_seconds: float     # triple synthesis (+ plan, if pooled)
    offline_modelled_ot_seconds: float
    he_seconds: float
    loop_seconds: float = 0.0         # raw Lloyd-loop wall-clock: with an
    # on-demand dealer this INCLUDES triple synthesis (no preprocessing means
    # the dealer sits on the online critical path); with offline="pooled" it
    # equals online_seconds.
    offline_plan_seconds: float = 0.0  # dry-run trace + fast-path AOT
    # compile (pooled only; the compile usually dominates)

    # -- convenience reconstructions (the protocol's single final Rec) -----
    def centroids_plain(self, f: int = ring.F) -> np.ndarray:
        return np.asarray(rec_real(self.centroids, f))

    def labels_plain(self) -> np.ndarray:
        oh = np.asarray(rec(self.assignment), np.uint64).astype(np.int64)
        return oh.argmax(1)

    def wan_lan_estimate(self, net: NetModel) -> dict:
        online = self.log.time_estimate(net, "online") + self.online_seconds \
            + self.he_seconds
        offline = self.log.time_estimate(net, "offline") \
            + self.offline_modelled_ot_seconds
        return {"online_s": online, "offline_s": offline,
                "total_s": online + offline}


@dataclasses.dataclass
class PredictResult:
    """One secure-scoring batch against a fitted model. Only the shares are
    held; the final Rec happens in `labels_plain` / `scores_plain` — the
    protocol's single reveal point, matching the paper's "nothing but the
    output" contract (centroids are never reconstructed)."""

    assignment: AShare                # (m, k) one-hot shares, scale 1
    scores: AShare | None             # (m,) ||x - mu_c||^2 shares, scale f
    log: CommLog
    seconds: float
    f: int = ring.F

    def labels_plain(self) -> np.ndarray:
        oh = np.asarray(rec(self.assignment), np.uint64).astype(np.int64)
        return oh.argmax(1)

    def scores_plain(self) -> np.ndarray:
        if self.scores is None:
            raise ValueError("assignments-only predict holds no scores; "
                             "use SecureKMeans.score")
        return np.asarray(ring.decode(rec(self.scores), self.f))


@dataclasses.dataclass
class PreparedPredict:
    """Host-phase output of one compiled scoring launch
    (`SecureKMeans.predict_prepare`): everything `predict_launch` needs to
    dispatch and `predict_collect` needs to finish. Produced on the main
    thread; the pipelined serving loop prepares request t+1 while request
    t's launch is on device."""

    prog: object                      # launch.kmeans_step.PredictProgram
    args: tuple                       # staged program inputs (device-ready)
    log: CommLog                      # the request's live log
    comm: CommLog                     # traced per-launch traffic to replay
    with_scores: bool
    x_a: np.ndarray                   # plaintext slices (for ||x||^2)
    x_b: np.ndarray
    t0: float


# (shapes, cfg-key) -> (one-iteration TriplePlan, one-iteration CommLog).
# The schedule is data-independent, so identical-shape fits share it; see
# SecureKMeans._plan_offline_iter.
_PLAN_CACHE: dict[tuple, tuple] = {}

# predict-plan cache: (shapes, with_scores, cfg-key) -> (TriplePlan,
# CommLog) of ONE scoring launch. The key doubles as the TripleBank lookup
# key — a bank provisioned under it serves any number of same-geometry
# requests across fits.
_PREDICT_PLAN_CACHE: dict[tuple, tuple] = {}


def clear_plan_cache() -> None:
    _PLAN_CACHE.clear()
    _PREDICT_PLAN_CACHE.clear()


class SecureKMeans:
    """Two-party secure K-means. Party data stays plaintext at its owner;
    centroids/assignments are secret-shared end to end."""

    def __init__(self, cfg: KMeansConfig):
        self.cfg = cfg
        self.he = cfg.he_backend or SimulatedPHE()

    # ------------------------------------------------------------------ #
    def fit(self, x_a: np.ndarray, x_b: np.ndarray, *,
            dealer=None, wire=None, checkpoint=None,
            resume: bool = False,
            resume_step: int | None = None) -> KMeansResult:
        with _trace.span("fit", rows=int(np.asarray(x_a).shape[0]),
                         k=self.cfg.k, iters=self.cfg.iters,
                         sparse=self.cfg.sparse,
                         wired=wire is not None), \
                contextlib.ExitStack() as prologue:
            # fit.prologue runs from here to the loop; each loop closes it
            # as it starts
            prologue.enter_context(_trace.span("fit.prologue"))
            return self._fit(x_a, x_b, dealer=dealer, wire=wire,
                             checkpoint=checkpoint, resume=resume,
                             resume_step=resume_step, prologue=prologue)

    def _fit(self, x_a: np.ndarray, x_b: np.ndarray, *,
             dealer=None, wire=None, checkpoint=None,
             resume: bool = False,
             resume_step: int | None = None,
             prologue: contextlib.ExitStack) -> KMeansResult:
        """Jointly cluster the two parties' data. `dealer` (optional)
        supplies the fit's correlated randomness from an EXTERNAL provider —
        pass a `TripleBank.dealer(key)` view over a bank provisioned with
        `plan_fit`'s (key, plan) to fit with zero in-process generation
        work. The bank must share the fit's seed (`cfg.seed`): per-class
        streams then make the served words — and hence every share and
        CommLog tally — bit-identical to the built-in dealers
        (test-enforced on all partition x sparsity combos).

        `wire` (optional `channel.WireSession`): attach a real two-party
        transport — every online CommLog event then SHIPS its byte count as
        sequenced frames to the peer process and pays its round-trips
        before tallying (core/channel.py). The in-process joint simulation
        is unchanged, so a wired fit is bit-exact with an unwired one.

        `checkpoint` (optional `checkpoint.fit.FitCheckpointer`): save a
        resumable `FitState` at the configured iteration/batch cadence.
        `resume=True` restores the latest checkpoint (fingerprint-checked
        against this cfg + data shapes) and continues — finishing with
        shares, dealer counters, and CommLog tallies bit-identical to an
        uninterrupted run (test-enforced; DESIGN.md §13). `resume_step`
        (the resume negotiation's agreed `min(step)`, DESIGN.md §16)
        instead restores the largest PUBLISHED step ≤ that value — a
        party holding a newer step than its peer witnessed rewinds to the
        common one; no such step means a fresh start (also bit-exact).

        `prologue` holds the open `fit.prologue` span; the loop closes it."""
        cfg = self.cfg
        rng = np.random.default_rng(cfg.seed)
        ctx = P.make_ctx(cfg.seed, backend=cfg.backend, wire=wire)
        ctx.vectorized = cfg.vectorized
        x_a = np.asarray(x_a, np.float64)
        x_b = np.asarray(x_b, np.float64)
        if cfg.partition == "vertical":
            assert x_a.shape[0] == x_b.shape[0]
            n, d = x_a.shape[0], x_a.shape[1] + x_b.shape[1]
        else:
            assert x_a.shape[1] == x_b.shape[1]
            n, d = x_a.shape[0] + x_b.shape[0], x_a.shape[1]
        with _trace.span("fit.encode"):
            enc_a = _encode_np(x_a, cfg.f)
            enc_b = _encode_np(x_b, cfg.f)
            csr_a = CSRMatrix.from_dense(enc_a) if cfg.sparse else None
            csr_b = CSRMatrix.from_dense(enc_b) if cfg.sparse else None

        st = None
        if checkpoint is not None:
            # bind the store to this (cfg, shapes) run; a foreign
            # checkpoint then fails the fingerprint check at load
            fp = self._fit_fingerprint(x_a.shape, x_b.shape)
            checkpoint.fingerprint = checkpoint.fingerprint or fp
        if resume or resume_step is not None:
            if checkpoint is None:
                raise ValueError(
                    "fit(resume=True) needs checkpoint=FitCheckpointer(...)"
                    " to restore from")
            if resume_step is not None:
                s = checkpoint.step_at_or_before(int(resume_step))
                st = checkpoint.load(s) if s is not None else None
            else:
                st = checkpoint.latest()
        if st is not None:
            if st.iteration >= cfg.iters:
                raise ValueError(
                    f"checkpoint is at iteration {st.iteration} of a "
                    f"{cfg.iters}-iteration fit: nothing left to resume")
            # the checkpointed mu shares + restored tallies REPLACE init:
            # the init exchange already happened (and was tallied) in the
            # interrupted run
            mu = AShare(jnp.asarray(st.mu0), jnp.asarray(st.mu1))
            ctx.log.restore(st.comm)
        else:
            # the k initial rows' two shares go up to the device
            with _trace.span("fit.init_centroids",
                             h2d_bytes=2 * ring.nbytes((cfg.k, d))):
                mu = self._init_centroids(ctx, rng, x_a, x_b)

        if cfg.batch_size is not None:
            # minibatch Lloyd: batched S1/S3-partial launches with secret-
            # shared running-sum accumulators and (optionally) pipelined
            # host exchanges — its own loop below
            return self._fit_minibatch(ctx, enc_a, enc_b, csr_a, csr_b,
                                       mu, n, d, ext_dealer=dealer,
                                       checkpoint=checkpoint, st=st,
                                       prologue=prologue)
        if st is not None and st.batch:
            raise ValueError(
                "a mid-iteration (batch > 0) checkpoint can only resume a "
                "minibatch fit; this config has batch_size=None")

        # pooled/streamed offline phase: trace the schedule (cached across
        # same-shape fits), bulk-generate the pools, upload once, and AOT-
        # compile the per-iteration S1/S3 program pair that consumes them —
        # for EVERY partition x sparsity combo. All of this is data-
        # independent work; the loop below then runs dealer-free, with the
        # sparse combos' Protocol-2 exchanges as host callbacks between the
        # two launches.
        it0 = st.iteration if st is not None else 0
        ckpt = checkpoint
        iter_counts = None
        if ckpt is not None or it0:
            # the advance map (per-class requests one iteration consumes) is
            # recomputed from the plan — the checkpoint stores a copy purely
            # as an integrity cross-check (DESIGN.md §13)
            iter_counts = self._plan_offline_iter(
                x_a.shape, x_b.shape)[0].class_counts()
        if it0:
            adv = {k: c * it0 for k, c in iter_counts.items()}
            if st.advance and st.advance != adv:
                raise ValueError(
                    "checkpoint dealer-stream positions disagree with the "
                    "plan-derived positions — the checkpoint belongs to a "
                    "different offline schedule")
        plan_s = 0.0
        fast = None
        if dealer is not None or cfg.offline in ("pooled", "streamed"):
            t0 = time.perf_counter()
            iter_plan, iter_comm = self._plan_offline_iter(
                x_a.shape, x_b.shape)
            # the compiled programs hardcode f = ring.F (launch/kmeans_step
            # has no per-config scale), so a custom precision falls back to
            # the eager pooled loop rather than silently truncating wrong;
            # the host-only numpy backend cannot be traced into a program
            use_fast = cfg.vectorized and cfg.f == ring.F \
                and self._traceable_backend()
            if use_fast:
                from repro.launch import kmeans_step as K
                progs = K.fit_programs(cfg.partition, cfg.sparse,
                                       enc_a.shape, enc_b.shape, cfg.k,
                                       backend=cfg.backend)
                # upload the constant plaintext operands once, offline; the
                # sparse host exchange #2 consumes the pre-transposed CSRs
                csr_at = csr_a.transpose() if cfg.sparse else None
                csr_bt = csr_b.transpose() if cfg.sparse else None
                fast = (progs, K.materialize_offline, iter_comm,
                        jnp.asarray(enc_a), jnp.asarray(enc_b),
                        csr_at, csr_bt)
            plan_s = time.perf_counter() - t0
            # resume: the restored comm snapshot already carries the FULL
            # fit's offline tallies (dealers account their whole plan at
            # construction), so a resumed dealer books offline to a scratch
            # log; its class streams start advanced past it0 iterations
            adv = {k: c * it0 for k, c in iter_counts.items()} if it0 else {}
            dlog = CommLog() if it0 else ctx.log
            if dealer is not None:
                # external provider (e.g. a provisioned TripleBank view):
                # its generation cost lives on the bank's offline books —
                # this fit pays only the (cached) plan + any stock-out stall
                if it0:
                    dealer.skip(iter_plan, it0)
                ctx.dealer = dealer
            elif cfg.offline == "pooled":
                ctx.dealer = PooledDealer(
                    iter_plan.repeat(cfg.iters - it0),
                    seed=cfg.seed, log=dlog, advance=adv)
            else:
                # group="auto": tiny k*d tranches share one background-
                # worker wakeup (bit-exact either way)
                ctx.dealer = StreamingPooledDealer(
                    iter_plan, cfg.iters - it0, seed=cfg.seed,
                    log=dlog, group="auto", advance=adv)
        elif it0:
            # on-demand resume: a fresh TrustedDealer on the live log (its
            # remaining offline tallies accrue ON TOP of the restored
            # snapshot, like the original loop's would have), streams
            # pre-advanced
            ctx.dealer = TrustedDealer(
                seed=cfg.seed, log=ctx.log,
                advance={k: c * it0 for k, c in iter_counts.items()})
        if st is not None:
            for attr in ("n_matmul", "n_mul", "n_bin"):
                setattr(ctx.dealer, attr, st.counters[attr])

        prologue.close()
        t_start = time.perf_counter()
        dealer_s_pre = ctx.dealer.dealer_seconds
        h_iter = _h_iter_seconds()
        it = it0
        try:
            for it in range(it0 + 1, cfg.iters + 1):
                t_iter = time.perf_counter()
                mu_old = mu
                if fast is not None:
                    # TWO launches per iteration (S1: distances+argmin, S3:
                    # update), the pool's device arrays entering as arguments
                    # (ListDealer discipline). The sparse combos run Protocol 2
                    # host-side around S1 — exchange #1 needs only the centroid
                    # shares, exchange #2 (the S2 callback) the assignment
                    # shares S1 just produced — and feed the results in as
                    # share inputs.
                    progs, materialize, iter_comm, dev_a, dev_b, \
                        csr_at, csr_bt = fast
                    he1 = he3 = []
                    hx = None
                    _fp.probe("fit.exchange1")
                    if cfg.sparse:
                        # scratch log (Ctx.fork): the launched programs' shape-
                        # determined traffic (incl. Protocol 2's) is replayed
                        # from iter_comm below; only he_seconds must flow back
                        hx = ctx.fork(tag="S1")
                        with _trace.span("fit.s1_exchange", iter=it):
                            he1 = self._s1_he_inputs(hx, enc_a, enc_b,
                                                     csr_a, csr_b, mu)
                    with _trace.span("fit.s1_launch", iter=it):
                        flat1 = materialize(progs.s1_requests, ctx.dealer)
                        c0, c1 = progs.s1(dev_a, dev_b, mu.s0, mu.s1,
                                          *he1, *flat1)
                    c = AShare(c0, c1)
                    _fp.probe("fit.mid_s1")
                    if cfg.sparse:
                        hx.tag = "S3"
                        with _trace.span("fit.s2_callback", iter=it):
                            he3 = self._s3_he_inputs(hx, csr_at, csr_bt, c)
                    _fp.probe("fit.s2_callback")
                    with _trace.span("fit.s3_launch", iter=it):
                        flat3 = materialize(progs.s3_requests, ctx.dealer)
                        mu0, mu1 = progs.s3(dev_a, dev_b, mu.s0, mu.s1,
                                            c0, c1, *he3, *flat3)
                    mu = AShare(mu0, mu1)
                    _fp.probe("fit.s3_partial")
                    if hx is not None:
                        ctx.add_he_seconds(hx.he_seconds)
                    # per-iteration traffic is shape-determined; replay the
                    # traced iteration's online tallies (protocol sends only
                    # fire at trace time inside a compiled step)
                    ctx.log.merge(iter_comm, phase="online")
                else:
                    ctx.tag = "S1"
                    with _trace.span("fit.s1_distances", iter=it):
                        dist = self._distances(ctx, enc_a, enc_b, csr_a,
                                               csr_b, mu)
                    ctx.tag = "S2"
                    r_before = ctx.log.total_rounds("online")
                    with _trace.span("fit.s2_argmin", iter=it):
                        c = P.argmin_onehot(ctx, dist)        # (n, k) scale 1
                    if not cfg.vectorized:
                        # pre-vectorization: each of the n samples runs its own
                        # tournament (n separate interaction chains per round)
                        dr = ctx.log.total_rounds("online") - r_before
                        _naive_extra_rounds(ctx, (n - 1) * dr + 1)
                    ctx.tag = "S3"
                    with _trace.span("fit.s3_update", iter=it):
                        mu = self._update(ctx, enc_a, enc_b, csr_a, csr_b,
                                          c, mu_old, n)
                if cfg.tol is not None:
                    ctx.tag = "CSC"
                    if self._converged(ctx, mu_old, mu, cfg.tol):
                        break
                if ckpt is not None and ckpt.want_iter(it, cfg.iters):
                    # iteration boundary: the live log is canonical (all of
                    # iterations 1..it merged, nothing ahead)
                    self._save_fit_ckpt(
                        ckpt, ctx, it, 0, mu,
                        {k: c * it for k, c in iter_counts.items()})
                h_iter.observe(time.perf_counter() - t_iter)
            jnp.asarray(mu.s0).block_until_ready()
            wall = time.perf_counter() - t_start
        finally:
            if isinstance(ctx.dealer, StreamingPooledDealer):
                # a tol early-stop — or an exception unwinding the loop —
                # leaves prefetched tranches and the worker thread alive;
                # release them AFTER the online clock stops (no-op when the
                # fit served every tranche)
                ctx.dealer.close()
        dealer = ctx.dealer
        in_loop_dealer_s = dealer.dealer_seconds - dealer_s_pre
        self.result_ = KMeansResult(
            centroids=mu, assignment=c, iters_run=it, log=ctx.log,
            dealer=dealer,
            online_seconds=max(0.0, wall - in_loop_dealer_s),
            offline_dealer_seconds=dealer.dealer_seconds + plan_s,
            offline_modelled_ot_seconds=dealer.modelled_ot_seconds,
            he_seconds=ctx.he_seconds,
            loop_seconds=wall,
            offline_plan_seconds=plan_s,
        )
        return self.result_

    # ------------------------------------------------------------------ #
    # Minibatch Lloyd — batched S1/S3-partial launches, pipelined exchanges
    # ------------------------------------------------------------------ #
    def _fit_minibatch(self, ctx, enc_a, enc_b, csr_a, csr_b, mu: AShare,
                       n: int, d: int, *, prologue: contextlib.ExitStack,
                       ext_dealer=None, checkpoint=None,
                       st=None) -> KMeansResult:
        """Each iteration is one full pass over the data in
        ceil(n / batch_size)-row batches: per batch an S1 launch (distances
        + argmin on the CURRENT centroids) and an S3-partial launch whose
        (k, d)/(k,) sums accumulate in secret-shared running accumulators
        (share addition — free), then ONE finalize launch divides. This is
        blocked full-batch Lloyd, not stochastic minibatching: bit-exact
        with the single-pass pooled path at batch_size >= n, and within
        truncation-LSB noise of it otherwise.

        With cfg.pipeline, batch t+1's Protocol-2 exchange and tranche pin
        run on the host while batch t's S1 launch is on device
        (launch/pipeline.run_pipeline); the SlotDealer pins each (iteration,
        batch, stage) slot's randomness at generation time — in canonical
        slot order — so pipeline=False is stream-identical."""
        cfg = self.cfg
        if cfg.offline not in ("pooled", "streamed"):
            raise ValueError(
                "batch_size (minibatch Lloyd) requires the planned offline "
                "phase: set offline='pooled' or 'streamed' "
                f"(got {cfg.offline!r})")
        if not (cfg.vectorized and cfg.f == ring.F
                and self._traceable_backend()):
            raise ValueError(
                "minibatch Lloyd runs on the compiled S1/S3 fast path only: "
                f"it needs vectorized=True, f={ring.F} and a device-"
                "traceable backend (numpy is host-only)")
        from repro.launch import kmeans_step as K
        from repro.launch.pipeline import run_pipeline

        t0 = time.perf_counter()
        with _trace.span("fit.plan"):
            bounds, stage_plans, (fin_plan, fin_comm), _ = \
                self._minibatch_slot_plans(enc_a.shape, enc_b.shape)
            batches = []
            for ((alo, ahi), (blo, bhi)), plans in zip(bounds, stage_plans):
                ea, eb = enc_a[alo:ahi], enc_b[blo:bhi]
                s1_plan, s1_comm, s3_plan, s3_comm = plans
                batches.append({
                    "enc_a": ea, "enc_b": eb,
                    "progs": K.fit_batch_programs(cfg.partition, cfg.sparse,
                                                  ea.shape, eb.shape, cfg.k,
                                                  backend=cfg.backend),
                    "s1_plan": s1_plan, "s1_comm": s1_comm,
                    "s3_plan": s3_plan, "s3_comm": s3_comm,
                    "a_rows": ahi - alo,
                })
            fin_prog = K.finalize_program(cfg.k, d, n, backend=cfg.backend)
        if cfg.sparse:
            with _trace.span("fit.encode"):
                for b in batches:
                    b["csr_a"] = CSRMatrix.from_dense(b["enc_a"])
                    b["csr_b"] = CSRMatrix.from_dense(b["enc_b"])
                    b["csr_at"] = b["csr_a"].transpose()
                    b["csr_bt"] = b["csr_b"].transpose()
        with _trace.span("fit.upload", h2d_bytes=sum(
                b["enc_a"].nbytes + b["enc_b"].nbytes for b in batches)):
            for b in batches:
                b["dev_a"] = jnp.asarray(b["enc_a"])
                b["dev_b"] = jnp.asarray(b["enc_b"])
        iter_slots = []
        for b in batches:
            iter_slots += [b["s1_plan"], b["s3_plan"]]
        iter_slots.append(fin_plan)
        spi = len(iter_slots)                    # slots per iteration
        ckpt = checkpoint
        if ckpt is not None and ckpt.batch_every is not None and cfg.pipeline:
            raise ValueError(
                "batch-granular checkpoints (batch_every) require "
                "pipeline=False: the pipelined executor merges batch t+1's "
                "traffic before batch t accumulates, so mid-iteration the "
                "live CommLog is not the canonical prefix a resume restores "
                "(iteration-boundary checkpoints work on both executors)")
        it0 = st.iteration if st is not None else 0
        b0 = st.batch if st is not None else 0
        start_slot = it0 * spi + 2 * b0
        slot_counts = [p.class_counts() for p in iter_slots]

        def slots_advance(n_slots: int) -> dict:
            adv: dict = {}
            for s in range(n_slots):
                for ck, c in slot_counts[s % spi].items():
                    adv[ck] = adv.get(ck, 0) + c
            return adv

        if st is not None and st.advance \
                and st.advance != slots_advance(start_slot):
            raise ValueError(
                "checkpoint dealer-stream positions disagree with the "
                "plan-derived slot positions — the checkpoint belongs to a "
                "different offline schedule")
        # resume: offline tallies for the WHOLE schedule were booked at the
        # original dealer's construction and live in the restored snapshot —
        # a resumed dealer books its (remaining-slot) accounting to scratch
        dlog = CommLog() if start_slot else ctx.log
        if ext_dealer is not None:
            bank = getattr(ext_dealer, "bank", None)
            if bank is None:
                raise ValueError(
                    "minibatch fit(dealer=...) takes a TripleBank dealer "
                    "view (bank.dealer(key) over a plan_fit provisioning); "
                    f"got {type(ext_dealer).__name__}")
            with _trace.span("fit.dealer_start"):
                dealer = BankSlotDealer(bank, ext_dealer.key,
                                        iter_slots * cfg.iters, log=dlog,
                                        start_slot=start_slot)
        else:
            with _trace.span("fit.dealer_start"):
                dealer = SlotDealer(iter_slots * cfg.iters, seed=cfg.seed,
                                    log=dlog,
                                    stream=(cfg.offline == "streamed"),
                                    start_slot=start_slot)
        ctx.dealer = dealer
        if st is not None:
            for attr in ("n_matmul", "n_mul", "n_bin"):
                setattr(dealer, attr, st.counters[attr])
        plan_s = time.perf_counter() - t0
        prologue.close()

        t_start = time.perf_counter()
        h_iter = _h_iter_seconds()
        it = it0
        c_parts = [None] * len(batches)
        try:
            for it in range(it0 + 1, cfg.iters + 1):
                t_iter = time.perf_counter()
                mu_old = mu
                base = (it - 1) * spi
                start_b = b0 if it == it0 + 1 else 0
                if start_b:
                    # mid-iteration resume: restored partial accumulators +
                    # completed batches' assignment shares; remaining
                    # batches run from the checkpointed cursor
                    acc = [jnp.asarray(a) for a in st.acc]
                    for t in range(start_b):
                        c_parts[t] = AShare(jnp.asarray(st.c0_parts[t]),
                                            jnp.asarray(st.c1_parts[t]))
                else:
                    acc = [jnp.zeros((cfg.k, d), ring.DTYPE),
                           jnp.zeros((cfg.k, d), ring.DTYPE),
                           jnp.zeros((cfg.k,), ring.DTYPE),
                           jnp.zeros((cfg.k,), ring.DTYPE)]

                def on_done(t_done: int, _it=it, _acc=acc, _mu=mu):
                    b_done = t_done + 1
                    if ckpt is None \
                            or not ckpt.want_batch(b_done, len(batches)):
                        return
                    # sequential executor only (enforced above): after batch
                    # t's post, the live log holds exactly batches 0..t —
                    # the canonical prefix
                    self._save_fit_ckpt(
                        ckpt, ctx, _it - 1, b_done, _mu,
                        slots_advance((_it - 1) * spi + 2 * b_done),
                        acc=_acc, c_parts=c_parts[:b_done])

                tasks = [self._batch_task(ctx, dealer, b, mu,
                                          base + 2 * t, acc, c_parts, t,
                                          on_done=on_done)
                         for t, b in enumerate(batches) if t >= start_b]
                run_pipeline(tasks, pipeline=cfg.pipeline)
                _fp.probe("fit.finalize")
                with _trace.span("fit.finalize", iter=it):
                    fin_view = dealer.acquire(base + 2 * len(batches))
                    flat_f = K.materialize_offline(fin_prog.requests,
                                                   fin_view)
                    mu0, mu1 = fin_prog.fn(mu.s0, mu.s1, acc[0], acc[1],
                                           acc[2], acc[3], *flat_f)
                mu = AShare(mu0, mu1)
                ctx.log.merge(fin_comm, phase="online")
                if cfg.tol is not None:
                    # CSC triples live at the tail of the finalize slot
                    cctx = P.Ctx(dealer=fin_view, log=ctx.log, tag="CSC",
                                 backend=ctx.backend)
                    if self._converged(cctx, mu_old, mu, cfg.tol):
                        break
                if ckpt is not None and ckpt.want_iter(it, cfg.iters):
                    # iteration boundary: the pipeline fully drained at
                    # finalize, so this cut is canonical on BOTH executors
                    self._save_fit_ckpt(ckpt, ctx, it, 0, mu,
                                        slots_advance(it * spi))
                h_iter.observe(time.perf_counter() - t_iter)
            jnp.asarray(mu.s0).block_until_ready()
            wall = time.perf_counter() - t_start
        finally:
            dealer.close()

        with _trace.span("fit.assemble"):
            c = _assemble_assignment(cfg.partition, c_parts, batches)
        self.result_ = KMeansResult(
            centroids=mu, assignment=c, iters_run=it, log=ctx.log,
            dealer=dealer,
            # SlotDealer stalls (wait_seconds) stay in the online clock on
            # purpose — they are real online stalls, like the streaming
            # dealer's
            # same convention as the streamed full-batch path: overlapped
            # worker generation (gen_seconds) stays OFF the offline column
            # — it already overlaps the online wall
            online_seconds=wall,
            offline_dealer_seconds=dealer.dealer_seconds + plan_s,
            offline_modelled_ot_seconds=dealer.modelled_ot_seconds,
            he_seconds=ctx.he_seconds,
            loop_seconds=wall,
            offline_plan_seconds=plan_s,
        )
        return self.result_

    def _batch_task(self, ctx, dealer, b: dict, mu: AShare, slot0: int,
                    acc: list, c_parts: list, t: int, on_done=None):
        """One minibatch as a 4-phase pipeline step (launch/pipeline.py):
        pre = exchange #1 (centroid shares only) + S1 tranche pin; launch =
        S1 dispatch; mid = exchange #2 on the assignment shares (the S2
        callback — blocks on the device) + S3 tranche pin; post = S3-partial
        dispatch + accumulator adds."""
        cfg = self.cfg
        from repro.launch import kmeans_step as K
        from repro.launch.pipeline import StageTask
        progs = b["progs"]

        def hx_ctx(view, tag):
            return P.Ctx(dealer=view, log=CommLog(), tag=tag,
                         backend=ctx.backend)

        def flow_he(hx):
            ctx.add_he_seconds(hx.he_seconds)

        def pre():
            _fp.probe("fit.exchange1")
            view = dealer.acquire(slot0)
            he1 = []
            if cfg.sparse:
                hx = hx_ctx(view, "S1")
                he1 = self._s1_he_inputs(hx, b["enc_a"], b["enc_b"],
                                         b["csr_a"], b["csr_b"], mu)
                flow_he(hx)
            flat1 = K.materialize_offline(progs.s1_requests, view)
            ctx.log.merge(b["s1_comm"], phase="online")
            return he1, flat1

        def launch(prep):
            he1, flat1 = prep
            c0, c1 = progs.s1(b["dev_a"], b["dev_b"], mu.s0, mu.s1,
                              *he1, *flat1)
            _fp.probe("fit.mid_s1")
            return AShare(c0, c1)

        def mid(prep, c):
            _fp.probe("fit.s2_callback")
            view = dealer.acquire(slot0 + 1)
            he3 = []
            if cfg.sparse:
                hx = hx_ctx(view, "S3")
                he3 = self._s3_he_inputs(hx, b["csr_at"], b["csr_bt"], c)
                flow_he(hx)
            flat3 = K.materialize_offline(progs.s3p_requests, view)
            ctx.log.merge(b["s3_comm"], phase="online")
            return he3, flat3

        def post(prep, c, m):
            _fp.probe("fit.s3_partial")
            he3, flat3 = m
            n0, n1, d0, d1 = progs.s3p(b["dev_a"], b["dev_b"], c.s0, c.s1,
                                       *he3, *flat3)
            acc[0] = acc[0] + n0
            acc[1] = acc[1] + n1
            acc[2] = acc[2] + d0
            acc[3] = acc[3] + d1
            c_parts[t] = c
            if on_done is not None:
                on_done(t)
            return None

        return StageTask(pre, launch, mid, post)

    def _plan_batch_stage(self, shape_a, shape_b, stage: str):
        """(plan, comm) of ONE minibatch stage — 's1' (distances + argmin)
        or 's3p' (C^T X partial sums) — cached like the full-iteration
        plans. Concatenated per iteration (batch stages + finalize) the
        slot plans equal the full-batch iteration plan when
        batch_size >= n: the bit-exactness anchor."""
        key = ("mb", stage) + self._plan_cache_key(shape_a, shape_b)
        hit = _PLAN_CACHE.get(key)
        if hit is None:
            hit = _PLAN_CACHE[key] = self._trace_batch_stage(
                shape_a, shape_b, stage)
        plan, comm = hit
        return TriplePlan(list(plan.requests)), comm.copy()

    def _trace_batch_stage(self, shape_a, shape_b, stage: str):
        """Dry-run trace of one minibatch stage on zero-filled batch
        slices with a PlanningDealer (the per-stage analogue of
        `_trace_iteration`)."""
        cfg = self.cfg
        ctx = P.Ctx(dealer=PlanningDealer(), log=CommLog(),
                    backend=cfg.backend)
        ctx.vectorized = cfg.vectorized
        enc_a = np.zeros(tuple(shape_a), np.uint64)
        enc_b = np.zeros(tuple(shape_b), np.uint64)
        d = enc_a.shape[1] + enc_b.shape[1] if cfg.partition == "vertical" \
            else enc_a.shape[1]
        csr_a = CSRMatrix.from_dense(enc_a) if cfg.sparse else None
        csr_b = CSRMatrix.from_dense(enc_b) if cfg.sparse else None
        if stage == "s1":
            mu = AShare(jnp.zeros((cfg.k, d), ring.DTYPE),
                        jnp.zeros((cfg.k, d), ring.DTYPE))
            ctx.tag = "S1"
            dist = self._distances(ctx, enc_a, enc_b, csr_a, csr_b, mu)
            ctx.tag = "S2"
            P.argmin_onehot(ctx, dist)
        else:
            rows = enc_a.shape[0] if cfg.partition == "vertical" \
                else enc_a.shape[0] + enc_b.shape[0]
            c = AShare(jnp.zeros((rows, cfg.k), ring.DTYPE),
                       jnp.zeros((rows, cfg.k), ring.DTYPE))
            ctx.tag = "S3"
            self._ct_x(ctx, enc_a, enc_b, csr_a, csr_b, c)
        comm = CommLog()
        comm.merge(ctx.log, phase="online")
        return ctx.dealer.plan(), comm

    def _plan_finalize(self, d: int, n: int):
        """(plan, comm) of the per-iteration finalize launch (+ CSC when
        tol is set); keyed by the division constants, not the batch
        layout."""
        cfg = self.cfg
        key = ("mb", "fin", cfg.k, int(d), int(n), cfg.f, cfg.vectorized,
               cfg.tol is not None)
        hit = _PLAN_CACHE.get(key)
        if hit is None:
            hit = _PLAN_CACHE[key] = self._trace_finalize(d, n)
        plan, comm = hit
        return TriplePlan(list(plan.requests)), comm.copy()

    def _trace_finalize(self, d: int, n: int):
        cfg = self.cfg
        ctx = P.Ctx(dealer=PlanningDealer(), log=CommLog(),
                    backend=cfg.backend)
        ctx.vectorized = cfg.vectorized
        z = lambda s: jnp.zeros(s, ring.DTYPE)  # noqa: E731
        mu = AShare(z((cfg.k, d)), z((cfg.k, d)))
        num = AShare(z((cfg.k, d)), z((cfg.k, d)))
        den = AShare(z((cfg.k,)), z((cfg.k,)))
        ctx.tag = "S3"
        mu_new = self._update_final(ctx, num, den, mu, n)
        comm = CommLog()
        comm.merge(ctx.log, phase="online")
        if cfg.tol is not None:
            ctx.tag = "CSC"
            self._converged(ctx, mu, mu_new, cfg.tol)
        return ctx.dealer.plan(), comm

    def _minibatch_slot_plans(self, shape_a, shape_b):
        """Canonical minibatch offline layout for party-input shapes — the
        single source of truth shared by `plan_fit` (bank provisioning) and
        `_fit_minibatch` (consumption), so a provisioned bank and a live fit
        can never disagree on slot order. Returns (bounds, per-batch
        [(s1_plan, s1_comm, s3_plan, s3_comm)], (fin_plan, fin_comm),
        iter_comm): per iteration the slots run [s1(b0), s3p(b0), s1(b1),
        ..., finalize]."""
        cfg = self.cfg
        na, nb = int(shape_a[0]), int(shape_b[0])
        if cfg.partition == "vertical":
            n, d = na, int(shape_a[1]) + int(shape_b[1])
        else:
            n, d = na + nb, int(shape_a[1])
        bounds = _minibatch_bounds(cfg.partition, na, nb, cfg.batch_size)
        stage_plans = []
        iter_comm = CommLog()
        for (alo, ahi), (blo, bhi) in bounds:
            sa = (ahi - alo, int(shape_a[1]))
            sb = (bhi - blo, int(shape_b[1]))
            s1_plan, s1_comm = self._plan_batch_stage(sa, sb, "s1")
            s3_plan, s3_comm = self._plan_batch_stage(sa, sb, "s3p")
            stage_plans.append((s1_plan, s1_comm, s3_plan, s3_comm))
            iter_comm.merge(s1_comm, phase="online")
            iter_comm.merge(s3_comm, phase="online")
        fin_plan, fin_comm = self._plan_finalize(d, n)
        iter_comm.merge(fin_comm, phase="online")
        return bounds, stage_plans, (fin_plan, fin_comm), iter_comm

    # ------------------------------------------------------------------ #
    # Secure scoring: batched predict/score against the secret-shared model
    # ------------------------------------------------------------------ #
    def predict(self, x_a: np.ndarray, x_b: np.ndarray,
                result: KMeansResult | None = None, *, dealer=None,
                compiled: bool | None = None, wire=None) -> PredictResult:
        """Assign a NEW batch to the fitted clusters without revealing the
        model: batched secure distances + tournament argmin against the
        secret-shared centroids; only the (m, k) assignment shares come
        back (Rec happens in `labels_plain`). Vertical: the parties hold
        the batch rows' column slices (equal row counts); horizontal: each
        party owns whole arrival rows, outputs ordered [A rows; B rows].

        `dealer` supplies the correlated randomness — default an on-demand
        `TrustedDealer(cfg.seed)`; pass a `TripleBank.dealer(...)` view to
        serve from a provisioned pool (`plan_predict` gives the bank key
        and plan). `compiled=None` auto-selects the AOT-compiled
        `predict_program` launch (vectorized, f = ring.F) and falls back to
        the eager reference otherwise; both paths are bit-exact for any
        same-seeded per-class dealer (tests/test_serve.py)."""
        return self._predict(x_a, x_b, result, dealer=dealer,
                             compiled=compiled, with_scores=False, wire=wire)

    def score(self, x_a: np.ndarray, x_b: np.ndarray,
              result: KMeansResult | None = None, *, dealer=None,
              compiled: bool | None = None, wire=None) -> PredictResult:
        """`predict` + the (m,) squared-distance-to-assigned-centroid
        shares: the tournament's winning D' value (carried for free) plus
        each party's locally-computable ||x||^2 contribution. This is the
        fraud-scoring primitive — outlier flags follow from revealing ONLY
        these scores, never centroids or per-cluster structure."""
        return self._predict(x_a, x_b, result, dealer=dealer,
                             compiled=compiled, with_scores=True, wire=wire)

    def _check_predict_args(self, x_a, x_b, result):
        cfg = self.cfg
        if result is None:
            result = getattr(self, "result_", None)
        if result is None:
            raise ValueError("predict/score needs a fitted model: call "
                             "fit() first or pass result=")
        x_a = np.asarray(x_a, np.float64)
        x_b = np.asarray(x_b, np.float64)
        d = result.centroids.shape[1]
        if cfg.partition == "vertical":
            if x_a.shape[0] != x_b.shape[0]:
                raise ValueError("vertical predict needs equal batch rows")
            if x_a.shape[1] + x_b.shape[1] != d:
                raise ValueError("predict feature split disagrees with the "
                                 f"fitted model: {x_a.shape[1]}+{x_b.shape[1]}"
                                 f" != {d}")
        else:
            if x_a.shape[1] != d or x_b.shape[1] != d:
                raise ValueError("horizontal predict rows must carry all "
                                 f"{d} model features")
        return x_a, x_b, result

    def _predict(self, x_a, x_b, result, *, dealer, compiled,
                 with_scores: bool, wire=None) -> PredictResult:
        cfg = self.cfg
        x_a, x_b, result = self._check_predict_args(x_a, x_b, result)
        if compiled:
            # an explicit request for the compiled path must not silently
            # truncate at the wrong scale or die in an obscure trace error
            if cfg.f != ring.F:
                raise ValueError(
                    f"compiled predict hardcodes f = {ring.F}; cfg.f = "
                    f"{cfg.f} must use the eager path (compiled=False)")
            if not self._traceable_backend():
                raise ValueError(
                    "the host-only numpy backend cannot lower into the "
                    "compiled predict program; use compiled=False")
        use_fast = compiled if compiled is not None \
            else (cfg.vectorized and cfg.f == ring.F
                  and self._traceable_backend())
        if use_fast:
            prep = self.predict_prepare(x_a, x_b, result, dealer=dealer,
                                        with_scores=with_scores, wire=wire)
            return self.predict_collect(prep, self.predict_launch(prep))
        with _trace.span("predict.eager", rows=int(x_a.shape[0]),
                         scores=with_scores):
            return self._predict_eager(x_a, x_b, result, dealer=dealer,
                                       with_scores=with_scores, wire=wire)

    def _predict_eager(self, x_a, x_b, result, *, dealer,
                       with_scores: bool, wire=None) -> PredictResult:
        cfg = self.cfg
        t0 = time.perf_counter()
        enc_a = _encode_np(x_a, cfg.f)
        enc_b = _encode_np(x_b, cfg.f)
        csr_a = CSRMatrix.from_dense(enc_a) if cfg.sparse else None
        csr_b = CSRMatrix.from_dense(enc_b) if cfg.sparse else None
        log = CommLog()
        log.wire = wire
        if dealer is None:
            # domain-separated from the fit's streams: reusing cfg.seed
            # verbatim would replay the fit's Beaver masks on overlapping
            # shape-classes (mask reuse on two secrets leaks their diff)
            dealer = TrustedDealer(seed=serve_seed(cfg.seed), log=log)
        ctx = P.Ctx(dealer=dealer, log=log, backend=cfg.backend)
        ctx.vectorized = cfg.vectorized
        ctx.tag = "predict"
        mu = result.centroids
        vmin = None
        dist = self._distances(ctx, enc_a, enc_b, csr_a, csr_b, mu)
        if with_scores:
            c, vmin = P.argmin_onehot(ctx, dist, return_min=True)
        else:
            c = P.argmin_onehot(ctx, dist)
        scores = None
        if with_scores:
            # ||x - mu_c||^2 = ||x||^2 + (||mu_c||^2 - 2 x.mu_c): the first
            # term is party-local plaintext (each owner encodes its slice's
            # contribution into its share — no triples, no traffic), the
            # parenthesis is the tournament's winning D' value.
            scores = P.add(vmin, self._norm_shares(x_a, x_b))
        jnp.asarray(c.s0).block_until_ready()
        return PredictResult(assignment=c, scores=scores, log=log,
                             seconds=time.perf_counter() - t0, f=cfg.f)

    # -- compiled scoring, split into pipelineable phases ---------------- #
    def predict_prepare(self, x_a, x_b, result: KMeansResult | None = None,
                        *, dealer=None, with_scores: bool = False,
                        wire=None) -> "PreparedPredict":
        """Host phase of ONE compiled scoring launch: validate, encode, run
        the Protocol-2 pre-launch exchange (computable from the centroid
        shares alone), draw the offline tranche, stage the program
        arguments. `predict_launch` dispatches (async under jax) and
        `predict_collect` assembles the PredictResult; prepare -> launch ->
        collect in sequence IS the compiled predict path, and the serving
        loop overlaps request t+1's prepare with request t's in-flight
        launch (launch/pipeline.py) — same calls, same order per request,
        so the pipelined and sequential drains are stream-identical."""
        cfg = self.cfg
        x_a, x_b, result = self._check_predict_args(x_a, x_b, result)
        if not (cfg.vectorized and cfg.f == ring.F
                and self._traceable_backend()):
            raise ValueError(
                "predict_prepare stages the compiled scoring program only; "
                "non-default f / unvectorized / numpy-backend configs must "
                "score through predict/score (eager path)")
        from repro.launch import kmeans_step as K
        with _trace.span("predict.prepare", rows=int(x_a.shape[0]),
                         scores=with_scores):
            t0 = time.perf_counter()
            enc_a = _encode_np(x_a, cfg.f)
            enc_b = _encode_np(x_b, cfg.f)
            csr_a = CSRMatrix.from_dense(enc_a) if cfg.sparse else None
            csr_b = CSRMatrix.from_dense(enc_b) if cfg.sparse else None
            log = CommLog()
            log.wire = wire
            if dealer is None:
                # domain-separated from the fit's streams (see _predict)
                dealer = TrustedDealer(seed=serve_seed(cfg.seed), log=log)
            ctx = P.Ctx(dealer=dealer, log=log, backend=cfg.backend)
            ctx.vectorized = cfg.vectorized
            ctx.tag = "predict"
            mu = result.centroids
            prog = K.predict_program(cfg.partition, cfg.sparse,
                                     enc_a.shape, enc_b.shape, cfg.k,
                                     with_scores=with_scores,
                                     backend=cfg.backend)
            _, comm = self._plan_predict_cached(x_a.shape, x_b.shape,
                                                with_scores)
            he1 = []
            if cfg.sparse:
                # scratch log (Ctx.fork): the launch's shape-determined
                # traffic — the exchange's included — replays from the
                # traced plan's CommLog at collect time
                hx = ctx.fork(tag="predict")
                he1 = self._s1_he_inputs(hx, enc_a, enc_b, csr_a, csr_b, mu)
            flat = K.materialize_offline(prog.requests, ctx.dealer)
            args = (jnp.asarray(enc_a), jnp.asarray(enc_b), mu.s0, mu.s1,
                    *he1, *flat)
            return PreparedPredict(prog=prog, args=args, log=log, comm=comm,
                                   with_scores=with_scores, x_a=x_a,
                                   x_b=x_b, t0=t0)

    def predict_launch(self, prep: "PreparedPredict"):
        """Dispatch the staged scoring program — asynchronous under jax:
        the raw output buffers come back immediately while the device
        computes."""
        with _trace.span("predict.launch"):
            return prep.prog.fn(*prep.args)

    def predict_collect(self, prep: "PreparedPredict",
                        outs) -> PredictResult:
        """Reveal-side assembly of one launch's outputs (blocks on the
        device): assignment shares, optional score shares (winning D' +
        locally-encoded ||x||^2), replayed traffic tallies."""
        with _trace.span("predict.collect"):
            c = AShare(outs[0], outs[1])
            scores = None
            if prep.with_scores:
                vmin = AShare(outs[2], outs[3])
                scores = P.add(vmin, self._norm_shares(prep.x_a, prep.x_b))
            prep.log.merge(prep.comm, phase="online")
            jnp.asarray(c.s0).block_until_ready()
            return PredictResult(assignment=c, scores=scores, log=prep.log,
                                 seconds=time.perf_counter() - prep.t0,
                                 f=self.cfg.f)

    def _traceable_backend(self) -> bool:
        """The numpy ring backend runs host-side and cannot lower into the
        compiled fast paths; eager loops serve it (bit-exact either way)."""
        from repro.core.backend import get_backend
        return get_backend(self.cfg.backend).name != "numpy"

    def _norm_shares(self, x_a, x_b) -> AShare:
        """(m,) shares of ||x||^2 at scale f from party-local plaintext.
        Vertical: A's columns land in s0, B's in s1. Horizontal: the owner
        of each row holds its whole norm (A rows -> s0, B rows -> s1)."""
        cfg = self.cfg
        na = _encode_np((x_a ** 2).sum(1), cfg.f)
        nb = _encode_np((x_b ** 2).sum(1), cfg.f)
        if cfg.partition == "vertical":
            return AShare(jnp.asarray(na), jnp.asarray(nb))
        za = np.zeros_like(na)
        zb = np.zeros_like(nb)
        return AShare(jnp.asarray(np.concatenate([na, zb])),
                      jnp.asarray(np.concatenate([za, nb])))

    # ------------------------------------------------------------------ #
    def plan_predict(self, shape_a, shape_b,
                     with_scores: bool = False) -> tuple:
        """(bank_key, TriplePlan, CommLog) of ONE scoring launch for
        party-input batch shapes — without seeing any data. The plan is the
        exact correlated-randomness schedule a `predict`/`score` call of
        these shapes consumes (Protocol-2 mask seeds included); the key is
        the predict-plan cache key, which `TripleBank.provision` uses as
        the pool lookup key. Cached: a service scoring thousands of batches
        traces each geometry once."""
        key = self._predict_plan_key(shape_a, shape_b, with_scores)
        plan, comm = self._plan_predict_cached(shape_a, shape_b, with_scores)
        return key, plan, comm

    def _predict_plan_key(self, shape_a, shape_b, with_scores) -> tuple:
        return ("predict", bool(with_scores)) \
            + self._plan_cache_key(shape_a, shape_b)

    def _plan_predict_cached(self, shape_a, shape_b, with_scores):
        key = self._predict_plan_key(shape_a, shape_b, with_scores)
        hit = _PREDICT_PLAN_CACHE.get(key)
        if hit is None:
            hit = _PREDICT_PLAN_CACHE[key] = self._trace_predict(
                shape_a, shape_b, with_scores)
        plan, comm = hit
        return TriplePlan(list(plan.requests)), comm.copy()

    def _trace_predict(self, shape_a, shape_b, with_scores):
        """Dry-run trace of one scoring launch (distances + argmin) with a
        PlanningDealer on zero-filled inputs — the predict counterpart of
        `_trace_iteration`."""
        cfg = self.cfg
        ctx = P.Ctx(dealer=PlanningDealer(), log=CommLog(),
                    backend=cfg.backend)
        ctx.vectorized = cfg.vectorized
        ctx.tag = "predict"
        enc_a = np.zeros(tuple(shape_a), np.uint64)
        enc_b = np.zeros(tuple(shape_b), np.uint64)
        d = enc_a.shape[1] + enc_b.shape[1] if cfg.partition == "vertical" \
            else enc_a.shape[1]
        csr_a = CSRMatrix.from_dense(enc_a) if cfg.sparse else None
        csr_b = CSRMatrix.from_dense(enc_b) if cfg.sparse else None
        mu = AShare(jnp.zeros((cfg.k, d), ring.DTYPE),
                    jnp.zeros((cfg.k, d), ring.DTYPE))
        dist = self._distances(ctx, enc_a, enc_b, csr_a, csr_b, mu)
        P.argmin_onehot(ctx, dist, return_min=with_scores)
        comm = CommLog()
        comm.merge(ctx.log, phase="online")
        return ctx.dealer.plan(), comm

    # ------------------------------------------------------------------ #
    def plan_offline(self, shape_a, shape_b) -> TriplePlan:
        """Derive the exact correlated-randomness schedule of `fit` for
        party-input shapes (shape_a, shape_b) — without seeing any data.

        One Lloyd iteration (+ the CSC check when `tol` is set) is traced
        eagerly on zero-filled inputs with a `PlanningDealer`; every triple
        shape is data-independent, so the full-fit schedule is that trace
        repeated `iters` times. A `tol` early-stop only leaves pool surplus.
        The trace runs the real protocol ops, so it also warms the backend's
        kernel caches with exactly the online shapes — offline work again.
        """
        return self._plan_offline_iter(shape_a, shape_b)[0] \
            .repeat(self.cfg.iters)

    def plan_fit(self, shape_a, shape_b) -> tuple:
        """(bank_key, TriplePlan, CommLog) of a WHOLE fit for party-input
        shapes — the fit-side counterpart of `plan_predict`. The plan is the
        exact correlated-randomness schedule `fit` consumes (full-batch:
        the iteration plan repeated `iters` times; minibatch: the canonical
        slot-plan sequence, concatenated), Protocol-2 mask seeds included;
        the key is the fit-plan cache key extended with the loop geometry
        (iters, batch_size), which `TripleBank.provision` uses as the pool
        lookup key. Provision a bank under the fit's `cfg.seed`, then call
        `fit(..., dealer=bank.dealer(key))`: the fit runs with zero
        generation work and bit-exact shares/counters/CommLog vs the
        built-in dealers. The returned CommLog carries ONE iteration's
        online traffic (informational — provisioning needs only the plan)."""
        cfg = self.cfg
        key = self._fit_plan_key(shape_a, shape_b)
        if cfg.batch_size is None:
            iter_plan, iter_comm = self._plan_offline_iter(shape_a, shape_b)
            return key, iter_plan.repeat(cfg.iters), iter_comm
        _bounds, stage_plans, (fin_plan, _fc), iter_comm = \
            self._minibatch_slot_plans(shape_a, shape_b)
        iter_reqs = [r for (s1, _c1, s3, _c3) in stage_plans
                     for r in list(s1.requests) + list(s3.requests)]
        iter_reqs += list(fin_plan.requests)
        return key, TriplePlan(iter_reqs * cfg.iters), iter_comm

    def _fit_plan_key(self, shape_a, shape_b) -> tuple:
        return ("fit", self.cfg.iters, self.cfg.batch_size) \
            + self._plan_cache_key(shape_a, shape_b)

    def _fit_fingerprint(self, shape_a, shape_b) -> str:
        """Checkpoint identity: everything that shapes the fit's schedule,
        streams, and init — a resumed run with ANY of these changed would
        not be the same fit. `pipeline` is deliberately excluded: the
        executors are stream-identical at checkpointable cuts."""
        from repro.checkpoint.store import config_fingerprint
        cfg = self.cfg
        key = self._fit_plan_key(shape_a, shape_b) + (
            "fit-ckpt", cfg.seed, cfg.offline, cfg.init, cfg.tol)
        return config_fingerprint(key)

    def _save_fit_ckpt(self, ckpt, ctx, it: int, batch: int, mu: AShare,
                       advance: dict, acc=None, c_parts=None) -> None:
        from repro.checkpoint.fit import FitState
        d = ctx.dealer
        ckpt.save(FitState(
            iteration=it, batch=batch,
            mu0=np.asarray(mu.s0, np.uint64),
            mu1=np.asarray(mu.s1, np.uint64),
            counters={"n_matmul": int(d.n_matmul), "n_mul": int(d.n_mul),
                      "n_bin": int(d.n_bin)},
            comm=ctx.log.state(), advance=advance,
            fingerprint=ckpt.fingerprint,
            acc=None if acc is None else [np.asarray(a, np.uint64)
                                          for a in acc],
            c0_parts=[np.asarray(p.s0, np.uint64) for p in (c_parts or [])],
            c1_parts=[np.asarray(p.s1, np.uint64) for p in (c_parts or [])]))

    def _plan_cache_key(self, shape_a, shape_b) -> tuple:
        cfg = self.cfg
        key = (tuple(shape_a), tuple(shape_b), cfg.k, cfg.partition,
               cfg.sparse, cfg.vectorized, cfg.f, cfg.tol is not None)
        if cfg.sparse:
            # the HE backend's sizes shape Protocol 2's logged traffic
            he = self.he
            key += (getattr(he, "name", type(he).__name__),
                    getattr(he, "ct_bytes", 0), getattr(he, "plain_bits", 0))
        return key

    def _plan_offline_iter(self, shape_a, shape_b):
        """(iter_plan, iter_comm): ONE iteration's TriplePlan plus a CommLog
        of its online traffic (S1/S2/S3, sans CSC) — the tallies the
        compiled fast path replays per launch. Cached across fits by
        (shapes, config key): the dry-run trace dominated the offline phase
        (6.8 of 7.6 s at the reference fit), so a second same-shape fit
        must not pay it again. Returns defensive copies; cached state is
        never handed out mutable."""
        key = self._plan_cache_key(shape_a, shape_b)
        hit = _PLAN_CACHE.get(key)
        if hit is None:
            hit = _PLAN_CACHE[key] = self._trace_iteration(shape_a, shape_b)
        plan, comm = hit
        return TriplePlan(list(plan.requests)), comm.copy()

    def _trace_iteration(self, shape_a, shape_b):
        """Dry-run trace of one Lloyd iteration (+CSC when tol is set) with
        a PlanningDealer on zero-filled inputs."""
        cfg = self.cfg
        ctx = P.Ctx(dealer=PlanningDealer(), log=CommLog(),
                    backend=cfg.backend)
        ctx.vectorized = cfg.vectorized
        enc_a = np.zeros(tuple(shape_a), np.uint64)
        enc_b = np.zeros(tuple(shape_b), np.uint64)
        n = enc_a.shape[0] if cfg.partition == "vertical" \
            else enc_a.shape[0] + enc_b.shape[0]
        d = enc_a.shape[1] + enc_b.shape[1] if cfg.partition == "vertical" \
            else enc_a.shape[1]
        csr_a = CSRMatrix.from_dense(enc_a) if cfg.sparse else None
        csr_b = CSRMatrix.from_dense(enc_b) if cfg.sparse else None
        mu = AShare(jnp.zeros((cfg.k, d), ring.DTYPE),
                    jnp.zeros((cfg.k, d), ring.DTYPE))
        ctx.tag = "S1"
        dist = self._distances(ctx, enc_a, enc_b, csr_a, csr_b, mu)
        ctx.tag = "S2"
        c = P.argmin_onehot(ctx, dist)
        ctx.tag = "S3"
        mu_new = self._update(ctx, enc_a, enc_b, csr_a, csr_b, c, mu, n)
        iter_comm = CommLog()
        iter_comm.merge(ctx.log, phase="online")
        if cfg.tol is not None:
            ctx.tag = "CSC"
            self._converged(ctx, mu, mu_new, cfg.tol)
        return ctx.dealer.plan(), iter_comm

    # ------------------------------------------------------------------ #
    def _init_centroids(self, ctx, rng, x_a, x_b) -> AShare:
        """Jointly negotiated random sample indexes (paper Sec 4.2); each
        party secret-shares its slice of the chosen rows."""
        cfg = self.cfg
        if cfg.partition == "vertical":
            n = x_a.shape[0]
            idx = rng.choice(n, cfg.k, replace=False)
            mu_a = _encode_np(x_a[idx], cfg.f)        # A shares its columns
            mu_b = _encode_np(x_b[idx], cfg.f)
            sh = _share_cat(ctx, rng, [mu_a, mu_b], axis=1)
        else:
            n = x_a.shape[0] + x_b.shape[0]
            idx = rng.choice(n, cfg.k, replace=False)
            mask = idx < x_a.shape[0]
            rows_a = _encode_np(x_a[idx[mask]], cfg.f)
            rows_b = _encode_np(x_b[idx[~mask] - x_a.shape[0]], cfg.f)
            # each party shares its rows (A's draws first); the shares are
            # joined and put back in the jointly-negotiated index order on
            # the host, so the device gets one (k, d) array per share and
            # nothing compiles by how many of the k rows lie in A
            with _trace.span("fit.join", rows_a=len(rows_a),
                             rows_b=len(rows_b)):
                parts = [share_np(rows_a, rng), share_np(rows_b, rng)]
                inv = np.argsort(np.concatenate([np.flatnonzero(mask),
                                                 np.flatnonzero(~mask)]))
                s0, s1 = (np.concatenate(s)[inv] for s in zip(*parts))
            sh = AShare(jnp.asarray(s0), jnp.asarray(s1))
        ctx.log.send(2 * ring.nbytes(sh.shape), tag="init", phase="online")
        return sh

    # ------------------------------------------------------------------ #
    def _distances(self, ctx, enc_a, enc_b, csr_a, csr_b, mu: AShare) -> AShare:
        """F_ESD: D' = U - 2 X mu^T at scale f (one final truncation)."""
        cfg = self.cfg
        k = cfg.k
        # U_j = ||mu_j||^2 : one batched SMUL + row-sum  (scale 2f)
        mu_sq = P.smul(ctx, mu, mu)
        u = AShare(mu_sq.s0.sum(1), mu_sq.s1.sum(1))          # (k,)
        if not cfg.vectorized:
            _naive_extra_rounds(ctx, k * mu.shape[1])
        xmu = self._x_mut(ctx, enc_a, enc_b, csr_a, csr_b, mu)  # (n,k) 2f
        d2 = P.sub(AShare(u.s0[None, :], u.s1[None, :]), P.lshift(xmu, 1))
        return P.trunc(d2, cfg.f)

    def _x_mut(self, ctx, enc_a, enc_b, csr_a, csr_b, mu: AShare) -> AShare:
        """X @ mu^T as shares, splitting local vs joint blocks (Eq. 4/5)."""
        cfg = self.cfg
        mm = ctx.backend.ring_mm
        mut = AShare(mu.s0.T, mu.s1.T)                        # (d, k)
        j1, j2 = self._joint_x_mut(ctx, enc_a, enc_b, csr_a, csr_b, mut)
        if cfg.partition == "vertical":
            da = enc_a.shape[1]
            # local: A's data x A's share slice; B's data x B's share slice
            loc_a = mm(jnp.asarray(enc_a), mut.s0[:da])
            loc_b = mm(jnp.asarray(enc_b), mut.s1[da:])
            return AShare(loc_a + j1.s0 + j2.s0, loc_b + j1.s1 + j2.s1)
        # horizontal: rows split; each party's rows hit BOTH mu shares
        loc_a = mm(jnp.asarray(enc_a), mut.s0)                # A x own share
        loc_b = mm(jnp.asarray(enc_b), mut.s1)
        top = AShare(loc_a + j1.s0, j1.s1)
        bot = AShare(j2.s0, loc_b + j2.s1)
        return AShare(jnp.concatenate([top.s0, bot.s0], 0),
                      jnp.concatenate([top.s1, bot.s1], 0))

    def _joint_x_mut(self, ctx, enc_a, enc_b, csr_a, csr_b,
                     mut: AShare) -> tuple:
        """The two JOINT blocks of X mu^T — A's data x B's share slice and
        vice versa (vertical: column slices of mu^T; horizontal: each
        party's rows x the other's full share). ONE implementation shared
        by the eager `_x_mut` and the fast path's pre-S1 host exchange, so
        both consume the dealer streams identically (the S1 counterpart of
        `_joint_share_times_pub`)."""
        cfg = self.cfg
        if cfg.partition == "vertical":
            da = enc_a.shape[1]
            j1 = self._pub_times_share(ctx, enc_a, csr_a,
                                       AShare(jnp.zeros_like(mut.s1[:da]),
                                              mut.s1[:da]), owner="A")
            j2 = self._pub_times_share(ctx, enc_b, csr_b,
                                       AShare(mut.s0[da:],
                                              jnp.zeros_like(mut.s0[da:])),
                                       owner="B")
            return j1, j2
        j1 = self._pub_times_share(ctx, enc_a, csr_a,
                                   AShare(jnp.zeros_like(mut.s1), mut.s1),
                                   owner="A")                  # A x B's share
        j2 = self._pub_times_share(ctx, enc_b, csr_b,
                                   AShare(mut.s0, jnp.zeros_like(mut.s0)),
                                   owner="B")                  # B x A's share
        return j1, j2

    def _pub_times_share(self, ctx, enc, csr, other_share: AShare,
                         owner: str) -> AShare:
        """One party's plaintext matrix x the OTHER party's share matrix.

        Dense path: Beaver matmul with the plaintext embedded as a degenerate
        share (this is what ships X-sized masked matrices).
        Sparse path: Protocol 2 — nnz-proportional HE compute, X never moves.
        """
        cfg = self.cfg
        if cfg.sparse:
            b_mat = np.asarray(other_share.s1 if owner == "A" else other_share.s0)
            z = secure_sparse_matmul(ctx, csr, b_mat, self.he,
                                     time_model=OU_COST_S)
            return z if owner == "A" else AShare(z.s1, z.s0)
        pub = AShare(jnp.asarray(enc), jnp.zeros_like(jnp.asarray(enc))) \
            if owner == "A" else \
            AShare(jnp.zeros_like(jnp.asarray(enc)), jnp.asarray(enc))
        out = P.smatmul(ctx, pub, other_share)
        if not cfg.vectorized:
            _naive_extra_rounds(ctx, enc.shape[0] * other_share.shape[1])
        return out

    # ------------------------------------------------------------------ #
    def _update(self, ctx, enc_a, enc_b, csr_a, csr_b, c: AShare,
                mu_old: AShare, n: int) -> AShare:
        """F_SCU: mu = C^T X / 1^T C with empty-cluster MUX guard."""
        num = self._ct_x(ctx, enc_a, enc_b, csr_a, csr_b, c)   # (k, d) scale f
        den = AShare(c.s0.sum(0), c.s1.sum(0))                 # (k,) scale 1
        return self._update_final(ctx, num, den, mu_old, n)

    def _update_final(self, ctx, num: AShare, den: AShare, mu_old: AShare,
                      n: int) -> AShare:
        """The S3 tail on (possibly cross-batch accumulated) sums: empty-
        cluster guard + balanced-split division + MUX. ONE implementation
        shared by the eager loop and the minibatch finalize trace, so both
        consume the dealer streams identically (kmeans_step._s3_final_body
        compiles the same algebra)."""
        cfg = self.cfg
        k = cfg.k
        one = AShare(jnp.full((k,), 1, ring.DTYPE), jnp.zeros((k,), ring.DTYPE))
        is_empty = P.cmp_lt(ctx, den, one)                     # [den < 1]
        den_safe = P.mux(ctx, is_empty, one, den)
        # Balanced-split division (see DESIGN.md numerics note): computing
        # num * (2^f/den) naively either loses den*2^-f relative precision
        # (plain reciprocal) or pushes the pre-truncation product to
        # ~2^(2f+m) bits, where SecureML local truncation fails with
        # probability 2^(bits+1-l) — at m=12 that is 2^-7 PER ELEMENT with
        # a +-2^(l-t) error (observed!). Split the 2^m rescale: shift num
        # down by s=m//2 and keep 2^s/den in the reciprocal; the product is
        # (num/2^s)*(2^s/den) = mean at ~2^(2f+4) bits -> failure 2^-19,
        # absolute error <= 2^(m-s)*|x|*2^-f ~ 1e-3.
        m = int(np.ceil(np.log2(max(2, n))))
        s = m // 2
        num_s = P.trunc(num, s)
        r = P.reciprocal(ctx, den_safe, max_den=n, f=cfg.f, extra_bits=s)
        mu_new = P.smul(ctx, num_s, AShare(r.s0[:, None], r.s1[:, None]),
                        trunc_f=cfg.f)
        guard = AShare(is_empty.s0[:, None], is_empty.s1[:, None])
        return P.mux(ctx, guard, mu_old, mu_new)

    def _ct_x(self, ctx, enc_a, enc_b, csr_a, csr_b, c: AShare) -> AShare:
        """C^T X -> (k, d) shares at scale f (C is scale-1 one-hot)."""
        cfg = self.cfg
        ct = AShare(c.s0.T, c.s1.T)                            # (k, n)
        if cfg.partition == "vertical":
            # [C^T X_A | C^T X_B]; each block: share x one party's plaintext
            za = self._share_times_pub(ctx, ct, enc_a, csr_a, owner="A")
            zb = self._share_times_pub(ctx, ct, enc_b, csr_b, owner="B")
            return AShare(jnp.concatenate([za.s0, zb.s0], 1),
                          jnp.concatenate([za.s1, zb.s1], 1))
        na = enc_a.shape[0]
        ct_a = AShare(ct.s0[:, :na], ct.s1[:, :na])
        ct_b = AShare(ct.s0[:, na:], ct.s1[:, na:])
        za = self._share_times_pub(ctx, ct_a, enc_a, csr_a, owner="A")
        zb = self._share_times_pub(ctx, ct_b, enc_b, csr_b, owner="B")
        return P.add(za, zb)

    def _share_times_pub(self, ctx, ct: AShare, enc, csr, owner: str) -> AShare:
        """<C>^T @ X_owner: the owner's share-product is local; the other
        party's requires a joint product (Beaver dense / Protocol 2 sparse,
        via the transpose identity <C>_other^T X = (X^T <C>_other)^T)."""
        cfg = self.cfg
        mm = ctx.backend.ring_mm
        x = jnp.asarray(enc)
        if owner == "A":
            local = mm(ct.s0, x)                               # A local
            if cfg.sparse:
                joint = self._joint_share_times_pub(ctx, ct, csr.transpose(),
                                                    owner="A")
            else:
                joint = P.smatmul(ctx, AShare(jnp.zeros_like(ct.s1), ct.s1),
                                  AShare(x, jnp.zeros_like(x)))
                if not cfg.vectorized:
                    _naive_extra_rounds(ctx, ct.shape[0] * x.shape[1])
            return AShare(local + joint.s0, joint.s1)
        local = mm(ct.s1, x)                                   # B local
        if cfg.sparse:
            joint = self._joint_share_times_pub(ctx, ct, csr.transpose(),
                                                owner="B")
        else:
            joint = P.smatmul(ctx, AShare(ct.s0, jnp.zeros_like(ct.s0)),
                              AShare(jnp.zeros_like(x), x))
            if not cfg.vectorized:
                _naive_extra_rounds(ctx, ct.shape[0] * x.shape[1])
        return AShare(joint.s0, local + joint.s1)

    def _joint_share_times_pub(self, ctx, ct: AShare, csr_t: CSRMatrix,
                               owner: str) -> AShare:
        """The sparse joint block of <C>^T X_owner: Protocol 2 on the pre-
        transposed CSR (transpose identity). ONE implementation shared by
        the eager loop and the fast path's mid-iteration host callback, so
        both consume the owner's dealer mask-seed stream identically —
        that's what makes the split-launch path bit-exact."""
        if owner == "A":
            z = secure_sparse_matmul(ctx, csr_t, np.asarray(ct.s1.T),
                                     self.he, time_model=OU_COST_S)
            return AShare(z.s0.T, z.s1.T)
        z = secure_sparse_matmul(ctx, csr_t, np.asarray(ct.s0.T), self.he,
                                 time_model=OU_COST_S)
        return AShare(z.s1.T, z.s0.T)

    # -- Protocol-2 host exchanges for the split-launch fast path -------- #
    def _s1_he_inputs(self, ctx, enc_a, enc_b, csr_a, csr_b,
                      mu: AShare) -> list:
        """Host exchange #1 (pre-S1): the distance-phase joint products of
        X mu^T, computable from the centroid shares alone. Returns the flat
        [s0, s1, ...] share list the S1 program takes as inputs, in the
        FitGeometry.he_shapes_s1 order."""
        mut = AShare(mu.s0.T, mu.s1.T)
        j1, j2 = self._joint_x_mut(ctx, enc_a, enc_b, csr_a, csr_b, mut)
        return [t for h in (j1, j2) for t in (h.s0, h.s1)]

    def _s3_he_inputs(self, ctx, csr_at, csr_bt, c: AShare) -> list:
        """Host exchange #2 (the S2 callback, post-S1): the update-phase
        joint products of C^T X on the assignment shares the S1 launch just
        produced. Flat share list in FitGeometry.he_shapes_s3 order."""
        cfg = self.cfg
        ct = AShare(c.s0.T, c.s1.T)
        if cfg.partition == "vertical":
            ja = self._joint_share_times_pub(ctx, ct, csr_at, owner="A")
            jb = self._joint_share_times_pub(ctx, ct, csr_bt, owner="B")
        else:
            na = csr_at.shape[1]                 # csr_at is X_A^T: (d, na)
            ct_a = AShare(ct.s0[:, :na], ct.s1[:, :na])
            ct_b = AShare(ct.s0[:, na:], ct.s1[:, na:])
            ja = self._joint_share_times_pub(ctx, ct_a, csr_at, owner="A")
            jb = self._joint_share_times_pub(ctx, ct_b, csr_bt, owner="B")
        return [t for h in (ja, jb) for t in (h.s0, h.s1)]

    # ------------------------------------------------------------------ #
    def _converged(self, ctx, mu_old: AShare, mu_new: AShare, tol: float) -> bool:
        """F_CSC: reveal only CMP(ESD(mu_t, mu_t+1), eps)."""
        diff = P.sub(mu_new, mu_old)
        sq = P.smul(ctx, diff, diff)                           # scale 2f
        tot = AShare(sq.s0.sum(), sq.s1.sum())
        eps = ring.encode(tol, 2 * self.cfg.f).reshape(())
        bit = P.cmp_lt(ctx, tot, AShare(eps, jnp.zeros((), ring.DTYPE)))
        ctx.log.send(8, tag="CSC", phase="online")             # reveal stop bit
        return bool(np.asarray(rec(bit)) == 1)


# ---------------------------------------------------------------------------
# Plaintext oracle (same init, same ESD criterion) + fraud-detection utils
# ---------------------------------------------------------------------------

def plaintext_kmeans(x: np.ndarray, k: int, iters: int, seed: int = 0,
                     tol: float | None = None):
    """Float Lloyd with the same joint-random-row init as SecureKMeans."""
    rng = np.random.default_rng(seed)
    idx = rng.choice(x.shape[0], k, replace=False)
    mu = x[idx].copy()
    labels = np.zeros(x.shape[0], np.int64)
    for _ in range(iters):
        d = (mu ** 2).sum(1)[None, :] - 2 * x @ mu.T           # same D'
        labels = d.argmin(1)
        mu_old = mu.copy()
        for j in range(k):
            m = labels == j
            if m.any():
                mu[j] = x[m].mean(0)
        if tol is not None and ((mu - mu_old) ** 2).sum() < tol:
            break
    return mu, labels


def _minibatch_bounds(partition: str, na: int, nb: int,
                      batch_size: int) -> list:
    """Per-batch row windows [((a_lo, a_hi), (b_lo, b_hi)), ...].

    Vertical: both parties hold column slices of the SAME rows, so batches
    are shared contiguous chunks of `batch_size` rows — at most two
    distinct shapes (full + remainder). Horizontal: each party's rows are
    split into the same NUMBER of contiguous near-equal chunks
    (B = ceil((na+nb)/batch_size), clamped so no chunk is empty); chunk
    sizes differ by at most one per party, so a fit compiles at most a
    handful of batch geometries regardless of batch count."""
    if partition == "vertical":
        bs = max(1, min(int(batch_size), na))
        return [((lo, min(lo + bs, na)),) * 2 for lo in range(0, na, bs)]
    n_batches = max(1, min(-(-(na + nb) // int(batch_size)), na, nb))
    return list(zip(_even_chunks(na, n_batches),
                    _even_chunks(nb, n_batches)))


def _even_chunks(n: int, parts: int) -> list:
    """Exactly `parts` contiguous windows over n rows, sizes q+1 x r then
    q x (parts - r) — never empty for parts <= n."""
    q, r = divmod(n, parts)
    out, lo = [], 0
    for i in range(parts):
        hi = lo + q + (1 if i < r else 0)
        out.append((lo, hi))
        lo = hi
    return out


def _assemble_assignment(partition: str, c_parts: list,
                         batches: list) -> AShare:
    """Stitch the last iteration's per-batch assignment shares back into
    the full-fit (n, k) layout: vertical concatenates rows in batch order;
    horizontal restores the [all A rows; all B rows] order the full-batch
    path produces (each batch's rows come back [A chunk; B chunk])."""
    if partition == "vertical":
        return AShare(jnp.concatenate([p.s0 for p in c_parts], 0),
                      jnp.concatenate([p.s1 for p in c_parts], 0))
    a_rows = tuple(b["a_rows"] for b in batches)
    rows = sum(p.shape[0] for p in c_parts)
    with _trace.span("fit.join", rows_a=sum(a_rows),
                     rows_b=rows - sum(a_rows)):
        return AShare(*_horizontal_order(a_rows, [p.s0 for p in c_parts],
                                         [p.s1 for p in c_parts]))


@functools.partial(jax.jit, static_argnums=0)
def _horizontal_order(a_rows: tuple, s0_parts: list, s1_parts: list):
    """Both shares' per-batch [A chunk; B chunk] rows in [all A rows; all B
    rows] order: one program per batch geometry, no per-batch slices."""
    def join(parts):
        return jnp.concatenate([p[:a] for p, a in zip(parts, a_rows)]
                               + [p[a:] for p, a in zip(parts, a_rows)], 0)
    return join(s0_parts), join(s1_parts)


def _encode_np(x: np.ndarray, f: int) -> np.ndarray:
    return np.round(np.asarray(x, np.float64) * (1 << f)) \
        .astype(np.int64).astype(np.uint64)


def _share_cat(ctx, rng, mats, axis):
    parts = [share(m, rng) for m in mats]
    return AShare(jnp.concatenate([p.s0 for p in parts], axis),
                  jnp.concatenate([p.s1 for p in parts], axis))


def _naive_extra_rounds(ctx, n_interactions: int) -> None:
    """Pre-vectorization accounting: the same payload would be shipped in
    `n_interactions` round-trips instead of 1 (paper Sec 4.2). Bytes are
    already logged by the vectorized op; only rounds differ (+ per-message
    framing overhead which we ignore, making the naive baseline *favorable*)."""
    ctx.log.send(0, tag=ctx.tag, phase="online", rounds=int(n_interactions) - 1)
