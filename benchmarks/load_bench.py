"""Sustained-load + chaos benchmark for the serving plane (DESIGN.md §14).

Two legs, both against the long-lived `ScoringService` (background drain
loop, bounded admission queue, deadlines, `BankReplenisher` daemon):

* **Saturation sweep** — measure the service's closed-loop base rate,
  then offer open-loop request streams at 0.5x / 1x / 2x that rate (the
  2x point is past saturation by construction). Each row reports offered
  vs achieved request rate, p50/p99 submit-to-publish latency, shed rate
  (admission-control rejections), expired deadlines, max queue depth,
  and replenish-stall occupancy (hot-path synchronous stock-out seconds
  as a fraction of the run, with the daemon's off-path top-ups next to
  it).
* **Chaos wire leg** — a real `serve_kmeans --serve-port` server process
  under a seeded `FaultyTransport` (drop/dup/delay) is killed with
  os._exit right after its 3rd journaled response and restarted on the
  same port/checkpoint; the client's rid-pinned retries must get every
  request answered exactly once, bit-exact vs a fault-free direct run.

Writes benchmarks/BENCH_load.json; wired as
`python -m benchmarks.run --only load --quick` (the per-PR smoke).
"""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import time

import numpy as np

from benchmarks.common import make_blobs
from repro.core.channel import FaultyTransport, SocketTransport, session_key
from repro.core.kmeans import KMeansConfig, SecureKMeans
from repro.serve import ScoringClient, ScoringResponse, ScoringService

BENCH_PATH = os.path.join(os.path.dirname(__file__), "BENCH_load.json")


def _fit(n_train: int, d: int, k: int, seed: int = 3):
    d_a = d // 2
    x = make_blobs(n_train, d, k, seed=4)
    km = SecureKMeans(KMeansConfig(k=k, iters=2, seed=seed,
                                   offline="pooled"))
    km.fit(x[:, :d_a], x[:, d_a:])
    return km, d_a


def _stream(n_requests: int, rows: int, d: int, k: int, d_a: int):
    arr = make_blobs(n_requests * rows, d, k, seed=11)
    return [(arr[i * rows:(i + 1) * rows, :d_a],
             arr[i * rows:(i + 1) * rows, d_a:]) for i in range(n_requests)]


def _service(km, d_a, d, *, ladder, copies, **kw):
    return ScoringService(km, ladder=ladder, with_scores=True,
                          d_a=d_a, d_b=d - d_a, provision_copies=copies,
                          **kw)


def _closed_loop_rate(km, d_a, d, ladder, batches, copies) -> float:
    """Base throughput: one request at a time, no think time."""
    svc = _service(km, d_a, d, ladder=ladder, copies=copies)
    svc.warm()
    t0 = time.perf_counter()
    for xa, xb in batches:
        svc.submit(xa, xb)
        svc.drain()
    return len(batches) / (time.perf_counter() - t0)


def _open_loop_row(km, d_a, d, ladder, batches, copies, offered_rps,
                   max_queue) -> dict:
    svc = _service(km, d_a, d, ladder=ladder, copies=copies,
                   max_queue=max_queue, default_deadline_s=30.0,
                   replenisher={"low_water": 1, "high_water": 3,
                                "poll_s": 0.001})
    svc.warm()
    bank_stall0 = svc.bank.replenish_seconds
    svc.start()
    t0 = time.perf_counter()
    admitted, shed = [], 0
    for i, (xa, xb) in enumerate(batches):
        lag = t0 + i / offered_rps - time.perf_counter()
        if lag > 0:
            time.sleep(lag)
        r = svc.submit(xa, xb)
        if isinstance(r, ScoringResponse):
            shed += 1                       # admission-control rejection
        else:
            admitted.append(r)
    answered = 0
    expired = 0
    for rid in admitted:
        resp = svc.response(rid, timeout=300)
        assert resp is not None, f"rid {rid} never answered"
        if resp.error is None:
            answered += 1
        elif resp.error.startswith("DeadlineExceeded"):
            expired += 1
    wall = time.perf_counter() - t0
    svc.close()
    st = svc.stats
    return {
        "leg": "open_loop",
        "offered_rps": round(offered_rps, 2),
        "achieved_rps": round(answered / wall, 2),
        "n_requests": len(batches), "answered": answered,
        "shed": shed, "shed_rate": round(shed / len(batches), 3),
        "expired": expired,
        "p50_ms": st.as_dict()["p50_ms"], "p99_ms": st.as_dict()["p99_ms"],
        "queue_max": st.max_queue_depth,
        "replenish_occupancy": round(
            (svc.bank.replenish_seconds - bank_stall0) / max(wall, 1e-9),
            4),
        "daemon_topups": svc.replenisher.topups,
        "daemon_topup_s": round(svc.replenisher.topup_seconds, 4),
        "wall_s": round(wall, 3),
    }


# ---------------------------------------------------------------------------
# chaos wire leg
# ---------------------------------------------------------------------------

def _spawn_server(args, env):
    from repro.launch.supervisor import ensure_chip_free
    ensure_chip_free("load bench server")
    p = subprocess.Popen(
        [sys.executable, "-m", "repro.launch.serve_kmeans"] + args,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env)
    for line in p.stdout:
        m = re.match(r"SERVING (\d+)", line)
        if m:
            return p, int(m.group(1))
    raise RuntimeError(f"server died before SERVING: rc={p.wait()}")


def _chaos_row(tmp_dir: str, n_requests: int = 6) -> dict:
    import tempfile
    from repro.core.fraud import FraudDataset

    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    env["PYTHONPATH"] = os.path.abspath(src) + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    ck = os.path.join(tmp_dir, "ck")
    base = ["--n-train", "200", "--d-a", "4", "--d-b", "4", "--k", "3",
            "--iters", "2", "--rungs", "16", "--serve-checkpoint-dir", ck,
            "--auth-key", "bench", "--provision-copies",
            str(2 * n_requests), "--idle-timeout", "120", "--seed", "0"]
    t_start = time.perf_counter()
    p, port = _spawn_server(base + ["--serve-port", "0",
                                    "--die-after-responses", "3"], env)
    arr = FraudDataset.synthesize(n=8 * n_requests, d_a=4, d_b=4,
                                  n_clusters=3, seed=3)
    batches = [(arr.x_a[i * 8:(i + 1) * 8], arr.x_b[i * 8:(i + 1) * 8])
               for i in range(n_requests)]
    t = SocketTransport("connect", port=port, io_timeout_s=5.0)
    ft = FaultyTransport(t, seed=11, drop=0.05, dup=0.05, delay_s=0.002)
    client = ScoringClient(ft, auth_key=session_key("bench"),
                           deadline_s=10.0, waves=2, retry_wait_s=0.2)
    got = {}
    restarts = 0
    try:
        for i, (xa, xb) in enumerate(batches):
            while True:
                try:
                    got[i] = client.score(xa, xb, rid=i)
                    break
                except Exception:
                    if restarts:
                        raise
                    p.wait(timeout=60)
                    p.stdout.read()
                    p, _port = _spawn_server(
                        base + ["--serve-port", str(port)], env)
                    restarts += 1
        client.bye()
    finally:
        t.close()
        try:
            p.stdout.read()
            p.wait(timeout=60)
        except Exception:
            p.kill()
    wall = time.perf_counter() - t_start

    # fault-free direct reference: same deterministic fit/seeds
    km = SecureKMeans(KMeansConfig(k=3, iters=2, seed=0, offline="pooled"))
    ds = FraudDataset.synthesize(n=200, d_a=4, d_b=4, n_clusters=3, seed=0)
    res = km.fit(ds.x_a, ds.x_b)
    ref_svc = ScoringService(km, res, rungs=(16,), d_a=4, d_b=4,
                             with_scores=True,
                             provision_copies=2 * n_requests)
    ref = {}
    for xa, xb in batches:
        ref_svc.submit(xa, xb)
        ref.update({r.request_id: r for r in ref_svc.drain()})
    lost = sum(1 for i in range(n_requests) if i not in got)
    bit_exact = all(
        got[i].error is None
        and np.array_equal(got[i].labels, ref[i].labels)
        and np.array_equal(got[i].scores, ref[i].scores)
        for i in got)
    assert lost == 0 and len(got) == n_requests, "lost/dup responses"
    assert restarts == 1, "kill/restart never exercised"
    assert bit_exact, "chaos responses diverged from fault-free run"
    return {"leg": "chaos_wire", "n_requests": n_requests,
            "restarts": restarts, "lost": lost,
            "bit_exact": bool(bit_exact),
            "faults": {"dropped": ft.faults.dropped,
                       "duplicated": ft.faults.duplicated,
                       "delayed": ft.faults.delayed},
            "wall_s": round(wall, 3)}


def run(quick: bool = False):
    import tempfile
    if quick:
        kw = dict(n_train=256, d=8, k=3, ladder=(16,), rows=8,
                  n_requests=24, copies=8, max_queue=4)
    else:
        kw = dict(n_train=1024, d=16, k=5, ladder=(32, 128), rows=24,
                  n_requests=64, copies=16, max_queue=8)
    # the kill/restart leg first: its server needs the chip, which this
    # process holds once the in-process legs below have touched JAX
    with tempfile.TemporaryDirectory() as td:
        chaos = _chaos_row(td, n_requests=6)
    km, d_a = _fit(kw["n_train"], kw["d"], kw["k"])
    batches = _stream(kw["n_requests"], kw["rows"], kw["d"], kw["k"], d_a)
    base = _closed_loop_rate(km, d_a, kw["d"], kw["ladder"],
                             batches[:max(8, kw["n_requests"] // 4)],
                             kw["copies"])
    rows = [{"leg": "closed_loop_base", "base_rps": round(base, 2),
             "ladder": list(kw["ladder"]), "rows_per_request": kw["rows"]}]
    for mult in (0.5, 1.0, 2.0):        # 2x is past saturation
        rows.append(_open_loop_row(km, d_a, kw["d"], kw["ladder"], batches,
                                   kw["copies"], mult * base,
                                   kw["max_queue"]))
    rows.append(chaos)
    with open(BENCH_PATH, "w") as f:
        json.dump({"rows": rows,
                   "note": "Serving-plane load + chaos: open-loop offered "
                           "rates at 0.5x/1x/2x the measured closed-loop "
                           "base (2x past saturation; shed_rate is "
                           "admission-control rejections at max_queue, "
                           "replenish_occupancy the hot-path stock-out "
                           "stall fraction with the BankReplenisher "
                           "daemon's top-ups beside it), plus a two-"
                           "process kill/restart chaos leg asserting "
                           "exactly-once bit-exact responses."},
                  f, indent=1)
    return rows


def derived(rows):
    """Headline: achieved req/s at the past-saturation (2x) offered load."""
    sat = [r for r in rows if r.get("leg") == "open_loop"]
    return sat[-1]["achieved_rps"] if sat else ""
