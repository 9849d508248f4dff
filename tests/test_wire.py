"""Transport, reliability, and fault-injection tests (DESIGN.md §13).

Load-bearing properties:
* the frame codec round-trips arbitrary payloads, survives arbitrarily
  split reads, and CRC-rejects bit flips without desyncing;
* `ReliableChannel` + `Responder` give exactly-once EFFECT over an
  at-least-once wire: drops, duplicates, corruption, and a severed
  connection all collapse to "resend until the response lands", with the
  responder's seq dedup preventing double handling;
* a fit run over a fault-injected wire produces IDENTICAL shares, dealer
  counters, and CommLog tallies to the clean in-process fit — the chaos
  only costs wall-clock;
* a real two-process fit over TCP (launch/two_party.py) is bit-exact
  against the in-process reference on every partition × sparsity combo;
* `NetModel.time_estimate` predicts the measured wall of a latency-
  injected exchange within tolerance;
* `CommLog` tallies stay exact under concurrent writers.
"""
import json
import os
import struct
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st
from repro.core.channel import (CommLog, FaultyTransport, FrameCorrupt,
                                FrameDecoder, FrameError, LoopbackTransport,
                                NetModel, ReliableChannel, Responder,
                                SocketTransport, T_BLOB, T_EXCHANGE,
                                WireSession, WireTimeout, decode_frame,
                                encode_frame, serve_peer)
from repro.core.kmeans import KMeansConfig, SecureKMeans


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


def _wired_pair(**chan_kw):
    """Loopback engine channel + responder thread; returns
    (WireSession, engine transport, responder transport, thread)."""
    ta, tb = LoopbackTransport.pair()
    th = threading.Thread(target=serve_peer, args=(tb,),
                          kwargs={"idle_timeout_s": 60.0}, daemon=True)
    th.start()
    return WireSession(ReliableChannel(ta, **chan_kw)), ta, tb, th


# ---------------------------------------------------------------------------
# frame codec
# ---------------------------------------------------------------------------

@settings(deadline=None, max_examples=50)
@given(st.integers(0, 127), st.integers(0, 2**63), st.integers(0, 4096))
def test_frame_roundtrip(ftype, seq, size):
    payload = bytes((i * 131 + 7) % 256 for i in range(size))
    ft, sq, pl = decode_frame(encode_frame(ftype, seq, payload))
    assert (ft, sq, pl) == (ftype, seq, payload)


@settings(deadline=None, max_examples=25)
@given(st.integers(1, 8), st.integers(1, 997), st.integers(0, 2**31))
def test_frame_decoder_split_reads(n_frames, chunk, seed):
    """Any chunking of the byte stream yields the same frame sequence."""
    rng = np.random.default_rng(seed)
    frames = [(i % 5 + 1, i, rng.bytes(int(rng.integers(0, 600))))
              for i in range(n_frames)]
    stream = b"".join(encode_frame(*f) for f in frames)
    dec = FrameDecoder()
    got = []
    for lo in range(0, len(stream), chunk):
        got.extend(dec.feed(stream[lo:lo + chunk]))
    assert got == frames
    assert dec.pending() == 0 and dec.crc_errors == 0


def test_frame_decoder_drops_corrupt_keeps_stream():
    a = encode_frame(T_EXCHANGE, 1, b"hello world")
    b = encode_frame(T_EXCHANGE, 2, b"intact")
    bad = bytearray(a)
    bad[-3] ^= 0x40                     # flip a payload bit: CRC catches it
    dec = FrameDecoder()
    got = dec.feed(bytes(bad) + b)
    assert got == [(T_EXCHANGE, 2, b"intact")]
    assert dec.crc_errors == 1


def test_frame_decoder_bad_magic_raises():
    dec = FrameDecoder()
    with pytest.raises(FrameError):
        dec.feed(b"\x00" * 64)


def test_decode_frame_rejects_truncation():
    f = encode_frame(T_BLOB, 9, b"payload!")
    with pytest.raises(FrameError):
        decode_frame(f[:10])
    with pytest.raises(FrameCorrupt):
        decode_frame(f[:-2])


# ---------------------------------------------------------------------------
# reliability: retries, dedup, heartbeat
# ---------------------------------------------------------------------------

def test_exactly_once_effect_under_drop_dup_corrupt():
    """Chaos on BOTH directions; every request's handler still runs exactly
    once and every exchange completes with the exact byte count."""
    ta, tb = LoopbackTransport.pair()
    fa = FaultyTransport(ta, seed=3, drop=0.15, dup=0.15, corrupt=0.1)
    fb = FaultyTransport(tb, seed=4, drop=0.1, dup=0.1, corrupt=0.1)
    calls = []

    def handler(ftype, payload):
        if ftype == T_EXCHANGE:
            (b_len,) = struct.unpack_from(">I", payload)
            calls.append(b_len)
            return bytes(b_len)
        return b""

    resp = Responder(fb, handler, idle_timeout_s=30.0)
    th = threading.Thread(target=resp.serve_forever, daemon=True)
    th.start()
    ws = WireSession(ReliableChannel(fa, try_timeout_s=0.05,
                                     backoff_s=0.002, max_retries=200,
                                     deadline_s=30.0))
    for i in range(30):
        assert ws.exchange(101 + i, rounds=1) == 101 + i
    ws.bye()
    th.join(timeout=10)
    assert not th.is_alive()
    # exactly-once effect: one handler call per exchange, in seq order
    assert calls == [(101 + i) - (101 + i + 1) // 2 for i in range(30)]
    # and the chaos actually happened
    f = fa.faults
    assert f.dropped + f.duplicated + f.corrupted > 0
    assert resp.dedup_replays + resp.crc_drops + resp.stale_drops > 0


def test_sever_reconnect_mid_session():
    ta, tb = LoopbackTransport.pair()
    fa = FaultyTransport(ta, sever_at=(4,))
    resp_holder = {}

    def run():
        resp_holder["r"] = serve_peer(tb, idle_timeout_s=30.0)

    th = threading.Thread(target=run, daemon=True)
    th.start()
    ws = WireSession(ReliableChannel(fa, try_timeout_s=0.05,
                                     backoff_s=0.002, deadline_s=30.0,
                                     max_retries=100))
    for _ in range(8):
        ws.exchange(64, rounds=1)
    ws.bye()
    th.join(timeout=10)
    assert not th.is_alive()
    assert fa.faults.severed == 1
    assert ws.chan.reconnects >= 1
    assert ws.payload_bytes == 8 * 64


def test_responder_dead_engine_times_out_not_livelocks():
    """Engine gone for good: the responder's failed redials must count
    against the idle budget and surface as WireTimeout — NOT loop forever
    in reconnect (recv raises ConnectionError, the lazy redial inside the
    next recv fails with ConnectionError too)."""
    srv = SocketTransport("listen", port=0, io_timeout_s=2.0)
    port = srv.port
    cli = SocketTransport("connect", port=port, io_timeout_s=2.0,
                          connect_retries=1, backoff_s=0.01,
                          backoff_max_s=0.05)
    out = {}

    def run():
        try:
            serve_peer(cli, idle_timeout_s=1.5)
        except WireTimeout as e:
            out["err"] = e

    th = threading.Thread(target=run, daemon=True)
    th.start()
    # accept, then kill the engine end entirely (socket AND listener)
    srv._ensure()
    srv.close()
    th.join(timeout=30)
    assert not th.is_alive(), "responder livelocked on a dead engine"
    assert "err" in out, "responder exited without WireTimeout"


def test_heartbeat_keeps_idle_responder_alive():
    ws, _ta, _tb, th = _wired_pair()
    for _ in range(3):
        ws.heartbeat()
        time.sleep(0.01)
    ws.exchange(32, rounds=1)
    ws.bye()
    th.join(timeout=5)
    assert not th.is_alive()


def test_blob_roundtrip_ships_arrays():
    ta, tb = LoopbackTransport.pair()

    def on_blob(meta, arrays):
        assert meta["op"] == "double"
        return {"ok": True}, {"y": arrays["x"] * 2}

    th = threading.Thread(target=serve_peer, args=(tb,),
                          kwargs={"on_blob": on_blob,
                                  "idle_timeout_s": 30.0}, daemon=True)
    th.start()
    ws = WireSession(ReliableChannel(ta))
    x = np.arange(12, dtype=np.uint64).reshape(3, 4)
    meta, arrays = ws.send_arrays({"op": "double"}, {"x": x})
    assert meta == {"ok": True}
    np.testing.assert_array_equal(arrays["y"], x * 2)
    ws.bye()
    th.join(timeout=5)


# ---------------------------------------------------------------------------
# CommLog thread safety (the wire made it load-bearing)
# ---------------------------------------------------------------------------

def test_commlog_concurrent_tallies_exact():
    log = CommLog()
    n_threads, n_sends = 8, 500

    def worker(i):
        for _ in range(n_sends):
            log.send(3, tag=f"t{i % 2}", phase="online", rounds=1)

    ts = [threading.Thread(target=worker, args=(i,))
          for i in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert log.total_bytes("online") == 3 * n_threads * n_sends
    assert log.total_rounds("online") == n_threads * n_sends


def test_commlog_concurrent_merges_exact():
    src = CommLog()
    src.send(7, tag="x", phase="online", rounds=2)
    dst = CommLog()
    n_threads, n_merges = 8, 200

    def worker():
        for _ in range(n_merges):
            dst.merge(src, phase="online")

    ts = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert dst.total_bytes("online") == 7 * n_threads * n_merges
    assert dst.total_rounds("online") == 2 * n_threads * n_merges


# ---------------------------------------------------------------------------
# the chaos acceptance test: a faulted fit is bit-exact with the clean one
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("partition,sparse",
                         [("vertical", False), ("horizontal", True)])
def test_chaos_fit_bit_exact(partition, sparse):
    """Seeded drops + delays + duplicates + corruption + one severed
    connection: the wired fit terminates with shares, counters, and
    tallies identical to the clean in-process run."""
    n, d, k = 48, 4, 2
    x = _blobs(n, d, k, seed=11, sparse_frac=0.5 if sparse else 0.0)
    a, b = _split(x, partition)
    cfg = KMeansConfig(k=k, iters=2, partition=partition, sparse=sparse,
                       seed=5, backend="xla")
    r_clean = SecureKMeans(cfg).fit(a, b)

    ta, tb = LoopbackTransport.pair()
    fa = FaultyTransport(ta, seed=13, drop=0.05, dup=0.05, corrupt=0.05,
                         delay_s=0.0005, sever_at=(25,))
    fb = FaultyTransport(tb, seed=14, drop=0.03, dup=0.03, corrupt=0.03)
    th = threading.Thread(target=serve_peer, args=(fb,),
                          kwargs={"idle_timeout_s": 60.0}, daemon=True)
    th.start()
    ws = WireSession(ReliableChannel(fa, try_timeout_s=0.05,
                                     backoff_s=0.002, max_retries=500,
                                     deadline_s=120.0))
    r_chaos = SecureKMeans(cfg).fit(a, b, wire=ws)
    ws.bye()
    th.join(timeout=30)
    assert not th.is_alive()
    _assert_same_fit(r_clean, r_chaos)
    f = fa.faults
    assert f.severed == 1 and f.dropped + f.duplicated + f.corrupted > 0


def test_wired_fit_pays_the_modelled_traffic():
    """The wire's shipped payload bytes equal the CommLog's online tally —
    the accounting IS the traffic, not an estimate of it."""
    x = _blobs(48, 4, 2, seed=11)
    a, b = _split(x, "vertical")
    cfg = KMeansConfig(k=2, iters=2, partition="vertical", seed=5,
                       backend="xla")
    ws, _ta, _tb, th = _wired_pair()
    r = SecureKMeans(cfg).fit(a, b, wire=ws)
    ws.bye()
    th.join(timeout=10)
    assert ws.payload_bytes == r.log.total_bytes("online")
    assert ws.rounds == r.log.total_rounds("online")


# ---------------------------------------------------------------------------
# NetModel pin: prediction vs measured wall under injected latency
# ---------------------------------------------------------------------------

def test_netmodel_time_estimate_matches_measured_wall():
    net = NetModel("emul", 1e12, 0.02)     # latency-dominated on purpose
    ta, tb = LoopbackTransport.pair()
    fa = FaultyTransport.emulate(ta, net)
    fb = FaultyTransport.emulate(tb, net)
    th = threading.Thread(target=serve_peer, args=(fb,),
                          kwargs={"idle_timeout_s": 30.0}, daemon=True)
    th.start()
    ws = WireSession(ReliableChannel(fa, try_timeout_s=5.0))
    log = CommLog()
    log.wire = ws
    nbytes, rounds = 4096, 8
    t0 = time.perf_counter()
    log.send(nbytes, tag="pin", phase="online", rounds=rounds)
    wall = time.perf_counter() - t0
    ws.bye()
    th.join(timeout=10)
    predicted = log.time_estimate(net, "online")
    assert predicted == net.time_s(nbytes, rounds)
    # sleep-based emulation only ever overshoots; allow generous headroom
    # above (scheduler) and a small floor below (nothing to undershoot by)
    assert 0.8 * predicted <= wall <= 3.0 * predicted + 0.25, \
        (predicted, wall)


# ---------------------------------------------------------------------------
# two real processes over TCP — the deployment acceptance test
# ---------------------------------------------------------------------------

def _run_two_party(extra_a, extra_b=(), timeout=600):
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    env["PYTHONPATH"] = os.path.abspath(src) + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    a = subprocess.Popen(
        [sys.executable, "-m", "repro.launch.two_party", "--role", "A",
         "--port", "0"] + list(extra_a),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env)
    line = a.stdout.readline()
    assert line.startswith("LISTENING "), line
    port = int(line.split()[1])
    b = subprocess.Popen(
        [sys.executable, "-m", "repro.launch.two_party", "--role", "B",
         "--port", str(port)] + list(extra_b),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env)
    a_out = a.communicate(timeout=timeout)[0]
    try:
        b_out = b.communicate(timeout=60)[0]
    except subprocess.TimeoutExpired:
        b.kill()
        b_out = b.communicate()[0]
    return a.returncode, a_out, b.returncode, b_out


@pytest.mark.parametrize("partition", ["vertical", "horizontal"])
@pytest.mark.parametrize("sparse_frac", [0.0, 0.5])
def test_two_process_socket_fit_bit_exact(tmp_path, partition, sparse_frac):
    """Party A and party B as REAL processes over TCP: shares, dealer
    counters, and online tallies equal the in-process reference."""
    out = str(tmp_path / "a.npz")
    rc_a, a_out, rc_b, b_out = _run_two_party(
        ["--out", out, "--partition", partition,
         "--sparse-frac", str(sparse_frac)],
        ["--partition", partition, "--sparse-frac", str(sparse_frac)])
    assert rc_a == 0, a_out
    assert rc_b == 0, b_out

    from repro.launch.two_party import make_data, split_data
    x = make_data(48, 4, 2, 5, sparse_frac)
    xa, xb = split_data(x, partition)
    cfg = KMeansConfig(k=2, iters=2, seed=5, partition=partition,
                       sparse=sparse_frac > 0, backend="xla")
    km = SecureKMeans(cfg)
    res = km.fit(xa, xb)
    arr = make_data(16, 4, 2, 6, sparse_frac)
    pa, pb = split_data(arr, partition)
    pred = km.predict(pa, pb)

    z = np.load(out)
    meta = json.loads(bytes(z["meta"]))
    np.testing.assert_array_equal(
        z["mu0"], np.asarray(res.centroids.s0, np.uint64))
    np.testing.assert_array_equal(
        z["mu1"], np.asarray(res.centroids.s1, np.uint64))
    np.testing.assert_array_equal(
        z["c0"], np.asarray(res.assignment.s0, np.uint64))
    np.testing.assert_array_equal(
        z["c1"], np.asarray(res.assignment.s1, np.uint64))
    np.testing.assert_array_equal(
        z["p0"], np.asarray(pred.assignment.s0, np.uint64))
    np.testing.assert_array_equal(
        z["p1"], np.asarray(pred.assignment.s1, np.uint64))
    assert meta["counters"] == {attr: int(getattr(res.dealer, attr))
                                for attr in ("n_matmul", "n_mul", "n_bin")}
    ref_online = {t: [int(v[0]), int(v[1])]
                  for t, v in res.log.by_tag("online").items()}
    assert meta["fit_online"] == ref_online
    # the wire carried exactly the modelled fit+predict traffic
    pred_online = res.log.total_bytes("online") \
        + pred.log.total_bytes("online")
    assert meta["wire_payload_bytes"] == pred_online


def test_socket_transport_port_zero_and_reconnect():
    """Socket specifics the loopback can't exercise: ephemeral port pickup
    and a reconnect after the server drops the connection."""
    srv = SocketTransport("listen", port=0, io_timeout_s=10.0)
    assert srv.port > 0
    cli = SocketTransport("connect", port=srv.port, io_timeout_s=10.0)
    done = {}

    def server():
        f = srv.recv_frame(10.0)
        srv.send_frame(f)               # echo 1
        srv.reconnect()                 # drop the conn; re-accept lazily
        f = srv.recv_frame(10.0)
        srv.send_frame(f)               # echo 2 on the new conn
        done["ok"] = True

    th = threading.Thread(target=server, daemon=True)
    th.start()
    f1 = encode_frame(T_EXCHANGE, 0, b"one")
    cli.send_frame(f1)
    assert cli.recv_frame(10.0) == f1
    # server tore the connection down; client sees it and reconnects
    f2 = encode_frame(T_EXCHANGE, 1, b"two")
    for _ in range(20):
        try:
            cli.send_frame(f2)
            got = cli.recv_frame(10.0)
            break
        except (ConnectionError, TimeoutError):
            cli.reconnect()
            time.sleep(0.05)
    else:
        pytest.fail("client never re-established the connection")
    assert got == f2
    th.join(timeout=10)
    assert done.get("ok")
    cli.close()
    srv.close()
