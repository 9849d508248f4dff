"""Chaos matrix for the self-healing two-party runtime (DESIGN.md §16).

Every cell runs the SAME deterministic two-party fit (party A = engine,
party B = wire peer) under `launch/supervisor.py`, kills one or both
parties at a named protocol seam (`core/faultpoints.py`), optionally
overlays a wire-fault mix, and asserts the run still converges to the
UNKILLED fit's exact bytes:

* kill-points — fit.exchange1, fit.mid_s1, fit.s2_callback,
  fit.s3_partial, fit.finalize, fit.publish (party B is killed inside
  its serve loop, `wire.serve:K`, with K spread across the run);
* victims — A, B, or both;
* fault mixes — sever (scripted connection tears), drop+dup, corrupt
  (all CRC-recoverable; injected on incarnation 0 only, like the kills,
  so a restart doesn't re-die at the same seam forever).

Convergence is byte-exact: the six share arrays in A's --out npz
(mu0/mu1/c0/c1/p0/p1) plus the dealer counters and per-phase online
tallies must equal the clean reference run's. (Transport-level frame
counts legitimately differ across incarnations and are reported, not
compared.) Each row also reports MTTR — mean seconds from a death to
the next incarnation's readiness — and retry amplification: total
frames A sent across ALL incarnations (WIRE_STATS lines from survivors
+ the DYING line's stats from killed ones) over the clean run's frames.

Writes benchmarks/BENCH_chaos.json. Default is the 18-cell rotating
matrix; `--full` runs all 6x3x3 = 54 cells; `--quick` is the 3-cell CI
smoke (kill A mid-iteration, kill B at publish time, sever the resume
handshake itself), wired as
`python -m benchmarks.run --only chaos --quick`.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import statistics
import sys
import tempfile
import time

import numpy as np

BENCH_PATH = os.path.join(os.path.dirname(__file__), "BENCH_chaos.json")

KILL_POINTS = ("fit.exchange1", "fit.mid_s1", "fit.s2_callback",
               "fit.s3_partial", "fit.finalize", "fit.publish")
VICTIMS = ("A", "B", "both")
FAULT_NAMES = ("sever", "drop_dup", "corrupt")

# nth hit per A-side kill-point: batch-loop seams die in iteration 2
# (so a published checkpoint exists to resume from); per-iteration
# seams on their 2nd hit
A_NTH = {"fit.exchange1": 4, "fit.mid_s1": 4, "fit.s2_callback": 4,
         "fit.s3_partial": 4, "fit.finalize": 2, "fit.publish": 2}

# the shared tiny workload: 3 iterations x 3 minibatches, sequential
# executor (mid-iteration checkpoints are only legal there), pooled
# offline so restarts don't pay a cold dealer
FIT_ARGS = ["--n", "48", "--d", "4", "--k", "2", "--iters", "3",
            "--seed", "5", "--batch-size", "16", "--no-pipeline",
            "--offline", "pooled", "--checkpoint-every", "1",
            "--io-timeout", "120", "--peer-wait", "60"]


def _fault_flags(fault: str, seed: int) -> list[str]:
    if fault == "sever":
        return ["--fault-sever-at", "3,9"]
    if fault == "sever_handshake":
        # tear A's very first sends — the incarnation hello and the
        # resume negotiation ride frames 0..2
        return ["--fault-sever-at", "0,2"]
    if fault == "drop_dup":
        return ["--fault-drop", "0.03", "--fault-dup", "0.03",
                "--fault-seed", str(seed)]
    if fault == "corrupt":
        return ["--fault-corrupt", "0.03", "--fault-seed", str(seed)]
    return []


def _load_result(path: str):
    with np.load(path) as z:
        arrays = {k: z[k].copy()
                  for k in ("mu0", "mu1", "c0", "c1", "p0", "p1")}
        meta = json.loads(bytes(z["meta"]).decode())
    return arrays, meta


def _parse_stats(lines: list[str], role: str) -> list[dict]:
    """Every per-incarnation traffic dict a child printed: WIRE_STATS
    from incarnations that exited cleanly, the DYING line's stats= from
    killed ones."""
    out = []
    for line in lines:
        m = re.search(r"(?:WIRE_STATS\s+|\bstats=)(\{.*\})\s*$", line)
        if m:
            try:
                d = json.loads(m.group(1))
            except ValueError:
                continue
            if d.get("role") == role:
                out.append(d)
    return out


def _total(stats: list[dict], key: str) -> int:
    return sum(int(d.get(key, 0)) for d in stats)


def _cell(point, victim, fault, *, b_nth=6, fault_seed=0,
          timeout_s=300.0) -> dict:
    """One supervised two-party run; returns outputs + the timeline."""
    from repro.launch.supervisor import (RestartPolicy, SupervisedChild,
                                         child_env, free_port, python_argv)

    base_dir = os.environ.get("CHAOS_DIR") or None
    if base_dir:
        os.makedirs(base_dir, exist_ok=True)    # CI artifact collection
    td = tempfile.mkdtemp(prefix="chaos_", dir=base_dir)
    port = free_port()
    out_npz = os.path.join(td, "a.npz")
    a_base = ["--role", "A", "--port", str(port), *FIT_ARGS,
              "--out", out_npz,
              "--checkpoint-dir", os.path.join(td, "ck"), "--auto-resume"]
    if os.environ.get("CHAOS_TRACE"):
        # Perfetto trace from A's final (surviving) incarnation
        a_base += ["--trace-out", os.path.join(td, "trace_A.json")]
    b_base = ["--role", "B", "--port", str(port),
              "--io-timeout", "120", "--peer-wait", "60",
              "--state-dir", os.path.join(td, "bstate")]
    a_inc0, b_inc0 = [], []
    if victim in ("A", "both") and point:
        a_inc0 += ["--die-at", f"{point}:{A_NTH[point]}"]
    if victim in ("B", "both"):
        b_inc0 += ["--die-at", f"wire.serve:{b_nth}"]
    a_inc0 += _fault_flags(fault, fault_seed)

    def _argv_for(base, inc0):
        # kills and faults ride incarnation 0 only: a restarted party
        # runs clean and finishes the job
        def f(incarnation):
            extra = inc0 if incarnation == 0 else []
            return python_argv("repro.launch.two_party", *base, *extra)
        return f

    env = child_env()
    src = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                       os.pardir, "src"))
    env["PYTHONPATH"] = src + (os.pathsep + env["PYTHONPATH"]
                               if env.get("PYTHONPATH") else "")
    policy = RestartPolicy(max_restarts=5, backoff_s=0.05,
                           backoff_max_s=0.5)
    a = SupervisedChild("A", _argv_for(a_base, a_inc0), policy=policy,
                        terminal_codes=(0, 4), env=env,
                        ready_pattern=r"^LISTENING ",
                        log_path=os.path.join(td, "supervisor_A.log"))
    # B is a pure wire peer: pinned off the device, so only A needs the chip
    b = SupervisedChild("B", _argv_for(b_base, b_inc0), policy=policy,
                        terminal_codes=(0, 4), env=env,
                        log_path=os.path.join(td, "supervisor_B.log"),
                        needs_device=False)
    t0 = time.perf_counter()
    a.start()
    deadline = time.monotonic() + 60.0
    while time.monotonic() < deadline:       # B dials a bound port only
        if any("LISTENING" in line for line in a.lines):
            break
        if a.wait(0.0):                      # A already terminal: report
            break
        time.sleep(0.02)
    b.start()
    ok = a.wait(timeout_s) and b.wait(timeout_s)
    if not ok:
        a.stop()
        b.stop()
        raise RuntimeError(
            f"cell {point}/{victim}/{fault} hung past {timeout_s}s;\n"
            f"A tail:\n{a.tail()}\nB tail:\n{b.tail()}")
    wall = time.perf_counter() - t0
    return {"a": a, "b": b, "out_npz": out_npz, "wall": wall, "dir": td}


def _row(point, victim, fault, cell, clean) -> dict:
    a, b = cell["a"], cell["b"]
    tails = f"\nA tail:\n{a.tail()}\nB tail:\n{b.tail()}"
    name = f"{point or 'none'}/{victim or 'none'}/{fault}"
    assert a.returncode == 0, f"{name}: A terminal rc={a.returncode} " \
        f"({a.terminal_reason}){tails}"
    assert b.returncode == 0, f"{name}: B terminal rc={b.returncode} " \
        f"({b.terminal_reason}){tails}"
    if victim in ("A", "both"):
        assert a.restarts >= 1 and any("DYING point=" in line
                                       for line in a.lines), \
            f"{name}: A kill never fired{tails}"
    if victim in ("B", "both"):
        assert b.restarts >= 1 and any("DYING point=" in line
                                       for line in b.lines), \
            f"{name}: B kill never fired{tails}"
    arrays, meta = _load_result(cell["out_npz"])
    for k, ref in clean["arrays"].items():
        assert np.array_equal(arrays[k], ref), \
            f"{name}: array {k} diverged from the clean run{tails}"
    for k in ("counters", "fit_online", "predict_online"):
        assert meta[k] == clean["meta"][k], \
            f"{name}: {k} diverged: {meta[k]} != {clean['meta'][k]}"
    a_stats = _parse_stats(a.lines, "A")
    frames = _total(a_stats, "frames_sent")
    latencies = a.restart_latencies() + b.restart_latencies()
    amp = frames / clean["frames"] if clean["frames"] else 0.0
    return {
        "point": point or "none", "victim": victim or "none",
        "fault": fault,
        "restarts_a": a.restarts, "restarts_b": b.restarts,
        "incarnations": a.incarnation + b.incarnation + 2,
        "mttr_s": round(statistics.mean(latencies), 3) if latencies
        else None,
        "frames_sent_total": frames,
        "retry_amplification": round(amp, 3),
        "reconnects": _total(a_stats, "reconnects"),
        "retries": _total(a_stats, "retries"),
        "bit_exact": True,
        "wall_s": round(cell["wall"], 3),
    }


def _clean_reference() -> dict:
    """The unkilled, fault-free run every cell must reproduce exactly."""
    cell = _cell(None, None, "none")
    a, b = cell["a"], cell["b"]
    assert a.returncode == 0 and b.returncode == 0, \
        f"clean run failed\nA:\n{a.tail()}\nB:\n{b.tail()}"
    assert a.restarts == 0 and b.restarts == 0
    arrays, meta = _load_result(cell["out_npz"])
    a_stats = _parse_stats(a.lines, "A")
    b_stats = _parse_stats(b.lines, "B")
    return {"arrays": arrays, "meta": meta,
            "frames": _total(a_stats, "frames_sent"),
            "served": _total(b_stats, "served"),
            "wall": cell["wall"]}


def _matrix(full: bool) -> list[tuple]:
    cells = []
    for i, point in enumerate(KILL_POINTS):
        for j, victim in enumerate(VICTIMS):
            faults = FAULT_NAMES if full \
                else (FAULT_NAMES[(i + j) % len(FAULT_NAMES)],)
            for fault in faults:
                cells.append((point, victim, fault))
    return cells


# the 3-cell CI smoke: an engine death mid-iteration, a peer death at
# publish time, and connection tears during the resume handshake itself
QUICK_CELLS = [("fit.mid_s1", "A", "none"),
               ("fit.publish", "B", "none"),
               (None, None, "sever_handshake")]


def run(quick: bool = False, full: bool = False):
    clean = _clean_reference()
    served = clean["served"]
    rows = [{"point": "none", "victim": "none", "fault": "none",
             "restarts_a": 0, "restarts_b": 0, "incarnations": 2,
             "mttr_s": None, "frames_sent_total": clean["frames"],
             "retry_amplification": 1.0, "reconnects": 0, "retries": 0,
             "bit_exact": True, "wall_s": round(clean["wall"], 3)}]
    cells = QUICK_CELLS if quick else _matrix(full)
    for i, (point, victim, fault) in enumerate(cells):
        # B's kill frame: spread across the run, clamped so the armed
        # hit lands strictly before B's clean-run workload ends
        k = KILL_POINTS.index(point) if point in KILL_POINTS else 2
        b_nth = min(3 + 2 * k, max(2, served - 3))
        cell = _cell(point, victim, fault, b_nth=b_nth, fault_seed=i)
        rows.append(_row(point, victim, fault, cell, clean))
        print(f"  chaos[{i + 1}/{len(cells)}] "
              f"{rows[-1]['point']}/{rows[-1]['victim']}/{fault}: "
              f"restarts A={rows[-1]['restarts_a']} "
              f"B={rows[-1]['restarts_b']}, "
              f"mttr={rows[-1]['mttr_s']}s, "
              f"amp={rows[-1]['retry_amplification']}x", flush=True)
    with open(BENCH_PATH, "w") as f:
        json.dump({"rows": rows,
                   "note": "Chaos matrix: kill-points x victims x fault "
                           "mixes under launch/supervisor.py. Every cell "
                           "must converge to the clean run's exact share "
                           "bytes and online tallies. mttr_s = mean "
                           "death-to-ready seconds; retry_amplification "
                           "= A's frames across all incarnations over "
                           "the clean run's."},
                  f, indent=1)
    return rows


def derived(rows):
    """Headline: worst retry amplification + mean MTTR over kill cells."""
    killed = [r for r in rows if r["mttr_s"] is not None]
    if not killed:
        return ""
    amp = max(r["retry_amplification"] for r in rows)
    mttr = statistics.mean(r["mttr_s"] for r in killed)
    return f"mttr_mean={mttr:.2f}s amp_max={amp:.2f}x"


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="3-cell CI smoke slice")
    ap.add_argument("--full", action="store_true",
                    help="all 54 cells instead of the rotating 18")
    args = ap.parse_args()
    rows = run(quick=args.quick, full=args.full)
    print(json.dumps(rows, indent=1))
    sys.exit(0)
