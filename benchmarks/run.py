"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # full
    PYTHONPATH=src python -m benchmarks.run --quick    # CI-sized

Prints one ``name,seconds,derived`` CSV line per suite plus the per-row
tables, and writes benchmarks/results.json.

A chip belongs to one process at a time. The suites that start child
processes needing the chip (`chaos`, and the kill/restart leg of `load`)
therefore run first, before any in-process suite initialises JAX here.
"""
from __future__ import annotations

import argparse
import json
import os
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated suite names")
    args = ap.parse_args()

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    from benchmarks import (chaos_bench, fig2_online_offline,
                            fig3_vectorization, fig4_sparse, kernel_bench,
                            load_bench, obs_bench, offline_bench,
                            online_offline, pipeline_bench, q5_fraud,
                            serve_bench, table1_2, wire_bench)

    suites = {
        "table1_2_runtime_comm": lambda: table1_2.run(quick=args.quick),
        "fig2_online_offline": fig2_online_offline.run,
        "fig3_vectorization": fig3_vectorization.run,
        "fig4a_sparse_dim": lambda: fig4_sparse.run_a(quick=args.quick),
        "fig4b_sparse_degree": fig4_sparse.run_b,
        "q5_fraud_jaccard": lambda: q5_fraud.run(quick=args.quick),
        # `--only kernels_interpret --quick` is the CI smoke entry: per-op
        # xla-vs-pallas timings, persisted to benchmarks/BENCH_kernels.json
        "kernels_interpret": lambda: kernel_bench.run(quick=args.quick),
        # `--only online_offline --quick` is the per-PR perf smoke: measured
        # offline/online split of the pooled/streamed fits vs the on-demand
        # baseline for ALL FOUR partition x sparsity combos, persisted to
        # benchmarks/BENCH_online.json (full mode adds an n=4096 row)
        "online_offline": lambda: online_offline.run(quick=args.quick),
        # `--only serve --quick` is the serving-subsystem smoke: scoring-
        # service throughput over dense and sparse batch ladders, persisted
        # to benchmarks/BENCH_serve.json
        "serve": lambda: serve_bench.run(quick=args.quick),
        # `--only pipeline --quick` is the overlap smoke: pipelined vs
        # sequential minibatch fit + serve drain (bit-exact asserted) and
        # streamed peak-pool residency vs n, persisted to
        # benchmarks/BENCH_pipeline.json
        "pipeline": lambda: pipeline_bench.run(quick=args.quick),
        # `--only offline --quick` is the cold-start smoke: cold vs warm vs
        # bank-provisioned fit offline walls, batched-vs-legacy HE exchange
        # accounting + real-Paillier wall, and provisioning worker scaling,
        # persisted to benchmarks/BENCH_offline.json
        "offline": lambda: offline_bench.run(quick=args.quick),
        # `--only wire --quick` is the transport smoke: the same fit over
        # loopback frames, a real TCP socket, and emulated LAN/WAN latency
        # (bit-exact asserted), measured wall next to the NetModel
        # prediction, persisted to benchmarks/BENCH_wire.json
        "wire": lambda: wire_bench.run(quick=args.quick),
        # `--only load --quick` is the serving-plane smoke: open-loop
        # offered loads at 0.5x/1x/2x the closed-loop base rate (shed
        # rate, p99, replenish-stall occupancy) plus a two-process
        # kill/restart chaos leg (exactly-once, bit-exact), persisted to
        # benchmarks/BENCH_load.json
        "load": lambda: load_bench.run(quick=args.quick),
        # `--only obs --quick` is the observability smoke: tracing-on vs
        # tracing-off online-fit and serve-drain walls (<=1.05x asserted,
        # outputs bit-identical), the disabled-path ns/call, and span
        # coverage; persists benchmarks/BENCH_obs.json + the sample
        # Perfetto trace benchmarks/trace_sample.json
        "obs": lambda: obs_bench.run(quick=args.quick),
        # `--only chaos --quick` is the self-healing smoke: a 3-cell
        # slice of the kill-point x victim x fault-mix matrix under the
        # supervisor (kill A mid-iteration, kill B at publish, sever the
        # resume handshake), every cell asserted byte-exact against the
        # unkilled run; full mode sweeps the 18-cell rotating matrix;
        # persisted to benchmarks/BENCH_chaos.json with MTTR and
        # retry-amplification columns
        "chaos": lambda: chaos_bench.run(quick=args.quick),
    }
    derived_fns = {
        "table1_2_runtime_comm": table1_2.derived,
        "fig2_online_offline": fig2_online_offline.derived,
        "fig3_vectorization": fig3_vectorization.derived,
        "fig4b_sparse_degree": fig4_sparse.derived,
        "q5_fraud_jaccard": q5_fraud.derived,
        "kernels_interpret": kernel_bench.derived,
        "online_offline": online_offline.derived,
        "serve": serve_bench.derived,
        "pipeline": pipeline_bench.derived,
        "offline": offline_bench.derived,
        "wire": wire_bench.derived,
        "load": load_bench.derived,
        "obs": obs_bench.derived,
        "chaos": chaos_bench.derived,
    }
    if args.only:
        keep = set(args.only.split(","))
        suites = {k: v for k, v in suites.items() if k in keep}
    first = [k for k in ("chaos", "load") if k in suites]
    suites = {k: suites[k] for k in first + [k for k in suites
                                             if k not in first]}

    all_results = {}
    print("name,seconds,derived")
    for name, fn in suites.items():
        t0 = time.perf_counter()
        rows = fn()
        dt = time.perf_counter() - t0
        d = derived_fns.get(name, lambda r: "")(rows)
        all_results[name] = {"rows": rows, "seconds": round(dt, 1),
                             "derived": d}
        print(f"{name},{dt:.1f},{d}")
        for row in rows:
            print("   ", row)

    out = os.path.join(os.path.dirname(__file__), "results.json")
    with open(out, "w") as f:
        json.dump(all_results, f, indent=1, default=str)
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
