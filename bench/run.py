#!/usr/bin/env python3
"""On-chip benchmark of the secure K-means system.

    python3 bench/run.py --workload fit.dense --seed 7 --seconds 30 --trace 0

Runs one cell of `BENCHMARK.json` in this process: loads the cell's
configuration (`bench/configs/<config>.json`) and traffic mix
(`bench/traffic/<traffic>.json`), hands them to the driver the mix names
(`bench/drivers/<kind>.py`), warms up every shape the window uses, measures
for `--seconds`, checks what the window produced against the plain
reference (`bench/reference.py`, limits in `bench/limits/<cell>.json`) and
prints one JSON line last on standard output. `--trace 0` reports the
cell's end-to-end metrics; `--trace 1` traces the same window with the
profiler and the program's span tracer on and reports the cell's per-layer
metrics (`bench/metrics/<metric>.py`) instead.

It exits non-zero and prints no result when JAX finds no TPU or fewer chips
than the cell asks for, when the device is missing from `bench/peaks.json`,
and when it does not sit in a checkout of the repository (`src/repro`).

`--cpu-rehearsal` runs the cell at a tiny size on whatever device JAX has and
prints its numbers on standard error as a rehearsal, never as a result;
`--control` puts the reference computed one precision step down
(`reference.lloyd_fixed`) in the program's place for the
check, to show that the check fails it.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import spec  # noqa: E402


class NoDevice(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def use_compile_cache() -> str:
    """The program's persistent compilation cache
    (`repro.launch.compile_cache`: `$JAX_COMPILATION_CACHE_DIR` if set,
    else `<checkout>/.jax_cache`), keeping every program however short its
    compile, so that a second run of a cell compiles nothing."""
    import jax
    from repro.launch.compile_cache import use_compile_cache as program_cache
    path = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts backend compiles and persistent-cache loads."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> tuple:
        return self.compiles, self.cache_hits


@contextlib.contextmanager
def device_trace(enabled: bool):
    """Profile the block into a temporary directory outside the checkout;
    yields a holder whose `path` is the `.xplane.pb` after the block."""
    holder = types.SimpleNamespace(path=None, dir=None)
    if not enabled:
        yield holder
        return
    import jax
    holder.dir = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(holder.dir, profiler_options=opts)
    try:
        yield holder
    finally:
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(holder.dir, "**", "*.xplane.pb"),
                          recursive=True)
        holder.path = found[0] if found else None


def annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def run_cell(args, *, require_tpu: bool = True) -> dict:
    """One run of one cell; returns the result object (see the module
    docstring). Raises NoDevice where the contract says to print nothing."""
    bench = spec.load_benchmark(ROOT)
    cell = spec.workload(bench, args.workload)
    cfg = spec.load_config(ROOT, bench, cell["config"])
    traffic = spec.load_traffic(ROOT, cell["traffic"])
    if args.cpu_rehearsal:
        cfg, traffic = spec.rehearsal_sizes(cfg, traffic)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        raise NoDevice(f"no src/repro under {ROOT}: run from a checkout of "
                       "the repository")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    # a rehearsal keeps nothing: CPU programs in the chip's cache only warn
    cache = None if args.cpu_rehearsal else use_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    log(f"device platform={dev.platform} device_kind={dev.device_kind} "
        f"count={len(devices)} cache={cache}")
    if require_tpu and dev.platform != "tpu":
        raise NoDevice(f"JAX found no TPU (default device {dev.platform}: "
                       f"{dev.device_kind})")
    if len(devices) < int(cell["chips"]):
        raise NoDevice(f"the cell asks for {cell['chips']} chips, JAX has "
                       f"{len(devices)}")
    peaks = spec.peaks_for(ROOT, dev.device_kind, required=require_tpu)
    counter = CompileCounter()
    driver_mod = spec.load_driver(ROOT, traffic["kind"])
    driver = driver_mod.Driver(cfg, traffic, int(args.seed),
                               float(args.seconds), log=log,
                               annotate=annotate)
    c0 = counter.snapshot()
    driver.setup()
    c1 = counter.snapshot()
    setup_s = time.monotonic() - T_PROCESS
    log(f"setup_s={setup_s:.3f} compiles_in_setup={c1[0] - c0[0]} "
        f"cache_loads_in_setup={c1[1] - c0[1]}")

    from repro.obs import trace as obs_trace
    tracer = obs_trace.get_tracer()
    if args.trace:
        tracer.reset()
        obs_trace.configure(enabled=True)
    with device_trace(bool(args.trace)) as tr:
        with annotate("bench.window"):
            driver.window()
    obs_spans = tracer.events() if args.trace else []
    obs_trace.configure(enabled=False)
    c2 = counter.snapshot()
    log(f"compiles_in_window={c2[0] - c1[0]} "
        f"cache_loads_in_window={c2[1] - c1[1]}")
    stats = dev.memory_stats() or {}
    mem_peak = int(stats.get("peak_bytes_in_use", 0))
    driver.finish()

    trace = None
    if tr.path is not None:
        from bench import trace_reduce
        from jax.profiler import ProfileData
        hlo = trace_reduce.HloIndex(driver.hlo_texts())
        trace = trace_reduce.reduce(ProfileData.from_file(tr.path), hlo,
                                    peaks=peaks, obs_spans=obs_spans)
        ring = trace["ring"]
        log(f"trace window_s={trace['window_s']:.3f} "
            f"busy_s={trace['busy_s']:.4f} ring_ops={ring['events']} "
            f"ring_s={ring['seconds']:.4f} ring_bytes={ring['bytes']:.4g} "
            f"ring_int8_ops={ring['ops']:.4g} "
            f"bytes_bound={ring['bytes_bound']} ops_bound={ring['ops_bound']}")
        shutil.rmtree(tr.dir, ignore_errors=True)
    elif tr.dir is not None:
        shutil.rmtree(tr.dir, ignore_errors=True)

    attempted, failed, checks = driver.check(control=args.control)
    correct, compared = spec.judge(attempted, failed, checks,
                                   spec.load_limits(ROOT, args.workload))

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem_peak}
    run = spec.Run(cell=cell, cfg=cfg, traffic=traffic, peaks=peaks,
                   e2e=driver.end_to_end(), counters=driver.counters(),
                   trace=trace, spans=obs_spans)
    metrics = {}
    if args.trace:
        for m in spec.per_layer_for(bench, cell):
            value = spec.load_metric(ROOT, m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
    else:
        for m in spec.end_to_end_for(bench, cell):
            value = setup_s if m["name"] == "setup_s" \
                else run.e2e.get(m["name"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(failed), "metrics": metrics, "device": device}
    if trace is not None:
        result["breakdown"] = {"device_ops": trace["device_ops"][:10],
                               "idle_gaps": trace["idle_gaps"][:10]}
    result["checks"] = compared
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    try:
        result = run_cell(args, require_tpu=not args.cpu_rehearsal)
    except (NoDevice, spec.SpecError) as e:
        log(f"bench: {type(e).__name__}: {e}")
        return 2
    for name, c in result["checks"].items():
        log(f"check {name} value={c['value']!r} limit={c['limit']!r}")
    if args.cpu_rehearsal:
        log("REHEARSAL (not a device measurement) " + json.dumps(result))
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
