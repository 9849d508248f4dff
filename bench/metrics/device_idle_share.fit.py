"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's XLA op intervals) / window, from the profiler
trace (bench/trace_reduce.py)."""
LAYER = "device programs (launch/kmeans_step.py)"
MOVES = "fit_rows_per_s"


def read(run):
    t = run.trace
    if not t or not t["window_s"] or not t["devices"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
