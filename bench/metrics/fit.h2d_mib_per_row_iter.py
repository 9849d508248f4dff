"""MiB the fits put from the host on the device, per row-iteration: the
sum of the `h2d_bytes` the program's spans carry (each byte on the
innermost span that moves it: the rows' upload, the initial centroids,
the dealer's uploads, the Protocol-2 result shares) over the window's
row-iterations, `fit_rows_per_s` times the fits' summed wall. Nothing is
read where no span carries the count."""
LAYER = "fit loop and pipeline (core/kmeans.py, launch/pipeline.py)"
MOVES = "fit_rows_per_s"


def read(run):
    wall = run.counters.get("fits_wall_s")
    rate = run.e2e.get("fit_rows_per_s")
    counted = [e["args"]["h2d_bytes"] for e in run.spans
               if e.get("ph") == "X" and "h2d_bytes" in e.get("args", {})]
    if not wall or not rate or not counted:
        return None
    return sum(counted) / 2 ** 20 / (rate * wall)
