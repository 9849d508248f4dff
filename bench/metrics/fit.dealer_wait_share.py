"""Share of the window's fit wall spent waiting on the slot dealer: the sum
of `SlotDealer.wait_seconds` (online stalls in `acquire`) over the fits'
summed wall."""
LAYER = "fit loop and pipeline (core/kmeans.py, launch/pipeline.py)"
MOVES = "fit_rows_per_s"


def read(run):
    wall = run.counters.get("fits_wall_s")
    if not wall:
        return None
    return 100.0 * run.counters["dealer_wait_s"] / wall
