"""Share of the window's fit wall spent in each fit's prologue: the summed
wall of the program's `fit.prologue` spans (from `SecureKMeans.fit` entry
to the loop's first launch: encoding, initial centroids, plan and program
lookups, the rows' upload and the dealer's start) over the fits' summed
wall. Nothing is read where the program has no such span."""
LAYER = "fit loop and pipeline (core/kmeans.py, launch/pipeline.py)"
MOVES = "fit_rows_per_s"
SPANS = ("fit.prologue",)


def read(run):
    wall = run.counters.get("fits_wall_s")
    spans = [e for e in run.spans
             if e.get("name") in SPANS and e.get("ph") == "X"]
    if not wall or not spans:
        return None
    return 100.0 * sum(e["dur"] for e in spans) * 1e-6 / wall
