"""Share of the window's fit wall spent joining a horizontal fit's two
parties' rows: the summed wall of the program's `fit.join` spans (the
initial rows' shares joined and put in order on the host, and the
assignment's per-batch [A chunk; B chunk] rows put in [A rows; B rows]
order) over the fits' summed wall. Nothing is read where the program has
no such span, as in a vertical fit or a program that predates it."""
LAYER = "fit loop and pipeline (core/kmeans.py, launch/pipeline.py)"
MOVES = "fit_rows_per_s"
SPANS = ("fit.join",)


def read(run):
    wall = run.counters.get("fits_wall_s")
    spans = [e for e in run.spans
             if e.get("name") in SPANS and e.get("ph") == "X"]
    if not wall or not spans:
        return None
    return 100.0 * sum(e["dur"] for e in spans) * 1e-6 / wall
