"""Share of the window's fit wall spent in the Protocol-2 host exchange:
measured wall of the program's `he.exchange` spans (its obs tracer, on in
the traced run) over the fits' summed wall. Never the modelled
`KMeansResult.he_seconds`."""
LAYER = "Protocol-2 exchange (core/sparse.py, core/he.py)"
MOVES = "fit_rows_per_s"


def read(run):
    wall = run.counters.get("fits_wall_s")
    spans = [e for e in run.spans
             if e.get("name") == "he.exchange" and e.get("ph") == "X"]
    if not wall or not spans:
        return None
    return 100.0 * sum(e["dur"] for e in spans) * 1e-6 / wall
