"""The dealer's host draw against the fits' wall: the summed wall of the
program's `dealer.draw` spans (the PCG64 draw and the ring products of
each shape-class, on the dealer's worker thread) over the fits' summed
wall. With `fit.dealer_upload_share` it splits `fit.dealer_gen_share`.
Nothing is read where the program has no such span."""
LAYER = "offline dealer (core/triples.py)"
MOVES = "fit_rows_per_s"
SPANS = ("dealer.draw",)


def read(run):
    wall = run.counters.get("fits_wall_s")
    spans = [e for e in run.spans
             if e.get("name") in SPANS and e.get("ph") == "X"]
    if not wall or not spans:
        return None
    return 100.0 * sum(e["dur"] for e in spans) * 1e-6 / wall
