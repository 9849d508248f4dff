"""The dealer's device side against the fits' wall: the summed wall of the
program's `dealer.upload` spans (one `jax.device_put` of a triple class's
per-request arrays) and `dealer.split` spans (the host list work that cuts
the class's stacked draw into those per-request arrays) over the fits'
summed wall, each as the host calls it. With
`fit.dealer_draw_share` it splits `fit.dealer_gen_share`. Nothing is read
where the program has no such span."""
LAYER = "offline dealer (core/triples.py)"
MOVES = "fit_rows_per_s"
SPANS = ("dealer.upload", "dealer.split")


def read(run):
    wall = run.counters.get("fits_wall_s")
    spans = [e for e in run.spans
             if e.get("name") in SPANS and e.get("ph") == "X"]
    if not wall or not spans:
        return None
    return 100.0 * sum(e["dur"] for e in spans) * 1e-6 / wall
