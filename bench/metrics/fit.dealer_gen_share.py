"""Background triple generation against the fits' wall: the sum of
`SlotDealer.gen_seconds` (the dealer worker's wall) over the fits' summed
wall. It overlaps the fit, so it can pass 100% only if generation ran on
more than one thread."""
LAYER = "offline dealer (core/triples.py)"
MOVES = "fit_rows_per_s"


def read(run):
    wall = run.counters.get("fits_wall_s")
    if not wall:
        return None
    return 100.0 * run.counters["dealer_gen_s"] / wall
