"""MiB of correlated randomness the S1, S3-partial and finalize launches of
one iteration take as arguments, per row: a count from the programs'
offline requests (`work.triple_bytes`), laid out as
`kmeans_step.offline_tensor_specs` lays them."""
LAYER = "offline dealer (core/triples.py)"
MOVES = "fit_rows_per_s"


def read(run):
    b = run.counters.get("triple_bytes_per_row_iter")
    if not b:
        return None
    return b / 2 ** 20
