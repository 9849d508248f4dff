"""Share of the ring products' roofline: the least time the chip could take
for the exact ring products that ran (work.ring_matmul_work: the larger of
bytes over HBM bandwidth and int8 digit products over the int8 peak, per
product) over the device time of the operations whose name path holds
`ring_matmul`, from the profiler trace. Nothing is read where no such
operation ran."""
LAYER = "ring kernels (core/backend.py ring_matmul)"
MOVES = "fit_rows_per_s"


def read(run):
    t = run.trace
    if not t or not run.peaks:
        return None
    ring = t["ring"]
    if ring["seconds"] <= 0 or ring["roofline_s"] <= 0:
        return None
    return 100.0 * ring["roofline_s"] / ring["seconds"]
