"""The driver's batch geometry is the program's: each party's rows of each
minibatch, and the triples the batch programs of a fit take, counted over
the batches that the fit runs."""
import itertools
import os

import pytest

from bench import data, reference, spec, work
from bench.drivers import fit_stream
from repro.core.kmeans import _minibatch_bounds

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

GRID = list(itertools.product(
    [1, 2, 7, 1000, 2000, 16385, 144233, 590540],   # n
    [0.5, 0.75, 0.9, 0.999, 0.001],                # party A's share of n
    [1, 3, 1024, 16384, 10 ** 6]))                 # batch_size


def _program_rows(partition, n, n_a, batch_size):
    if partition == "vertical":
        bounds = _minibatch_bounds("vertical", n, n, batch_size)
    else:
        bounds = _minibatch_bounds("horizontal", n_a, n - n_a, batch_size)
    return [(ahi - alo, bhi - blo) for (alo, ahi), (blo, bhi) in bounds]


@pytest.mark.parametrize("partition", ["vertical", "horizontal"])
def test_batch_rows_are_the_programs(partition):
    """Over a grid that holds skewed splits, splits where party B has fewer
    rows than ceil(n / batch_size) batches, a batch larger than n and one
    row a batch."""
    short_b = 0
    for n, share, bs in GRID:
        n_a = min(max(1, round(share * n)), n - 1)
        if partition == "horizontal" and n < 2:
            continue
        cfg = {"partition": partition, "n": n, "n_a": n_a, "d": 8,
               "d_a": 3, "batch_size": bs}
        got = fit_stream.batch_rows(cfg)
        assert got == _program_rows(partition, n, n_a, bs), (n, n_a, bs)
        short_b += n - n_a < -(-n // bs)
        shapes = fit_stream.batch_shapes(cfg)
        widths = (3, 5) if partition == "vertical" else (8, 8)
        assert shapes == [((ra, widths[0]), (rb, widths[1]))
                          for ra, rb in got]
    assert partition == "vertical" or short_b > 10


@pytest.mark.parametrize("workload", ["fit.dense", "fit.sparse"])
def test_vertical_cells_keep_their_batches(workload):
    """Both cells at their own sizes run the batches they ran before the
    geometry was written down: chunks of batch_size rows, the last one
    shorter, both parties on the same rows (shapes only)."""
    bench = spec.load_benchmark(ROOT)
    cfg = spec.load_config(ROOT, bench, spec.workload(bench, workload)["config"])
    n, bs, d_a = cfg["n"], cfg["batch_size"], cfg["d_a"]
    rows = [min(bs, n - lo) for lo in range(0, n, bs)]
    assert fit_stream.batch_rows(cfg) == [(r, r) for r in rows]
    assert fit_stream.batch_shapes(cfg) == [((r, d_a), (r, cfg["d"] - d_a))
                                            for r in rows]
    assert len(rows) == {"fit.dense": 9, "fit.sparse": 2}[workload]


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def test_horizontal_triples_are_counted_over_the_batches_run(
        horizontal_tree, monkeypatch):
    """A horizontal fit at rehearsal size: the batch programs the program
    fetches, one a batch in the order it runs them, have the driver's
    shapes, and `counters()` counts the triples of exactly those launches
    and of the finalize launch."""
    from repro.launch import kmeans_step as K
    bench = spec.load_benchmark(horizontal_tree)
    cell = spec.workload(bench, "fit.horizontal")
    cfg, traffic = spec.rehearsal_sizes(
        spec.load_config(horizontal_tree, bench, cell["config"]),
        spec.load_traffic(horizontal_tree, cell["traffic"]))
    cfg["batch_size"] = 600          # four batches, of 375 and 125 rows
    fetched = []
    real = K.fit_batch_programs

    def fit_batch_programs(*a, **kw):
        p = real(*a, **kw)
        fetched.append(p)
        return p
    d = fit_stream.Driver(cfg, traffic, 2147483999, 0.0,
                          log=lambda m: None, annotate=lambda name: _Null())
    d.setup()
    monkeypatch.setattr(K, "fit_batch_programs", fit_batch_programs)
    d._fit(12345)
    monkeypatch.undo()
    assert [(p.geo.shape_a, p.geo.shape_b) for p in fetched] \
        == fit_stream.batch_shapes(cfg)
    assert [s[0][0] for s in fit_stream.batch_shapes(cfg)] == [375] * 4
    fin = K.finalize_program(cfg["k"], cfg["d"], cfg["n"])
    want = work.triple_bytes(fin.requests) + sum(
        work.triple_bytes(p.s1_requests) + work.triple_bytes(p.s3p_requests)
        for p in fetched)
    assert d.counters()["triple_bytes_per_row_iter"] == want / cfg["n"]


def test_set_up_warms_every_split_of_the_initial_rows(horizontal_tree):
    """A horizontal fit's initial rows fall to the parties in k + 1 ways,
    each a program shape of its own: set-up warms each split that the
    window's fits take, so the window compiles nothing. A vertical fit has
    one warm-up fit, as before."""
    import jax
    bench = spec.load_benchmark(horizontal_tree)
    cell = spec.workload(bench, "fit.horizontal")
    cfg, traffic = spec.rehearsal_sizes(
        spec.load_config(horizontal_tree, bench, cell["config"]),
        spec.load_traffic(horizontal_tree, cell["traffic"]))
    d = fit_stream.Driver(cfg, traffic, 2147485369, 2.0,
                          log=lambda m: None, annotate=lambda name: _Null())

    def split(seed):
        return int((reference.init_rows(seed, cfg["n"], cfg["k"])
                    < cfg["n_a"]).sum())
    seeds = d.warmup_seeds()
    assert seeds[0] == data.derived_seed(2147485369, "warmup", 0)
    assert sorted(map(split, seeds)) == list(range(cfg["k"] + 1))
    vertical = dict(cfg, partition="vertical", d_a=32)
    del vertical["n_a"]
    assert fit_stream.Driver(vertical, traffic, 2147485369, 2.0,
                             log=None, annotate=None).warmup_seeds() \
        == seeds[:1]

    compiles = []

    def count(event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)
    d.setup()
    jax.monitoring.register_event_duration_secs_listener(count)
    try:
        d.window()
    finally:
        jax.monitoring.unregister_event_duration_listener(count)
    assert len(d.fits) > 10 and compiles == []
