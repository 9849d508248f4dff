"""The `fit.horizontal.skew` cell: the `fraud-horizontal-skew` deployment
(IEEE-CIS `train_transaction` split by ProductCD between two processors)
loads as the benchmark states it, its geometry is the program's, its check
passes a sound window and fails the control at rehearsal size on the CPU,
its triples are counted over the batches a fit runs, and `fit.join_share`
reads the program's `fit.join` spans."""
import os

import pytest

from bench import spec
from bench import work
from bench.drivers import fit_stream

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "fit.horizontal.skew"


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def _cell():
    bench = spec.load_benchmark(ROOT)
    cell = spec.workload(bench, CELL)
    return bench, cell, spec.load_config(ROOT, bench, cell["config"]), \
        spec.load_traffic(ROOT, cell["traffic"])


def _driver(seconds, **sizes):
    _bench, _cell_, cfg, traffic = _cell()
    cfg, traffic = spec.rehearsal_sizes(cfg, traffic)
    cfg.update(sizes)
    d = fit_stream.Driver(cfg, traffic, 2147490123, seconds,
                          log=lambda m: None, annotate=lambda name: _Null())
    d.setup()
    return d


def test_the_cell_loads_as_stated():
    bench, cell, cfg, traffic = _cell()
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("fraud-horizontal-skew", "fit_stream", 1)
    assert traffic["kind"] == "fit_stream"
    assert cfg["partition"] == "horizontal" and "d_a" not in cfg
    assert (cfg["n"], cfg["n_a"], cfg["d"], cfg["k"], cfg["iters"]) \
        == (590540, 439670, 392, 2, 1)
    assert not cfg["sparse"] and cfg["offline"] == "streamed"
    entry = next(c for c in bench["configs"]
                 if c["name"] == "fraud-horizontal-skew")
    assert entry["reduced"] == list(cfg["reduced"])
    names = {m["name"] for m in spec.per_layer_for(bench, cell)}
    assert "fit.join_share" in names and "fit.he_exchange_share" not in names
    assert {m["name"] for m in spec.end_to_end_for(bench, cell)} \
        == {"fit_rows_per_s", "fit_online_bytes_per_row_iter", "setup_s"}
    assert spec.load_limits(ROOT, CELL)["centroid_median_err"] > 0


def test_the_product_lines_sum_to_the_rows():
    """Party A is product W's rows, party B the rest (C, H, R and S), and
    the two make up every row of the table."""
    _bench, _cell_, cfg, _traffic = _cell()
    rows = cfg["product_rows"]
    assert rows["W"] == cfg["n_a"]
    assert sum(rows.values()) == cfg["n"]
    assert cfg["n"] - cfg["n_a"] == rows["C"] + rows["H"] + rows["R"] \
        + rows["S"] == 150870


def test_full_size_geometry_is_the_programs():
    """37 batches, each a near-equal chunk of each party's rows, as the
    program's `_minibatch_bounds` cuts them."""
    from repro.core.kmeans import _minibatch_bounds
    _bench, _cell_, cfg, _traffic = _cell()
    rows = fit_stream.batch_rows(cfg)
    assert len(rows) == 37
    assert rows[0] == (11883, 4078) and rows[-1] == (11882, 4077)
    assert sum(r[0] for r in rows) == cfg["n_a"]
    assert sum(r[1] for r in rows) == cfg["n"] - cfg["n_a"]
    bounds = _minibatch_bounds("horizontal", cfg["n_a"],
                               cfg["n"] - cfg["n_a"], cfg["batch_size"])
    assert [(ahi - alo, bhi - blo) for (alo, ahi), (blo, bhi) in bounds] \
        == rows


@pytest.fixture(scope="module")
def rehearsal_driver():
    return _driver(0.5)


def _window(d):
    d.fits, d.failures = [], []
    d.window()
    d.finish()
    assert d.fits


@pytest.mark.parametrize("control", [False, True], ids=["sound", "control"])
def test_check_passes_sound_and_fails_control(rehearsal_driver, control):
    _window(rehearsal_driver)
    attempted, failed, checks = rehearsal_driver.check(control=control)
    correct, compared = spec.judge(attempted, failed, checks,
                                   spec.load_limits(ROOT, CELL))
    assert correct is not control, compared


def test_triples_are_counted_over_the_batches_run(monkeypatch):
    """The batch programs a fit fetches, one a batch in the order it runs
    them, have the driver's shapes at the deployment's skew, and
    `counters()` counts the triples of exactly those launches and of the
    finalize launch."""
    from repro.launch import kmeans_step as K
    d = _driver(0.0, batch_size=256)
    fetched = []
    real = K.fit_batch_programs

    def fit_batch_programs(*a, **kw):
        p = real(*a, **kw)
        fetched.append(p)
        return p
    monkeypatch.setattr(K, "fit_batch_programs", fit_batch_programs)
    d._fit(2147490321)
    monkeypatch.undo()
    shapes = fit_stream.batch_shapes(d.cfg)
    assert len(shapes) == 8
    assert [(p.geo.shape_a, p.geo.shape_b) for p in fetched] == shapes
    fin = K.finalize_program(d.cfg["k"], d.cfg["d"], d.cfg["n"])
    want = work.triple_bytes(fin.requests) + sum(
        work.triple_bytes(p.s1_requests) + work.triple_bytes(p.s3p_requests)
        for p in fetched)
    assert d.counters()["triple_bytes_per_row_iter"] == want / d.cfg["n"]


def _span(name, dur_us, **args):
    return {"name": name, "ph": "X", "ts": 1_000, "dur": dur_us,
            "tid": 1, "args": args}


def _run(spans, wall):
    counters = {} if wall is None else {"fits_wall_s": wall}
    return spec.Run(cell={"name": CELL}, cfg={}, traffic={}, peaks=None,
                    e2e={}, counters=counters, trace=None, spans=spans)


def test_join_share_reads_the_join_spans():
    read = spec.load_metric(ROOT, "fit.join_share").read
    spans = [_span("fit", 4_000_000), _span("fit.init_centroids", 3_000),
             _span("fit.join", 1_000, rows_a=1, rows_b=1),
             _span("fit.assemble", 40_000),
             _span("fit.join", 30_000, rows_a=439670, rows_b=150870),
             {"name": "fit.join", "ph": "i", "s": "t", "ts": 5, "tid": 1,
              "args": {}}]
    assert read(_run(spans, 4.0)) == pytest.approx(100.0 * 0.031 / 4.0,
                                                   rel=1e-12)
    without = [e for e in spans if e["name"] != "fit.join"]
    assert read(_run(without, 4.0)) is None
    assert read(_run([], 4.0)) is None
    assert read(_run(spans, None)) is None
    assert read(_run(spans, 0.0)) is None
