"""The readers of the program's stage spans, on a run whose spans and
wall are known: each share is its spans' summed wall over the fits' wall,
the bytes are every span's `h2d_bytes` over the row-iterations, and each
reads nothing where its spans or the wall are absent (a program that
lacks the spans, as the commit before them did)."""
import os

import pytest

from bench import spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _span(name, dur_us, **args):
    return {"name": name, "ph": "X", "ts": 1_000, "dur": dur_us,
            "tid": 1, "args": args}


SPANS = [
    _span("fit", 4_000_000),
    _span("fit.prologue", 500_000),
    _span("fit.prologue", 300_000),
    _span("fit.upload", 100_000, h2d_bytes=3 * 2 ** 20),
    _span("fit.init_centroids", 1_000, h2d_bytes=2 ** 19),
    _span("dealer.draw", 600_000, key="('mul', (2,))", count=4),
    _span("dealer.draw", 200_000, key="('bin', (2,))", count=2),
    _span("dealer.upload", 150_000, key="('mul', (2,))", count=4,
          h2d_bytes=2 ** 20),
    _span("dealer.split", 250_000, key="('mul', (2,))", count=4),
    _span("he.exchange", 50_000, n=8, k=2, h2d_bytes=2 ** 19),
    {"name": "dealer.draw", "ph": "i", "s": "t", "ts": 5, "tid": 1,
     "args": {}},
]
WALL_S = 4.0
RATE = 1_000.0          # row-iters/s: 4,000 row-iterations in the wall

# (metric, value on SPANS, the spans it needs)
EXPECTED = [
    ("fit.prologue_share", 100.0 * 0.8 / WALL_S, {"fit.prologue"}),
    ("fit.dealer_draw_share", 100.0 * 0.8 / WALL_S, {"dealer.draw"}),
    ("fit.dealer_upload_share", 100.0 * 0.4 / WALL_S,
     {"dealer.upload", "dealer.split"}),
    ("fit.h2d_mib_per_row_iter", 5.0 / (RATE * WALL_S), None),
]


def _run(spans, wall=WALL_S, rate=RATE):
    counters = {} if wall is None else {"fits_wall_s": wall}
    e2e = {} if rate is None else {"fit_rows_per_s": rate}
    return spec.Run(cell={"name": "fit.dense"}, cfg={}, traffic={},
                    peaks=None, e2e=e2e, counters=counters, trace=None,
                    spans=spans)


@pytest.mark.parametrize("name,value,_needs", EXPECTED,
                         ids=[e[0] for e in EXPECTED])
def test_reader_value_on_known_spans(name, value, _needs):
    got = spec.load_metric(ROOT, name).read(_run(SPANS))
    assert got == pytest.approx(value, rel=1e-12)


@pytest.mark.parametrize("name,_value,needs", EXPECTED,
                         ids=[e[0] for e in EXPECTED])
def test_reader_reads_nothing_without_its_spans_or_wall(name, _value,
                                                        needs):
    read = spec.load_metric(ROOT, name).read
    if needs is None:
        without = [e for e in SPANS if "h2d_bytes" not in e["args"]]
    else:
        without = [e for e in SPANS if e["name"] not in needs]
    assert read(_run(without)) is None
    assert read(_run([])) is None
    assert read(_run(SPANS, wall=None)) is None
    assert read(_run(SPANS, wall=0.0)) is None


def test_bytes_need_the_rate():
    read = spec.load_metric(ROOT, "fit.h2d_mib_per_row_iter").read
    assert read(_run(SPANS, rate=None)) is None


def test_draw_and_upload_shares_partition_the_dealer_spans():
    run = _run(SPANS)
    draw = spec.load_metric(ROOT, "fit.dealer_draw_share").read(run)
    dev = spec.load_metric(ROOT, "fit.dealer_upload_share").read(run)
    dealer_s = sum(e["dur"] for e in SPANS if e["ph"] == "X"
                   and e["name"] in ("dealer.draw", "dealer.upload",
                                     "dealer.split")) * 1e-6
    assert draw + dev == pytest.approx(100.0 * dealer_s / WALL_S)
