"""The harness's arithmetic: fit throughput over whole fits started in the
window, the work credited to a ring product, the seeded rows, and the
reference and its control."""
import math
import os
import time
import types

import numpy as np
import pytest

from bench import data, reference, spec, work
from bench.drivers import fit_stream

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_fit_rate_counts_only_fits_started_in_the_window(monkeypatch):
    cfg = {"n": 1000}
    d = fit_stream.Driver(cfg, {}, 5, 1.0, log=lambda m: None,
                          annotate=lambda n: _Null())
    starts = []

    def fake_fit(seed):
        starts.append(time.monotonic())
        time.sleep(0.3)
        return types.SimpleNamespace(
            iters_run=2, dealer=types.SimpleNamespace(wait_seconds=0.1,
                                                      gen_seconds=0.2),
            log=types.SimpleNamespace(total_bytes=lambda phase: 4000))

    monkeypatch.setattr(d, "_fit", fake_fit)
    t0 = time.monotonic()
    d.window()
    assert len(d.fits) == 4
    assert all(s - t0 < 1.0 for s in starts)
    e = d.end_to_end()
    walls = sum(f["wall_s"] for f in d.fits)
    assert e["fit_rows_per_s"] == pytest.approx(4 * 2000 / walls)
    assert e["fit_online_bytes_per_row_iter"] == pytest.approx(2.0)
    assert [f["seed"] for f in d.fits] == [data.derived_seed(5, "fit", i)
                                           for i in range(4)]


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.mark.parametrize("m,k,n", [(16384, 512, 16), (64, 32, 8),
                                   (1, 1, 1), (1024, 1024, 16)])
def test_ring_work_depends_on_shapes_alone(m, k, n):
    want = work.ring_matmul_work(m, k, n)
    assert want.bytes == 8 * (m * k + k * n + m * n)
    assert want.ops == 36 * 2 * m * k * n
    # the same digit dot, however its dimensions are laid out
    for lhs, rhs, out in [(8 * m * k, 8 * k * n, 64 * m * n)]:
        got = work.ring_matmul_from_digit_dot(lhs, rhs, out)
        assert got.bytes == pytest.approx(want.bytes)
        assert got.ops == pytest.approx(want.ops)
    assert work.ring_matmul_work(m, k, n) == want


def test_triple_bytes_match_the_launch_arguments():
    from repro.launch.kmeans_step import offline_tensor_specs
    reqs = [("matmul", ((64, 32), (32, 4))), ("mul", (64, 4)),
            ("bin", (7, 3)), ("rand", (5,))]
    specs = offline_tensor_specs(reqs, 64)
    assert work.triple_bytes(reqs) == sum(
        math.prod(s.shape) * s.dtype.itemsize for s in specs)


def test_rows_are_the_same_for_the_same_seed():
    def rows(seed):
        rng = data.rng_for(seed, "data")
        return data.blobs(rng, data.centres(rng, 3, 40), 500, 0.05, 0.5)
    assert np.array_equal(rows(2 ** 40 + 3), rows(2 ** 40 + 3))
    assert not np.array_equal(rows(2 ** 40 + 3), rows(4))
    assert 0.4 < (rows(4) == 0).mean() < 0.6


def test_onehot_rows_keep_one_feature_per_attribute():
    groups = [5, 8, 2, 41]
    rng = data.rng_for(2 ** 33 + 1, "data")
    x = data.blobs(rng, data.centres(rng, 2, sum(groups)), 3000, 0.05,
                   groups=groups)
    lo = 0
    for g in groups:
        assert np.all((x[:, lo:lo + g] != 0).sum(1) == 1)
        lo += g
    with pytest.raises(ValueError):
        data.blobs(rng, data.centres(rng, 2, 10), 5, 0.05, groups=[3, 3])


def _blobs(seed=3, n=600, d=12, k=4):
    rng = data.rng_for(seed, "data")
    return data.blobs(rng, data.centres(rng, k, d), n, 0.05), k


def test_reference_lloyd_is_plain_lloyd():
    x, k = _blobs()
    init = reference.init_rows(9, len(x), k)
    assert np.array_equal(init, np.random.default_rng(9).choice(
        len(x), k, replace=False))
    hist, mu, labels = reference.lloyd(x, init, 3)
    m = x[init].copy()
    for _ in range(3):
        lab = ((x[:, None, :] - m[None]) ** 2).sum(-1).argmin(1)
        m = np.stack([x[lab == j].mean(0) if (lab == j).any() else m[j]
                      for j in range(k)])
    assert np.array_equal(labels, lab) and np.allclose(mu, m)
    assert len(hist) == 3 and np.array_equal(hist[0], x[init])


BLOCKS = [(0, 5), (5, 12)]          # two parties' columns of _blobs()


def _limits():
    return [spec.load_limits(ROOT, w)["centroid_median_err"]
            for w in ("fit.dense", "fit.sparse")]


def test_control_is_one_precision_step_down():
    x, k = _blobs()
    init = reference.init_rows(9, len(x), k)
    _h, mu, labels = reference.lloyd(x, init, 2)
    _hq, muq, labq = reference.lloyd_fixed(x, init, 2)
    err = np.abs(muq - mu)
    assert 2.0 ** -14 < err.max() < 2.0 ** -8
    want = max(np.median(err[:, lo:hi], axis=1).max() for lo, hi in BLOCKS)
    got = fit_stream.fit_numbers(muq, mu, BLOCKS)["centroid_median_err"]
    assert got == want and got > max(_limits())
    assert fit_stream.fit_numbers(mu, mu, BLOCKS) == \
        {"centroid_median_err": 0.0}


def test_a_row_in_the_wrong_cluster_moves_its_centroids():
    x, k = _blobs()
    init = reference.init_rows(9, len(x), k)
    _h, mu, labels = reference.lloyd(x, init, 1)
    wrong = labels.copy()
    wrong[::64] = (wrong[::64] + 1) % k
    moved = np.stack([x[wrong == j].mean(0) for j in range(k)])
    assert fit_stream.fit_numbers(moved, mu, BLOCKS)["centroid_median_err"] \
        > 1e-2


def test_one_partys_columns_wrong_show_in_its_block():
    x, k = _blobs()
    init = reference.init_rows(9, len(x), k)
    _h, mu, _labels = reference.lloyd(x, init, 1)
    off = mu.copy()
    off[:, 5:] += 1e-3
    assert fit_stream.fit_numbers(off, mu, BLOCKS)["centroid_median_err"] \
        == pytest.approx(1e-3)
    assert fit_stream.fit_numbers(off, mu, [(0, 12)])[
        "centroid_median_err"] == pytest.approx(1e-3)
    off[:, 5:7] += 1.0               # two of the block's seven features
    assert fit_stream.fit_numbers(off, mu, BLOCKS)["centroid_median_err"] \
        == pytest.approx(1e-3)


def test_one_row_moved_in_a_small_cluster_stays_within_the_limit():
    """The protocol's own rare truncation failure puts one row in a farther
    cluster. In a9a's shape, with a cluster of about 1,600 rows, that moves
    the largest error of a centroid past a third of the control's (2^-10 at
    most), so no limit on it sits threefold below the control; the median
    stays far within both cells' limits."""
    groups = [5, 8, 5, 16, 5, 7, 14, 6, 5, 2, 2, 2, 5, 41]
    rng = data.rng_for(2 ** 31 + 7, "data")
    n, d = 32561, sum(groups)
    x = data.blobs(rng, data.centres(rng, 2, d), n, 0.05, groups=groups)
    labels = np.where(np.arange(n) < 1600, 0, 1)
    mu = np.stack([x[labels == j].mean(0) for j in range(2)])
    labels[1600] = 0
    moved = np.stack([x[labels == j].mean(0) for j in range(2)])
    blocks = [(0, 66), (66, d)]
    assert np.abs(moved - mu).max() > 2.0 ** -reference.CONTROL_F / 3
    assert fit_stream.fit_numbers(moved, mu, blocks)["centroid_median_err"] \
        < min(_limits()) / 4
