"""The check of each cell fails what it must fail.

These drive the rest of a run at a tiny size on the CPU, past the harness's
look for a chip: a driver's set-up, its measured window and its check
against the cell's limits (bench/limits/). A sound window must come out
correct. The control (the reference one precision step down, in the
program's place) and each fault the cell can have, planted in the program
underneath the timed path, must come out not correct:

a finalize that returns the centroids unchanged; an S3-partial launch that
leaves half the batch out, so the mean is taken over the rest; an S1 launch
that alters the assignment of every 64th row where it is made (one row
alone is within the protocol's own rate of truncation failures, which put
about one row in 10^5 in a farther cluster). Each test runs in both fit
cells, and in a horizontally partitioned copy of the dense one.

No cell spans chips, so the fault of an exchange between chips left out
cannot occur.
"""
import os

import jax.numpy as jnp
import pytest

from bench import spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def _driver(root, workload, seconds):
    bench = spec.load_benchmark(root)
    cell = spec.workload(bench, workload)
    cfg, traffic = spec.rehearsal_sizes(
        spec.load_config(root, bench, cell["config"]),
        spec.load_traffic(root, cell["traffic"]))
    mod = spec.load_driver(root, traffic["kind"])
    d = mod.Driver(cfg, traffic, 20260917, seconds, log=lambda m: None,
                   annotate=lambda name: _Null())
    d.setup()
    d.root, d.workload = root, workload
    return d


def _judge(d, control=False):
    attempted, failed, checks = d.check(control=control)
    return spec.judge(attempted, failed, checks,
                      spec.load_limits(d.root, d.workload))


def _roll_rows(c, every: int):
    """The assignment shares with every `every`-th row's one-hot moved one
    cluster on."""
    return c.at[::every].set(jnp.roll(c[::every], 1, axis=1))


@pytest.fixture(scope="module",
                params=["fit.dense", "fit.sparse", "fit.horizontal"])
def fit_driver(request):
    """The driver of each fit cell at rehearsal size; `fit.horizontal` is a
    copy of `fit.dense`'s deployment with its rows split 3:1 between the
    parties, from a benchmark tree that adds it (conftest.py)."""
    root = ROOT
    if request.param == "fit.horizontal":
        root = request.getfixturevalue("horizontal_tree")
    return _driver(root, request.param, 0.5)


def _fit_window(d):
    d.fits, d.failures = [], []
    d.window()
    d.finish()
    assert d.fits


def test_fit_sound_window_is_correct(fit_driver):
    _fit_window(fit_driver)
    correct, compared = _judge(fit_driver)
    assert correct, compared


def test_fit_control_is_not_correct(fit_driver):
    _fit_window(fit_driver)
    correct, compared = _judge(fit_driver, control=True)
    assert not correct, compared


def _fault_finalize_unchanged(K, monkeypatch):
    real = K.finalize_program

    def finalize_program(*a, **kw):
        p = real(*a, **kw)
        return p._replace(fn=lambda mu0, mu1, *rest: (mu0, mu1))
    monkeypatch.setattr(K, "finalize_program", finalize_program)


def _fault_half_batch(K, monkeypatch):
    real = K.fit_batch_programs

    def fit_batch_programs(*a, **kw):
        p = real(*a, **kw)

        def s3p(xa, xb, c0, c1, *rest):
            half = c0.shape[0] // 2
            return p.s3p(xa, xb, c0.at[half:].set(0), c1.at[half:].set(0),
                         *rest)
        return p._replace(s3p=s3p)
    monkeypatch.setattr(K, "fit_batch_programs", fit_batch_programs)


def _fault_altered_assignment(K, monkeypatch):
    real = K.fit_batch_programs

    def fit_batch_programs(*a, **kw):
        p = real(*a, **kw)

        def s1(*args):
            c0, c1 = p.s1(*args)
            return _roll_rows(c0, 64), _roll_rows(c1, 64)
        return p._replace(s1=s1)
    monkeypatch.setattr(K, "fit_batch_programs", fit_batch_programs)


@pytest.mark.parametrize("fault", [_fault_finalize_unchanged,
                                   _fault_half_batch,
                                   _fault_altered_assignment])
def test_fit_fault_is_not_correct(fit_driver, monkeypatch, fault):
    from repro.launch import kmeans_step as K
    fault(K, monkeypatch)
    _fit_window(fit_driver)
    correct, compared = _judge(fit_driver)
    assert not correct, compared
