"""The harness finds each configuration, traffic mix, driver and metric by
its name, refuses malformed files, and BENCHMARK.json keeps to the shape the
harness and the check rely on."""
import json
import os
import re
import shutil

import pytest

from bench import spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark(ROOT)


@pytest.fixture
def tree(tmp_path):
    """A copy of BENCHMARK.json and bench/ to break."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    return tmp_path


def test_every_cell_finds_its_files_by_name(bench):
    for cell in bench["workloads"]:
        w = spec.workload(bench, cell["name"])
        cfg = spec.load_config(ROOT, bench, w["config"])
        traffic = spec.load_traffic(ROOT, w["traffic"])
        assert cfg["name"] == w["config"]
        assert hasattr(spec.load_driver(ROOT, traffic["kind"]), "Driver")
        assert spec.load_limits(ROOT, cell["name"]), cell["name"]
        assert spec.end_to_end_for(bench, w)
        assert spec.per_layer_for(bench, w)


def test_every_metric_file_agrees_with_benchmark_json(bench):
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        mod = spec.load_metric(ROOT, m["name"])
        assert mod.LAYER == m["layer"] and mod.MOVES == m["moves"]
        assert m["moves"] in e2e


def test_contract_shape(bench):
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    names += [c["name"] for c in bench["workloads"] + bench["configs"]]
    assert len(names) == len(set(names))
    for n in names:
        assert spec.NAME.match(n), n
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])
    for m in bench["per_layer"]:
        if "roofline" in m["name"]:
            assert m["unit"] == "%"
    pairs = [(c["config"], c["traffic"]) for c in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_configs_list_what_they_cut(bench):
    for entry in bench["configs"]:
        cfg = spec.load_config(ROOT, bench, entry["name"])
        assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
        assert len(entry["source"]) <= 200 and len(cfg["source"]) <= 200


@pytest.mark.parametrize("what", ["config", "traffic", "metric", "driver"])
def test_missing_file_is_refused(tree, what):
    b = spec.load_benchmark(str(tree))
    with pytest.raises(spec.SpecError):
        if what == "config":
            b["configs"][0]["file"] = "bench/configs/nothing.json"
            spec.load_config(str(tree), b, b["configs"][0]["name"])
        elif what == "traffic":
            spec.load_traffic(str(tree), "nothing")
        elif what == "metric":
            spec.load_metric(str(tree), "nothing")
        else:
            spec.load_driver(str(tree), "nothing")


def test_malformed_files_are_refused(tree):
    b = spec.load_benchmark(str(tree))
    name = b["configs"][0]["name"]
    path = tree / b["configs"][0]["file"]
    cfg = json.loads(path.read_text())
    path.write_text("{not json")
    with pytest.raises(spec.SpecError, match="not JSON"):
        spec.load_config(str(tree), b, name)
    path.write_text(json.dumps(dict(cfg, k=0)))
    with pytest.raises(spec.SpecError, match="'k'"):
        spec.load_config(str(tree), b, name)
    path.write_text(json.dumps(dict(cfg, name="other")))
    with pytest.raises(spec.SpecError, match="does not hold"):
        spec.load_config(str(tree), b, name)
    (tree / "bench/traffic/bad.json").write_text(json.dumps({"kind": "a b"}))
    with pytest.raises(spec.SpecError, match="kind"):
        spec.load_traffic(str(tree), "bad")
    (tree / "bench/metrics/half.py").write_text("LAYER = 'x'\n")
    with pytest.raises(spec.SpecError, match="MOVES"):
        spec.load_metric(str(tree), "half")
    with pytest.raises(spec.SpecError, match="not a name"):
        spec.load_traffic(str(tree), "../escape")
    with pytest.raises(spec.SpecError, match="no workload"):
        spec.workload(b, "nothing")


def test_a_device_missing_from_the_peaks_table_is_an_error():
    assert spec.peaks_for(ROOT, "TPU v5 lite")["int8_ops_per_s"] == 393e12
    with pytest.raises(spec.SpecError, match="peaks"):
        spec.peaks_for(ROOT, "TPU v9 imaginary")


def test_a_new_cell_needs_only_new_files(tree):
    """A cell added by entries in BENCHMARK.json, with its configuration,
    traffic mix and metric added as files, is found without an edit to any
    existing file."""
    b = json.loads((tree / "BENCHMARK.json").read_text())
    cfg = json.loads(
        (tree / "bench/configs/ieee-cis-vertical.json").read_text())
    (tree / "bench/configs/ieee-cis-all-rows.json").write_text(
        json.dumps(dict(cfg, name="ieee-cis-all-rows", n=590540)))
    t = json.loads((tree / "bench/traffic/fit_stream.json").read_text())
    (tree / "bench/traffic/fit_stream_b.json").write_text(
        json.dumps(dict(t, why="another mix")))
    (tree / "bench/metrics/fit.count.py").write_text(
        "LAYER = 'fit loop and pipeline'\n"
        "MOVES = 'fit_rows_per_s'\n"
        "def read(run):\n    return run.counters.get('fits')\n")
    b["configs"].append({"name": "ieee-cis-all-rows", "source": "x",
                         "file": "bench/configs/ieee-cis-all-rows.json",
                         "reduced": [], "why": "every transaction"})
    b["workloads"].append({"name": "fit.all_rows",
                           "config": "ieee-cis-all-rows",
                           "traffic": "fit_stream_b", "chips": 1,
                           "why": "all rows"})
    for m in b["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append("fit.all_rows")
    b["per_layer"].append({"name": "fit.count", "unit": "fits",
                           "better": "higher", "source": "program_counter",
                           "layer": "fit loop and pipeline",
                           "moves": "fit_rows_per_s",
                           "workloads": ["fit.all_rows"]})
    (tree / "BENCHMARK.json").write_text(json.dumps(b))
    b = spec.load_benchmark(str(tree))
    cell = spec.workload(b, "fit.all_rows")
    assert spec.load_config(str(tree), b, cell["config"])["n"] == 590540
    traffic = spec.load_traffic(str(tree), cell["traffic"])
    assert traffic["why"] == "another mix"
    assert hasattr(spec.load_driver(str(tree), traffic["kind"]), "Driver")
    assert "fit_rows_per_s" in [m["name"]
                                for m in spec.end_to_end_for(b, cell)]
    assert [m["name"] for m in spec.per_layer_for(b, cell)] == ["fit.count"]
    assert spec.load_metric(str(tree), "fit.count").read(
        spec.Run(cell, {}, {}, None, {}, {"fits": 3}, None, [])) == 3


def _without(cfg, key):
    return {k: v for k, v in cfg.items() if k != key}


HORIZONTAL = {"partition": "horizontal", "n": 2000, "n_a": 1500}
PARTITION_CASES = {
    "horizontal_loads": (HORIZONTAL, ["d_a"], None),
    "horizontal_without_n_a": (HORIZONTAL, ["d_a", "n_a"], "'n_a'"),
    "horizontal_n_a_zero": (dict(HORIZONTAL, n_a=0), ["d_a"], "'n_a'"),
    "horizontal_n_a_all_rows": (dict(HORIZONTAL, n_a=2000), ["d_a"],
                                "n_a < n"),
    "horizontal_n_a_past_n": (dict(HORIZONTAL, n_a=2001), ["d_a"],
                              "n_a < n"),
    "horizontal_with_d_a": (HORIZONTAL, [], "'d_a'"),
    "unknown_partition": ({"partition": "diagonal"}, [], "'partition'"),
    "no_partition": ({}, ["partition"], "'partition'"),
    "vertical_d_a_all_columns": ({"d_a": 432}, [], "d_a < d"),
    "vertical_with_n_a": ({"n_a": 10}, [], "'n_a'"),
}


@pytest.mark.parametrize("case", sorted(PARTITION_CASES))
def test_a_config_states_its_partition_and_split(tree, case):
    """A configuration loads only with the split its partition needs:
    vertical `0 < d_a < d`, horizontal `0 < n_a < n` and no `d_a`; each
    other case is refused with an error that names the key."""
    update, drop, refused = PARTITION_CASES[case]
    b = spec.load_benchmark(str(tree))
    path = tree / "bench/configs/ieee-cis-vertical.json"
    cfg = dict(json.loads(path.read_text()), **update)
    for key in drop:
        cfg = _without(cfg, key)
    path.write_text(json.dumps(cfg))
    if refused is None:
        got = spec.load_config(str(tree), b, "ieee-cis-vertical")
        assert (got["partition"], got["n_a"]) == ("horizontal", 1500)
    else:
        with pytest.raises(spec.SpecError, match=refused):
            spec.load_config(str(tree), b, "ieee-cis-vertical")


def test_a_new_horizontal_cell_needs_only_new_files(horizontal_tree):
    """A horizontal cell added as a later PR would add it (a configuration
    file, entries in BENCHMARK.json, a limits file) finds every file it
    needs, and its CPU rehearsal keeps the parties' share of the rows."""
    b = spec.load_benchmark(horizontal_tree)
    cell = spec.workload(b, "fit.horizontal")
    cfg = spec.load_config(horizontal_tree, b, cell["config"])
    assert (cfg["partition"], cfg["n"], cfg["n_a"]) == \
        ("horizontal", 144233, 108174)
    traffic = spec.load_traffic(horizontal_tree, cell["traffic"])
    assert hasattr(spec.load_driver(horizontal_tree, traffic["kind"]),
                   "Driver")
    assert spec.load_limits(horizontal_tree, cell["name"])
    assert spec.end_to_end_for(b, cell)
    for m in spec.per_layer_for(b, cell):
        assert spec.load_metric(horizontal_tree, m["name"]).MOVES \
            == m["moves"]
    small, _ = spec.rehearsal_sizes(cfg, traffic)
    assert (small["n"], small["n_a"], small["d"]) == (2000, 1500, 64)
    assert "d_a" not in small


@pytest.mark.parametrize("n,n_a,want", [(144233, 108174, 1500),
                                        (590540, 439670, 1489),
                                        (10 ** 6, 1, 1),
                                        (10 ** 6, 10 ** 6 - 1, 1999),
                                        (1500, 1000, 1000)])
def test_rehearsal_keeps_the_row_split(n, n_a, want):
    cfg = {"partition": "horizontal", "n": n, "n_a": n_a, "d": 392}
    small, _ = spec.rehearsal_sizes(cfg, {})
    assert small["n_a"] == want and 0 < small["n_a"] < small["n"]
