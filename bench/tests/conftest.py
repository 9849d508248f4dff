"""Test set-up of the benchmark's own tests: the repository root and `src`
on the import path (for `bench.*` and `repro.*`), 64-bit JAX types, which
the ring arithmetic needs, and a benchmark tree that adds a horizontally
partitioned cell."""
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

@pytest.fixture(scope="session")
def horizontal_tree(tmp_path_factory) -> str:
    """A copy of BENCHMARK.json and bench/ that adds, as a later cell would
    (new files and new entries only), a horizontal copy of
    `ieee-cis-vertical` with its rows split 3:1 between the parties and a
    one-chip cell `fit.horizontal` that fits it under the `fit_stream` mix
    and `fit.dense`'s limits."""
    root = str(tmp_path_factory.mktemp("horizontal"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench_json = os.path.join(root, "BENCHMARK.json")
    with open(bench_json) as fh:
        b = json.load(fh)
    configs = os.path.join(root, "bench", "configs")
    with open(os.path.join(configs, "ieee-cis-vertical.json")) as fh:
        cfg = json.load(fh)
    del cfg["d_a"]
    cfg.update(name="ieee-cis-horizontal", partition="horizontal",
               n_a=3 * cfg["n"] // 4)
    with open(os.path.join(configs, "ieee-cis-horizontal.json"), "w") as fh:
        json.dump(cfg, fh)
    limits = os.path.join(root, "bench", "limits")
    shutil.copy(os.path.join(limits, "fit.dense.json"),
                os.path.join(limits, "fit.horizontal.json"))
    b["configs"].append({"name": "ieee-cis-horizontal", "source": "x",
                         "file": "bench/configs/ieee-cis-horizontal.json",
                         "reduced": [], "why": "rows split 3:1"})
    b["workloads"].append({"name": "fit.horizontal",
                           "config": "ieee-cis-horizontal",
                           "traffic": "fit_stream", "chips": 1,
                           "why": "a horizontal dense fit"})
    for m in b["end_to_end"] + b["per_layer"]:
        if "fit.dense" in m.get("workloads", ()):
            m["workloads"].append("fit.horizontal")
    with open(bench_json, "w") as fh:
        json.dump(b, fh)
    return root
