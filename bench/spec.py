"""Finding a cell's files by name, and refusing malformed ones.

Everything that belongs to one configuration, traffic mix, per-layer metric
or cell sits in a file of its own, found by the name `BENCHMARK.json` gives
it:

    bench/configs/<config>.json     one deployment (sizes, guarantees)
    bench/traffic/<traffic>.json    one traffic mix; its `kind` names
    bench/drivers/<kind>.py         the driver that generates it
    bench/metrics/<metric>.py       one per-layer metric's reader
    bench/limits/<workload>.json    the limits of one cell's check

so a later cell or metric adds files and edits none.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


class SpecError(ValueError):
    """A benchmark file is missing or malformed."""


def _name(value, what: str) -> str:
    if not isinstance(value, str) or not NAME.match(value):
        raise SpecError(f"{what} {value!r} is not a name (letters, digits, "
                        "'_', '.', '-'; at most 64)")
    return value


def _json(path: str, what: str):
    try:
        with open(path) as fh:
            return json.load(fh)
    except FileNotFoundError:
        raise SpecError(f"no {what} file {path}") from None
    except json.JSONDecodeError as e:
        raise SpecError(f"{what} file {path} is not JSON: {e}") from None


def _bench_dir(root: str) -> str:
    return os.path.join(root, "bench")


def load_benchmark(root: str) -> dict:
    b = _json(os.path.join(root, "BENCHMARK.json"), "benchmark")
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        if not isinstance(b.get(key), list) or not b[key]:
            raise SpecError(f"BENCHMARK.json: {key!r} must be a non-empty "
                            "list")
    return b


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w.get("name") == name:
            for key in ("config", "traffic"):
                _name(w.get(key), f"workload {name}: {key}")
            if w.get("chips") not in (1, 4):
                raise SpecError(f"workload {name}: chips must be 1 or 4")
            return w
    raise SpecError(f"BENCHMARK.json has no workload {name!r}")


# partition -> (the key that says where party A's share ends, the size it
# cuts, the other partition's key, which such a configuration must not carry)
SPLIT = {"vertical": ("d_a", "d", "n_a"),
         "horizontal": ("n_a", "n", "d_a")}


def load_config(root: str, bench: dict, name: str) -> dict:
    """A configuration file, refused unless it states its partition and the
    split that goes with it: vertical, party A holds the first `d_a` of the
    `d` features of every row; horizontal, party A holds the first `n_a` of
    the `n` rows, every feature of each."""
    _name(name, "config")
    entry = next((c for c in bench["configs"] if c.get("name") == name),
                 None)
    if entry is None:
        raise SpecError(f"BENCHMARK.json has no config {name!r}")
    cfg = _json(os.path.join(root, entry["file"]), f"config {name}")
    if not isinstance(cfg, dict) or cfg.get("name") != name:
        raise SpecError(f"config file {entry['file']} does not hold "
                        f"config {name!r}")
    partition = cfg.get("partition")
    if partition not in SPLIT:
        raise SpecError(f"config {name}: 'partition' must be one of "
                        f"{sorted(SPLIT)}, not {partition!r}")
    split, whole, other = SPLIT[partition]
    for key in ("n", "d", "k", "iters", "ring_bits", "frac_bits", split):
        if not isinstance(cfg.get(key), int) or cfg[key] < 1:
            raise SpecError(f"config {name}: {key!r} must be a positive "
                            "integer")
    if not cfg[split] < cfg[whole]:
        raise SpecError(f"config {name}: need 0 < {split} < {whole}")
    if other in cfg:
        raise SpecError(f"config {name}: a {partition} configuration "
                        f"splits at {split!r} and carries no {other!r}")
    return cfg


def load_traffic(root: str, name: str) -> dict:
    _name(name, "traffic")
    t = _json(os.path.join(_bench_dir(root), "traffic", f"{name}.json"),
              f"traffic {name}")
    if not isinstance(t, dict):
        raise SpecError(f"traffic {name}: not a JSON object")
    _name(t.get("kind"), f"traffic {name}: kind")
    return t


def _module(path: str, what: str):
    if not os.path.isfile(path):
        raise SpecError(f"no {what} file {path}")
    mod_name = "bench_" + re.sub(r"\W", "_", os.path.relpath(path))
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_driver(root: str, kind: str):
    mod = _module(os.path.join(_bench_dir(root), "drivers", f"{kind}.py"),
                  f"driver {kind}")
    if not hasattr(mod, "Driver"):
        raise SpecError(f"driver {kind} defines no Driver")
    return mod


def load_metric(root: str, name: str):
    _name(name, "metric")
    mod = _module(os.path.join(_bench_dir(root), "metrics", f"{name}.py"),
                  f"metric {name}")
    for attr in ("LAYER", "MOVES", "read"):
        if not hasattr(mod, attr):
            raise SpecError(f"metric {name} defines no {attr}")
    return mod


def load_limits(root: str, workload_name: str) -> dict:
    """{number: limit} of a cell's check; a number with no limit can only
    fail."""
    path = os.path.join(_bench_dir(root), "limits", f"{workload_name}.json")
    if not os.path.isfile(path):
        return {}
    lim = _json(path, f"limits {workload_name}")
    return {k: float(v["limit"]) for k, v in lim.get("limits", {}).items()}


def judge(attempted: int, failed: int, checks: dict, limits: dict):
    """(correct, {number: {"value", "limit"}}): correct when something was
    attempted, nothing failed, and every number compared is within its
    limit. A number without a limit cannot pass."""
    correct = attempted > 0 and failed == 0
    compared = {}
    for name, value in checks.items():
        limit = limits.get(name)
        correct = correct and limit is not None and value <= limit
        compared[name] = {"value": value, "limit": limit}
    return bool(correct), compared


def peaks_for(root: str, device_kind: str, required: bool = True):
    """The device's row of bench/peaks.json. A device missing from the
    table is an error (None only where `required` is off: a rehearsal)."""
    p = _json(os.path.join(_bench_dir(root), "peaks.json"), "peaks")
    row = p.get("devices", {}).get(device_kind)
    if row is None and required:
        raise SpecError(f"device kind {device_kind!r} is not in "
                        "bench/peaks.json")
    return row


def _listed(metric: dict, cell: dict) -> bool:
    return "workloads" not in metric or cell["name"] in metric["workloads"]


def end_to_end_for(bench: dict, cell: dict) -> list:
    return [m for m in bench["end_to_end"] if _listed(m, cell)]


def per_layer_for(bench: dict, cell: dict) -> list:
    """Per-layer metrics of a cell: those that list it, or, without a
    `workloads` key, those whose end-to-end metric the cell reports."""
    e2e = {m["name"] for m in end_to_end_for(bench, cell)}
    out = []
    for m in bench["per_layer"]:
        if "workloads" in m:
            if cell["name"] in m["workloads"]:
                out.append(m)
        elif m.get("moves") in e2e:
            out.append(m)
    return out


def rehearsal_sizes(cfg: dict, traffic: dict):
    """A tiny copy of a cell for a CPU rehearsal: the same paths (a last
    batch shorter than the others included), widths cut to what the CPU
    compiles in seconds. A horizontal split keeps its share of the rows."""
    cfg = dict(cfg)
    n = cfg["n"]
    scale = {"n": 2000, "d": 64, "d_a": 32, "k": 4, "batch_size": 1024}
    for key, v in scale.items():
        if key in cfg and cfg[key] is not None:
            cfg[key] = min(cfg[key], v)
    if "n_a" in cfg:
        cfg["n_a"] = min(max(1, round(cfg["n_a"] * cfg["n"] / n)),
                         cfg["n"] - 1)
    if cfg.get("onehot_groups"):
        cfg["onehot_groups"] = [4] * (cfg["d"] // 4)
    return cfg, dict(traffic)


@dataclasses.dataclass
class Run:
    """What a per-layer metric's reader gets: the cell, its end-to-end
    numbers, the driver's counters, the reduced device trace (None without
    one) and the program's spans from the traced window."""
    cell: dict
    cfg: dict
    traffic: dict
    peaks: dict | None
    e2e: dict
    counters: dict
    trace: dict | None
    spans: list
