"""Find a cell's files by name: BENCHMARK.json, its configuration, its
traffic mix, its correctness limits, its per-layer metric readers, and its
model family's reference and weight loader.

Everything that belongs to one configuration, one traffic mix or one metric
lives in a file of its own under ``bench/``; adding a cell adds files and an
entry in ``BENCHMARK.json`` and edits nothing here.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def workload(name: str) -> dict:
    """The cell ``name`` with its configuration, traffic, limits and the
    metrics it reports, resolved from BENCHMARK.json."""
    spec = benchmark()
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    entry = configs[cell["config"]]
    config = load_json(ROOT / entry["file"])
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    limits = load_json(BENCH / "limits" / f"{name}.json")

    def reported(metrics):
        return [m for m in metrics
                if "workloads" not in m or name in m["workloads"]]

    return dict(name=name, chips=int(cell["chips"]), config=config,
                traffic=traffic, limits=limits,
                end_to_end=reported(spec["end_to_end"]),
                per_layer=reported(spec["per_layer"]))


def _module(kind: str, name: str):
    """``bench/<kind>/<name>.py``, loaded by its path."""
    path = BENCH / kind / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    return _module("metrics", name).read


def reference(name: str):
    """The plain reference of a model family,
    ``bench/reference/<name>.py``."""
    return _module("reference", name)


def loader(name: str):
    """What puts the reference's seeded weights into the program's
    parameter tree for a model family, ``bench/loaders/<name>.py``."""
    return _module("loaders", name)
