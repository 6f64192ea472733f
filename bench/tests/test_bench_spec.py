"""BENCHMARK.json and the files it names: every cell's configuration,
traffic, limits and per-layer readers exist and are well formed."""

import re

import pytest

from bench import spec
from bench.spec import BENCH, ROOT, load_json

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BM = spec.benchmark()
# widths a configuration may never cut
WIDTHS = {"hidden_size", "intermediate_size", "num_experts_per_tok",
          "moe_intermediate_size", "kv_lora_rank", "q_lora_rank"}
CELLS = [w["name"] for w in BM["workloads"]]


def test_top_level_keys():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert BM["paths"] == ["bench"]
    assert BM["command"] == ["python3", "bench/run.py"]
    assert 1 <= BM["run_seconds"] <= 51


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_names_units_and_bounds():
    for m in BM["end_to_end"] + BM["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "layer", "moves", "workloads"}
    for m in BM["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    names = [m["name"] for m in BM["end_to_end"] + BM["per_layer"]]
    assert len(names) == len(set(names))
    assert "setup_s" in names


def test_entries_fit_their_limits():
    for e in BM["configs"] + BM["workloads"]:
        assert NAME.match(e["name"]) and _line(e["why"])
    for e in BM["configs"]:
        assert _line(e["source"]) and e["source"].startswith("https://")
        assert len(e["reduced"]) <= 16
        for key in e["reduced"]:
            assert NAME.match(key)
            assert not key.endswith(("_dim", "_rank"))
            assert key not in WIDTHS
    for m in BM["per_layer"]:
        assert _line(m["layer"])
    for w in BM["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    pairs = [(w["config"], w["traffic"]) for w in BM["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in BM["workloads"])
    assert four <= max(1, len(BM["workloads"]) // 2)
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024


def test_per_layer_metrics_move_a_reported_metric():
    e2e = {m["name"]: m for m in BM["end_to_end"]}
    for m in BM["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
        moved = e2e[m["moves"]]
        for cell in m["workloads"]:
            assert cell in CELLS
            assert "workloads" not in moved or cell in moved["workloads"]


@pytest.mark.parametrize("name", CELLS)
def test_cell_files(name):
    c = spec.workload(name)
    assert NAME.match(name)
    assert c["chips"] in (1, 4)
    assert c["limits"]["logit_gap_max"]["limit"] > 0
    assert {m["name"] for m in c["end_to_end"]} >= {"setup_s"}
    assert len(c["end_to_end"]) >= 2 and c["per_layer"]
    for m in c["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))


def test_configs_name_their_reductions():
    for entry in BM["configs"]:
        cfg = load_json(ROOT / entry["file"])
        assert entry["file"].startswith("bench/configs/")
        assert set(entry["reduced"]) == set(cfg["reduced"])
        for key, (published, run) in cfg["reduced"].items():
            assert cfg[key] == run != published
        assert callable(spec.reference(cfg["reference"]).logits)
