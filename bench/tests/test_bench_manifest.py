"""BENCHMARK.json, the cell and configuration files, the metric readers and
the table of peaks agree with each other and with the naming rules."""
import json
import re
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def manifest():
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def _load(path):
    with open(path) as f:
        return json.load(f)


def test_top_level_keys(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs", "workloads",
                             "end_to_end", "per_layer"}
    assert manifest["paths"] == ["bench"]
    assert manifest["command"][1] == "bench/run.py"
    assert 1 <= manifest["run_seconds"] <= 51


def test_cells_and_configs_exist(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    used = set()
    for cell in manifest["workloads"]:
        assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
        wl = _load(BENCH / "workloads" / f"{cell['traffic']}.json")
        assert wl["name"] == cell["name"] and wl["config"] == cell["config"]
        assert wl["chips"] == cell["chips"] in (1, 4)
        assert wl["why"] == cell["why"] and len(cell["why"]) <= 200
        gaps = {"change_gap_r1", "change_gap_r4"}
        assert set(wl["limits"]) <= gaps | {f"{p}_{g}" for g in gaps for p in ("median", "layer")} | {
            "acc_gap", "init_gap", "retention_gap", "kept_units_mismatch", "repeat_gap"}
        assert cell["config"] in configs
        used.add(cell["config"])
    assert used == set(configs)
    for c in configs.values():
        assert NAME.match(c["name"])
        cfg = _load(ROOT / c["file"])
        assert cfg["name"] == c["name"] and c["file"].startswith("bench/configs/")
        assert cfg["source"] == c["source"]
        for key in c["reduced"]:
            assert NAME.match(key) and key in cfg and key in cfg["published"]
            assert cfg[key] != cfg["published"][key]


def test_metrics_have_readers_and_cells(manifest):
    cells = {c["name"] for c in manifest["workloads"]}
    names = [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]]
    assert len(names) == len(set(names))
    assert {"images_per_s", "setup_s"} <= {m["name"] for m in manifest["end_to_end"]}
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
        assert set(m.get("workloads", cells)) <= cells
    for m in manifest["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in manifest["end_to_end"]}
    layers = {}
    for m in manifest["per_layer"]:
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert m["moves"] in e2e and m["workloads"]
        layers.setdefault(m["layer"], []).append(m["name"])
    for cell in cells:
        assert any(cell in m["workloads"] for m in manifest["per_layer"])


def test_peaks_have_sources():
    peaks = _load(BENCH / "peaks.json")
    assert "TPU v5 lite" in peaks
    for kind, p in peaks.items():
        assert p["source"] and p["bf16_flops_per_s"] > 0 and p["hbm_bytes_per_s"] > 0
