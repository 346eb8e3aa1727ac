"""Every name in BENCHMARK.json resolves to a file of the benchmark, and
the file keeps to the benchmark's contract."""

import importlib
import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert BENCH["command"][1] == "chipbench/run.py"
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_files(cell):
    from chipbench.harness import BENCH_DIR, resolve

    w = next(w for w in BENCH["workloads"] if w["name"] == cell)
    c = next(c for c in BENCH["configs"] if c["name"] == w["config"])
    assert (ROOT / c["file"]).is_file()
    assert (BENCH_DIR / "traffic" / f"{w['traffic']}.json").is_file()
    resolved = resolve(cell)
    assert resolved.chips == w["chips"] in (1, 4)
    driver = importlib.import_module(f"chipbench.drivers.{resolved.driver}")
    assert hasattr(driver, "Session")
    importlib.import_module(
        f"chipbench.references.{resolved.config['reference']}")
    names = {m["name"] for m in resolved.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert resolved.per_layer


@pytest.mark.parametrize("metric", [m["name"] for m in METRICS])
def test_metric_has_reader(metric):
    if metric == "setup_s":
        return
    mod = importlib.import_module(f"chipbench.metrics.{metric}")
    assert callable(mod.read)


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry_keeps_contract(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert set(metric.get("workloads", CELLS)) <= set(CELLS)
    if metric in BENCH["end_to_end"]:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
        assert metric["source"] in ("device_trace", "program_span",
                                    "program_counter", "host_clock")
        assert 1 <= len(metric["layer"]) <= 200
    if metric["name"].endswith("_roofline"):
        assert metric["unit"] == "%"


def test_whys_are_one_short_line():
    for entry in BENCH["configs"] + BENCH["workloads"]:
        assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
        assert "\t" not in entry["why"]


def test_configs_are_used_and_named():
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        assert NAME.match(c["name"])
        assert c["file"].startswith("chipbench/")
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"]
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
