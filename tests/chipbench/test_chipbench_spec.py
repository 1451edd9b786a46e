"""``BENCHMARK.json`` keeps the benchmark's contract: its keys, names,
units and lengths, and every file it names exists under its paths."""
from __future__ import annotations

import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def test_top_level_keys_and_sizes():
    assert set(SPEC) == {"command", "paths", "run_seconds", *KEYS}
    assert 1 <= SPEC["run_seconds"] <= 51
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    for p in SPEC["paths"]:
        assert (ROOT / p).is_dir() and ".." not in p.split("/")
    assert len(SPEC["command"]) <= 32
    assert all(not w.startswith("/") for w in SPEC["command"])


@pytest.mark.parametrize("section", list(KEYS))
def test_entries_have_exactly_the_contract_keys(section):
    names = [e["name"] for e in SPEC[section]]
    assert len(names) == len(set(names))
    for e in SPEC[section]:
        extra = {"workloads"} if section in ("end_to_end", "per_layer") \
            else set()
        assert KEYS[section] <= set(e) <= KEYS[section] | extra, e["name"]
        assert NAME.match(e["name"]), e["name"]
        texts = ["why", "layer"] + (["source"] if section == "configs"
                                    else [])
        for key in texts:
            if key in e:
                assert 1 <= len(e[key]) <= 200, (e["name"], key)
                assert "\n" not in e[key] and "\t" not in e[key]
        if "unit" in e:
            assert UNIT.match(e["unit"]) and e["better"] in ("lower",
                                                             "higher")


def test_bounds_and_cells():
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in SPEC["end_to_end"])
    cells = {w["name"]: w for w in SPEC["workloads"]}
    four = sum(w["chips"] == 4 for w in cells.values())
    assert four <= max(1, len(cells) // 2)
    pairs = {(w["config"], w["traffic"]) for w in cells.values()}
    assert len(pairs) == len(cells)
    moved = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in moved
        for w in m.get("workloads", []):
            assert w in cells


def test_every_named_file_exists():
    for c in SPEC["configs"]:
        data = json.loads((ROOT / c["file"]).read_text())
        assert data["name"] == c["name"] and data["reduced"] == c["reduced"]
        assert (ROOT / "chipbench/drivers" / f"{data['driver']}.py").is_file()
        assert (ROOT / "chipbench/references"
                / f"{data['reference']}.py").is_file()
    for w in SPEC["workloads"]:
        assert (ROOT / "chipbench/traffic" / f"{w['traffic']}.json").is_file()
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert (ROOT / "chipbench/metrics" / f"{m['name']}.py").is_file()
