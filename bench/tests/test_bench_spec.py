"""BENCHMARK.json names only files that exist, and every cell reports
set-up, another end-to-end metric and a per-layer metric."""

import json
import re
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _reported(cell, group):
    return {m["name"] for m in SPEC[group]
            if "workloads" not in m or cell in m["workloads"]}


def test_every_piece_is_a_file_found_by_name():
    bench = ROOT / "bench"
    for c in SPEC["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert (bench / "configs" / f"{c['name']}.py").is_file()
    for w in SPEC["workloads"]:
        mix = json.loads((bench / "traffic" / f"{w['traffic']}.json")
                         .read_text())
        assert (bench / "drivers" / f"{mix['driver']}.py").is_file()
        assert w["name"] == f"{w['config']}.{w['traffic']}"
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert (bench / "metrics" / f"{m['name']}.py").is_file()
        assert NAME.match(m["name"])


def test_every_cell_reports_setup_another_metric_and_a_layer():
    for w in SPEC["workloads"]:
        e2e = _reported(w["name"], "end_to_end")
        assert "setup_s" in e2e and len(e2e) >= 2
        layers = [m for m in SPEC["per_layer"]
                  if w["name"] in m.get("workloads", [w["name"]])]
        assert layers
        for m in layers:  # what a layer metric moves, its cell reports
            assert m["moves"] in e2e


def test_bounds_and_run_length_within_the_contract():
    assert 1 <= SPEC["run_seconds"] <= 51
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= 1
