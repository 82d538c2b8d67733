"""BENCHMARK.json names only things the harness can find by name."""
import json
import re

import pytest

import harness
from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def bench():
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def test_every_name_resolves_to_its_files(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    for c in configs.values():
        assert NAME.match(c["name"]) and (ROOT / c["file"]).is_file()
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        cell = harness.load_cell(BENCH, w["name"])
        assert (cell.cell["config"], cell.cell["traffic"]) == (w["config"], w["traffic"])
        assert ROOT / configs[w["config"]]["file"] == BENCH / "configs" / f"{w['config']}.json"
    for m in bench["per_layer"]:
        assert NAME.match(m["name"]) and (BENCH / "layer_metrics" / f"{m['name']}.py").is_file()
        assert callable(harness.load_reader(m["name"]))


def test_each_cell_reports_setup_another_metric_and_a_layer(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        mine = {n for n, m in e2e.items() if w["name"] in m.get("workloads", [w["name"]])}
        assert "setup_s" in mine and len(mine) >= 2
        layers = [m for m in bench["per_layer"] if w["name"] in m.get("workloads", [w["name"]])]
        assert layers
        for m in layers:
            assert m["moves"] in mine, (w["name"], m["name"])
    for m in bench["end_to_end"]:
        assert 0 < m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
