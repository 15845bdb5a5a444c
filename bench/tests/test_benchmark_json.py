"""BENCHMARK.json and the files it names: every cell, configuration,
traffic mix and metric resolves by name, and the entries keep the
contract's shapes (names, units, lengths, bounds)."""
import json
import os
import re

import pytest

from conftest import BENCH, ROOT
from harness import cell

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    B = json.load(f)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert B["paths"] == ["bench"] and B["command"][1] == "bench/run.py"
    assert 1 <= B["run_seconds"] <= 51
    assert len(json.dumps(B)) < 64 * 1024


def test_names_units_and_text():
    entries = (B["configs"] + B["workloads"] + B["end_to_end"]
               + B["per_layer"])
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
        for k in ("why", "layer", "source"):
            if k in e:
                assert 1 <= len(e[k]) <= 200 and "\n" not in e[k], e
    for m in B["end_to_end"] + B["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    names = [m["name"] for m in B["end_to_end"] + B["per_layer"]]
    assert len(names) == len(set(names))


def test_bounds_and_sources():
    e2e = {m["name"]: m for m in B["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in B["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in B["per_layer"]:
        assert m["moves"] in e2e and "bound" not in m
        # every cell a per-layer metric lists reports the metric it moves
        moved = e2e[m["moves"]].get("workloads")
        assert moved is None or set(m["workloads"]) <= set(moved), m
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


@pytest.mark.parametrize("w", B["workloads"], ids=lambda w: w["name"])
def test_cell_resolves(w):
    spec = cell.load(ROOT, w["name"])
    from repro.configs.base import ModelConfig
    ModelConfig(**spec["config"]["model"])
    assert spec["traffic"]["burst"] == spec["cell"]["slots"]
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(cell.reader(m["name"]))
    lim = spec["cell"]["limits"]
    assert set(lim) == {"target_gap", "draft_gap", "target_rest",
                        "draft_rest"}
    assert all(0 < v < 1 for v in lim.values())


def test_config_files_list_their_cuts():
    for c in B["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            conf = json.load(f)
        assert sorted(conf["reduced"]) == sorted(c["reduced"])
        assert conf["source"] == c["source"]
        for k, v in conf["reduced"].items():
            assert conf[k] == v["held"] != v["published"]
