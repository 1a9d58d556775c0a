"""BENCHMARK.json names only what exists, in the characters allowed."""
import json
import os
import re

import pytest
from benchlib.registry import BENCH_DIR, ROOT, load_cell

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LINE = re.compile(r"^[^\t\n\r]{1,200}$")


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys():
    assert set(bench()) == {"command", "paths", "run_seconds", "configs",
                            "workloads", "end_to_end", "per_layer"}


@pytest.mark.parametrize("section", ["configs", "workloads", "end_to_end",
                                     "per_layer"])
def test_names_and_units_use_allowed_characters(section):
    entries = bench()[section]
    names = [e["name"] for e in entries]
    assert len(names) == len(set(names))
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
        for key in ("config", "traffic"):
            if key in e:
                assert NAME.match(e[key]), e[key]
        for key in ("why", "layer", "source"):
            if key in e:
                assert LINE.match(e[key]), e[key]
        if "unit" in e:
            assert UNIT.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
        for k in e.get("reduced", ()):
            assert NAME.match(k), k


def test_every_cell_finds_its_files_and_reports_what_it_must():
    b = bench()
    e2e = {m["name"] for m in b["end_to_end"]}
    for w in b["workloads"]:
        cell = load_cell(w["name"])
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer, w["name"]
        for m in cell.per_layer:
            assert m["moves"] in reported, (m["name"], w["name"])
            assert m["moves"] in e2e
            cell.reader(m["name"])
        assert os.path.isfile(os.path.join(BENCH_DIR, "drivers",
                                           cell.config["driver"] + ".py"))
        assert set(cell.limits["limits"]) >= {"loss_gap"}


def test_bounds_and_paths():
    b = bench()
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert "bound" not in m
    for p in b["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    for c in b["configs"]:
        assert any(c["file"].startswith(p + "/") for p in b["paths"])
    assert 1 <= b["run_seconds"] <= 51
