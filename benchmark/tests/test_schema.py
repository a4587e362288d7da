"""``BENCHMARK.json`` against the contract's character rules, and every name
in it against the data files the harness will look for."""

import json
import os
import re

import readers

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_keys_names_units():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    names = []
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line(c["source"]) and line(c["why"])
        assert c["file"].startswith(tuple(p + "/" for p in b["paths"]))
        assert all(NAME.match(k) for k in c["reduced"]) and len(c["reduced"]) <= 16
        names.append(c["name"])
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and line(w["why"])
        assert w["config"] in names and w["chips"] in (1, 4)
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.25
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in SOURCES and line(m["layer"])
        assert m["moves"] in {e["name"] for e in b["end_to_end"]}
    every = b["end_to_end"] + b["per_layer"]
    for m in every:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for group in (names, [w["name"] for w in b["workloads"]], [m["name"] for m in every]):
        assert len(group) == len(set(group))
    assert len({(w["config"], w["traffic"]) for w in b["workloads"]}) == len(b["workloads"])
    assert any(m["name"] == "setup_s" and "workloads" not in m for m in b["end_to_end"])


def test_every_name_has_its_data_file():
    b = bench()
    cells = {w["name"] for w in b["workloads"]}
    for c in b["configs"]:
        with open(os.path.join(ROOT, c["file"]), encoding="utf-8") as f:
            doc = json.load(f)
        assert doc["name"] == c["name"] and doc["source"] == c["source"]
        assert doc["request"] in ("job", "import") and "guarantees" in doc
    for w in b["workloads"]:
        for rel in (f"benchmark/traffic/{w['traffic']}.json", f"benchmark/cells/{w['name']}.json"):
            assert os.path.exists(os.path.join(ROOT, rel)), rel
        with open(os.path.join(ROOT, f"benchmark/cells/{w['name']}.json"), encoding="utf-8") as f:
            assert json.load(f)["why"] == w["why"]
    for m in b["end_to_end"] + b["per_layer"]:
        with open(os.path.join(ROOT, f"benchmark/metrics/{m['name']}.json"), encoding="utf-8") as f:
            spec = json.load(f)
        assert spec["name"] == m["name"] and spec["kind"] in readers.KINDS
        assert set(m.get("workloads", cells)) <= cells


def test_every_cell_reports_setup_another_end_to_end_and_a_per_layer_metric():
    b = bench()
    for w in b["workloads"]:
        here = lambda ms: [m["name"] for m in ms if w["name"] in m.get("workloads", [w["name"]])]
        e2e = here(b["end_to_end"])
        assert "setup_s" in e2e and len(e2e) >= 2 and here(b["per_layer"])
        for m in b["per_layer"]:
            if w["name"] in m.get("workloads", [w["name"]]):
                assert m["moves"] in e2e


def test_file_names_under_paths_use_only_name_characters():
    b = bench()
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for p in b["paths"]:
        for d, dirs, files in os.walk(os.path.join(ROOT, p)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                assert ok.match(os.path.relpath(os.path.join(d, f), ROOT)), f
