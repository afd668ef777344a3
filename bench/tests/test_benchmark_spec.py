"""BENCHMARK.json keeps to its contract, and every file it names exists."""

import json
import re

import pytest

from bench import spec

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")
PATH = re.compile(r"[A-Za-z0-9_.\-/]{1,200}")
TOP = {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bm():
    return spec.load_benchmark()


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_and_paths(bm):
    assert set(bm) == TOP
    assert 1 <= len(bm["paths"]) <= 16
    for p in bm["paths"]:
        assert PATH.fullmatch(p) and not p.startswith("/") and ".." not in p.split("/")
        assert (spec.ROOT / p).is_dir()
    assert 1 <= len(bm["command"]) <= 32 and all(_line(w) for w in bm["command"])
    assert isinstance(bm["run_seconds"], int) and 1 <= bm["run_seconds"] <= 51
    assert len(json.dumps(bm)) <= 64 * 1024


def test_names_units_and_keys(bm):
    groups = {
        "configs": {"name", "source", "file", "reduced", "why"},
        "workloads": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source"},
        "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
    }
    for group, keys in groups.items():
        names = [e["name"] for e in bm[group]]
        assert len(names) == len(set(names)), group
        for e in bm[group]:
            assert set(e) - {"workloads"} == keys or (group == "configs" and set(e) == keys), e
            assert NAME.fullmatch(e["name"]), e["name"]
            if "unit" in e:
                assert UNIT.fullmatch(e["unit"]) and e["better"] in ("lower", "higher")
                assert e["source"] in SOURCES
    for c in bm["configs"]:
        assert _line(c["source"]) and _line(c["why"]) and len(c["reduced"]) <= 16
        assert all(NAME.fullmatch(k) for k in c["reduced"])
    for w in bm["workloads"]:
        assert NAME.fullmatch(w["config"]) and NAME.fullmatch(w["traffic"]) and _line(w["why"])
        assert w["chips"] in (1, 4)
    for m in bm["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bm["per_layer"]:
        assert _line(m["layer"])


def test_every_named_file_exists(bm):
    configs = {c["name"]: c for c in bm["configs"]}
    for c in bm["configs"]:
        assert c["file"] == f"bench/configs/{c['name']}.json"
        cfg = spec.load_json(spec.config_path(c["name"]))
        assert cfg["name"] == c["name"] and set(c["reduced"]) == set(cfg["reduced"])
        assert any(w["config"] == c["name"] for w in bm["workloads"])
    for w in bm["workloads"]:
        assert w["config"] in configs
        tr = spec.load_json(spec.traffic_path(w["traffic"]))
        assert tr["loop"] in ("open", "closed")
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert spec.reader_path(m["name"]).exists(), m["name"]
        assert callable(spec.load_reader(m["name"]))


def test_every_cell_reports_what_it_must(bm):
    e2e = {m["name"]: m for m in bm["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for w in bm["workloads"]:
        mine = {m["name"] for m in spec.cell_metrics(bm, w["name"], "end_to_end")}
        assert "setup_s" in mine and len(mine) >= 2
        assert spec.cell_metrics(bm, w["name"], "per_layer")
    layers = {}
    for m in bm["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", [w["name"] for w in bm["workloads"]]):
            assert m["moves"] in {x["name"] for x in spec.cell_metrics(bm, cell, "end_to_end")}
        layers.setdefault(m["name"].split(".")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())
    four = sum(w["chips"] == 4 for w in bm["workloads"])
    assert four <= max(1, len(bm["workloads"]) // 2)
