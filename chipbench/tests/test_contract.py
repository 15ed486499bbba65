"""BENCHMARK.json against the static rules of the benchmark's contract
(names, lengths, keys, files), and every name it gives has its file."""

import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_benchmark_json_keeps_the_contract():
    raw = open(os.path.join(ROOT, "BENCHMARK.json")).read()
    assert len(raw.encode()) <= 64 * 1024
    b = json.loads(raw)
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(b["command"]) <= 32 and all(line(w) for w in b["command"])
    assert not any(w.startswith("/") or ".." in w for w in b["command"])
    assert 1 <= len(b["paths"]) <= 16 and all(PATH.match(p)
                                              for p in b["paths"])
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (b["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200

    assert 1 <= len(b["configs"]) <= 24
    files = set()
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line(c["source"]) and line(c["why"])
        assert any(c["file"].startswith(p.rstrip("/") + "/")
                   for p in b["paths"]) and PATH.match(c["file"])
        assert c["file"] not in files
        files.add(c["file"])
        assert len(c["reduced"]) <= 16 and all(NAME.match(k)
                                               for k in c["reduced"])
        doc = json.load(open(os.path.join(ROOT, c["file"])))
        assert sorted(doc["reduced"]) == sorted(c["reduced"])
        assert doc["name"] == c["name"]

    cells = b["workloads"]
    assert 1 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in {c["name"] for c in b["configs"]}
        assert w["chips"] in (1, 4) and line(w["why"])
        assert os.path.exists(os.path.join(
            ROOT, "chipbench", "traffic", w["traffic"] + ".json"))
    assert {w["config"] for w in cells} == {c["name"] for c in b["configs"]}
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 2)

    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(set(names)) == len(names)
    cell_names = {w["name"] for w in cells}
    assert 1 <= len(b["end_to_end"]) <= 16
    e2e = {}
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m.get("workloads", cell_names)) <= cell_names
        e2e[m["name"]] = set(m.get("workloads", cell_names))
        assert os.path.exists(os.path.join(
            ROOT, "chipbench", "metrics", m["name"] + ".py"))
    assert e2e["setup_s"] == cell_names
    assert 1 <= len(b["per_layer"]) <= 128
    layered = set()
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert line(m["layer"]) and m["moves"] in e2e
        where = set(m.get("workloads", e2e[m["moves"]]))
        assert where <= e2e[m["moves"]], m["name"]
        layered |= where
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
        assert os.path.exists(os.path.join(
            ROOT, "chipbench", "layers", m["name"] + ".py"))
    for w in cell_names:
        assert any(w in cs for n, cs in e2e.items() if n != "setup_s"), w
        assert w in layered, w


def test_only_named_characters_in_file_names():
    for base, _, fs in os.walk(os.path.join(ROOT, "chipbench")):
        if "__pycache__" in base or "/." in base:
            continue
        for f in fs:
            if f.startswith(".") or f.endswith(".pyc"):
                continue
            rel = os.path.relpath(os.path.join(base, f), ROOT)
            assert PATH.match(rel), rel
