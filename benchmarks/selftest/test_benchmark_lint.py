"""A lint of BENCHMARK.json against the parts of the contract a file can
show: names, units, files, and that every per-layer metric's cells report
the end-to-end metric it moves."""

import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def one_line(s, limit=200):
    return 1 <= len(s) <= limit and "\n" not in s and "\t" not in s


def test_top_level_keys_and_limits():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51 and isinstance(b["run_seconds"], int)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10
    assert all(one_line(w) for w in b["command"])
    cells = len(b["workloads"])
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(1, cells // 2)
    # the check's budget with the full 24 cells
    assert (2 + 14 * 24) * (b["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_names_units_and_entry_keys():
    b = bench()
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and one_line(c["source"]) \
            and one_line(c["why"])
        assert all(NAME.match(k) for k in c["reduced"]) \
            and len(c["reduced"]) <= 16
        assert c["file"].startswith(tuple(p + "/" for p in b["paths"]))
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"]
        assert set(c["reduced"]) == set(cfg["reduced"])
        assert cfg["guarantees"] and cfg["sizes"] and cfg["rehearse"]
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and one_line(w["why"])
        assert w["config"] in {c["name"] for c in b["configs"]}
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "traffic", w["traffic"] + ".json"))
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names))
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in b["end_to_end"])


def test_every_cell_has_its_metrics_and_layers_move_what_cells_report():
    b = bench()
    cells = [w["name"] for w in b["workloads"]]
    e2e = {m["name"]: m.get("workloads", cells) for m in b["end_to_end"]}
    for cell in cells:
        assert sum(cell in ws for n, ws in e2e.items() if n != "setup_s") >= 1
        assert any(cell in m.get("workloads", cells) for m in b["per_layer"])
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert one_line(m["layer"]) and m["moves"] in e2e
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "layer_metrics", m["name"] + ".json"))
        for cell in m.get("workloads", cells):
            assert cell in e2e[m["moves"]], (m["name"], cell)
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_the_runner_holds_no_cells_name():
    b = bench()
    with open(os.path.join(ROOT, "benchmarks", "run.py")) as f:
        src = f.read()
    for w in b["workloads"]:
        assert w["name"] not in src and w["traffic"] not in src
