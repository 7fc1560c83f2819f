"""BENCHMARK.json against the shape the driver checks before any run,
and against the files the harness must find for it."""
import json
import os
import re

from conftest import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_benchmark_json_meets_the_contract():
    path = os.path.join(ROOT, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    b = json.load(open(path))
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["benchmarks"] and len(b["command"]) <= 32
    assert all(line(w) for w in b["command"])
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51
    # a full check with all 24 cells has to fit 43200 s
    assert (2 + 14 * 24) * (b["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200

    configs = {c["name"]: c for c in b["configs"]}
    assert 1 <= len(configs) == len(b["configs"]) <= 24
    files = set()
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line(c["source"]) and line(c["why"])
        assert c["file"].startswith("benchmarks/") and c["file"] not in files
        files.add(c["file"])
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert len(c["reduced"]) <= 16 and all(NAME.match(k)
                                               for k in c["reduced"])
        assert os.path.isfile(os.path.join(
            ROOT, "benchmarks", "families", cfg["family"] + ".py"))
        assert cfg["tolerance"], f"{c['name']} has no measured limits"
        for stat, t in cfg["tolerance"].items():
            if t["limit"] > 0:  # between the two readings, room both sides
                assert (t["worst_sound_reading"] < t["limit"]
                        < t["best_control_reading"]), stat
                assert t["best_control_reading"] > 3 * t[
                    "worst_sound_reading"], stat

    cells = {w["name"]: w for w in b["workloads"]}
    assert 1 <= len(cells) == len(b["workloads"]) <= 24
    pairs = set()
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert line(w["why"]) and (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        tr = json.load(open(os.path.join(ROOT, "benchmarks", "traffic",
                                         w["traffic"] + ".json")))
        assert os.path.isfile(os.path.join(ROOT, "benchmarks", "drivers",
                                           tr["driver"] + ".py"))
    assert {w["config"] for w in b["workloads"]} == set(configs)
    four = sum(w["chips"] == 4 for w in b["workloads"])
    assert four <= max(1, len(cells) // 4)

    names = set()
    e2e = {}
    assert 1 <= len(b["end_to_end"]) <= 16
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1 and m["name"] not in names
        names.add(m["name"])
        e2e[m["name"]] = set(m.get("workloads", cells))
        assert e2e[m["name"]] <= set(cells)
    assert e2e["setup_s"] == set(cells)

    layers_of = {c: 0 for c in cells}
    assert 1 <= len(b["per_layer"]) <= 128
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert line(m["layer"]) and m["name"] not in names
        names.add(m["name"])
        assert m["moves"] in e2e
        listed = set(m.get("workloads", e2e[m["moves"]]))
        assert listed <= e2e[m["moves"]], m["name"]
        for c in listed:
            layers_of[c] += 1
        # a device or span metric needs a reader file of its own; a
        # counter or a host-clock value may be the driver's counter
        stem = m["name"].split(".")
        assert m["source"] in ("program_counter", "host_clock") or any(
            os.path.isfile(os.path.join(
                ROOT, "benchmarks", "readers", ".".join(stem[:n]) + ".py"))
            for n in range(len(stem), 0, -1)), m["name"]
    for c in cells:
        assert layers_of[c] >= 1
        assert any(c in ws for n, ws in e2e.items() if n != "setup_s")
