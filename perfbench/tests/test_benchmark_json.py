"""`BENCHMARK.json` against the limits of the benchmark's contract that can
be checked without a chip: keys, names, units, lengths, files, and that
every cell's metrics have a reader or a measured value behind them."""

import json
import os
import re

from perfbench.tests.helpers import REPO

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
B = json.load(open(os.path.join(REPO, "BENCHMARK.json")))


def _line(s, n=200):
    return isinstance(s, str) and 1 <= len(s) <= n and "\n" not in s and "\t" not in s


def test_top_level_and_sizes():
    assert set(B) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(B["run_seconds"], int) and 1 <= B["run_seconds"] <= 51
    assert 1 <= len(B["command"]) <= 32 and all(_line(w) for w in B["command"])
    assert B["paths"] == ["perfbench"]
    # a full check with 24 cells fits the driver's day
    cells = 24
    assert (2 + 14 * cells) * (B["run_seconds"] + 60) + cells * 180 + 1200 <= 43200


def test_configs_and_workloads():
    names = [c["name"] for c in B["configs"]]
    assert len(set(names)) == len(names)
    used = {w["config"] for w in B["workloads"]}
    for c in B["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith("perfbench/") and os.path.exists(os.path.join(REPO, c["file"]))
        assert len(c["reduced"]) <= 16
        data = json.load(open(os.path.join(REPO, c["file"])))
        assert data["reduced"] == c["reduced"]
        for part in ("dsl",):
            assert os.path.exists(os.path.join(REPO, "perfbench/configs", data[part]))
        for d in ("reference", "flops"):
            assert os.path.exists(os.path.join(REPO, "perfbench", d, data["reference"] + ".py"))
    pairs = [(w["config"], w["traffic"]) for w in B["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(1 for w in B["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(B["workloads"]) // 4)
    for w in B["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and w["config"] in names
        assert w["chips"] in (1, 4) and _line(w["why"])
        wl = json.load(open(os.path.join(REPO, "perfbench/workloads", w["name"] + ".json")))
        assert (wl["config"], wl["traffic"], wl["chips"]) == (w["config"], w["traffic"], w["chips"])
        assert os.path.exists(os.path.join(REPO, "perfbench/traffic", w["traffic"] + ".json"))
        assert os.path.exists(os.path.join(REPO, "perfbench/entries", wl["entry"] + ".py"))
        assert all(0 <= v for v in wl["limits"].values())


def test_metrics():
    cells = {w["name"] for w in B["workloads"]}
    e2e = {m["name"]: m for m in B["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    assert e2e["setup_s"]["bound"] <= 0.1
    all_names = [m["name"] for m in B["end_to_end"] + B["per_layer"]]
    assert len(set(all_names)) == len(all_names)
    for m in B["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for m in B["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert _line(m["layer"]) and m["moves"] in e2e
        assert os.path.exists(os.path.join(REPO, "perfbench/layer_metrics", m["name"] + ".py"))
        moved = e2e[m["moves"]]
        for c in m.get("workloads", cells):
            assert c in moved.get("workloads", cells), (m["name"], c)
    for m in B["end_to_end"] + B["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for c in cells:
        mine = [m for m in B["end_to_end"] if c in m.get("workloads", cells)]
        assert len(mine) >= 2, c                      # setup_s and one more
        assert any(c in m.get("workloads", cells) for m in B["per_layer"]), c
