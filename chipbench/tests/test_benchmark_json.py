"""``BENCHMARK.json`` keeps to the shape the benchmark's contract sets,
and every name in it resolves to a file of the benchmark."""
import json
import os
import re

import pytest

from chipbench import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")


@pytest.fixture(scope="module")
def bench():
    path = os.path.join(harness.ROOT, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    with open(path) as f:
        return json.load(f)


def _line(s):
    assert isinstance(s, str) and 1 <= len(s) <= 200
    assert "\n" not in s and "\t" not in s


def test_top_level(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(bench["paths"]) <= 16
    for p in bench["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
    cmd = bench["command"]
    assert 1 <= len(cmd) <= 32
    for w in cmd:
        _line(w)
        if "/" in w:
            assert any(w.startswith(p + "/") for p in bench["paths"])
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    # a full check with 24 cells fits its 43200 s
    assert (2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200


def test_configs(bench):
    assert 1 <= len(bench["configs"]) <= 24
    files = set()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"])
        _line(c["source"])
        _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        assert os.path.isfile(os.path.join(harness.ROOT, c["file"]))
        assert c["file"] not in files
        files.add(c["file"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
    assert len({c["source"] for c in bench["configs"]}) == \
        len(bench["configs"])
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}


def test_workloads(bench):
    cells = bench["workloads"]
    assert 1 <= len(cells) <= 24
    assert len({c["name"] for c in cells}) == len(cells)
    assert len({(c["config"], c["traffic"]) for c in cells}) == len(cells)
    for c in cells:
        assert set(c) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(c["name"]) and NAME.match(c["traffic"])
        assert c["chips"] in (1, 4)
        _line(c["why"])
        assert os.path.isfile(os.path.join(harness.BENCH_DIR, "traffic",
                                           c["traffic"] + ".json"))
    assert sum(c["chips"] == 4 for c in cells) <= max(1, len(cells) // 2)


def test_metrics(bench):
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(set(names)) == len(names)
    cells = {c["name"] for c in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert 1 <= len(e2e) <= 16 and "setup_s" in e2e
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert 1 <= len(bench["per_layer"]) <= 128
    layers = {}
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in e2e
        _line(m["layer"])
        assert os.path.isfile(os.path.join(harness.BENCH_DIR, "metrics",
                                           m["name"] + ".py"))
        for c in m.get("workloads", []):
            assert c in cells
            assert "workloads" not in e2e[m["moves"]] \
                or c in e2e[m["moves"]]["workloads"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for c in cells:
        got_e2e, got_per = harness.cell_metrics(bench, c)
        assert "setup_s" in {m["name"] for m in got_e2e}
        assert len(got_e2e) >= 2 and got_per


def test_layers_named_as_perf_md_lists_them(bench):
    with open(os.path.join(harness.ROOT, "PERF.md")) as f:
        perf = f.read()
    for layer in {m["layer"] for m in bench["per_layer"]}:
        assert f"| {layer} |" in perf, layer
