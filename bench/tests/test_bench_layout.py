"""BENCHMARK.json resolves, by name, every file the harness reads."""
import json
import os
import re

import pytest

from benchtest import ROOT, harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["bench"]
    assert 1 <= bench["run_seconds"] <= 51


@pytest.mark.parametrize("kind", ["configs", "workloads", "end_to_end",
                                  "per_layer"])
def test_names(bench, kind):
    names = [e["name"] for e in bench[kind]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for e in bench[kind]:
        if "unit" in e:
            assert UNIT.match(e["unit"]) and e["better"] in ("lower",
                                                             "higher")


def test_every_cell_resolves(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    for w in bench["workloads"]:
        _, cell, config, traffic = harness.cell_spec(w["name"])
        entry = configs[w["config"]]
        assert entry["file"].startswith("bench/configs/")
        assert config["name"] == w["config"]
        assert config["reduced"] == entry["reduced"]
        assert traffic["driver"] in ("ingest", "serve")
        limits = harness.load_json(harness.BENCH, "limits",
                                   f"{w['name']}.json")["limits"]
        assert limits
        assert w["chips"] == 1


def test_every_metric_has_a_reader(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            assert callable(harness.reader(m["name"]))
    for w in bench["workloads"]:
        mine = harness.metrics_of(bench, w["name"], traced=False)
        assert "setup_s" in [m["name"] for m in mine] and len(mine) >= 2
        layer = harness.metrics_of(bench, w["name"], traced=True)
        assert layer
        for m in layer:
            moved = e2e[m["moves"]]
            assert w["name"] in moved.get("workloads", [w["name"]])


def test_bounds(bench):
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert [m["bound"] for m in bench["end_to_end"]
            if m["name"] == "setup_s"] == [0.25]
