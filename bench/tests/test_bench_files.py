"""A deployment plugs in as files: a benchmark tree of its own under a
temporary root, with a toy deployment (config, traffic, driver, generator,
reference, check and limits) added as new files and one new cell in
BENCHMARK.json, runs through the harness without an edit to any file the
tree had."""
import hashlib
import json
import os
import shutil

import pytest

from benchtest import ROOT, harness, small_cell

TOY = os.path.join(ROOT, "bench", "tests", "data", "toy")
SEED = 2**31 + 4099


def digests(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


@pytest.fixture
def tree(tmp_path, monkeypatch):
    """(root, digests of the files it had): a copy of the benchmark with
    the toy deployment added as new files."""
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    before = digests(tmp_path)
    toy = os.path.join(TOY, "bench")
    for d, _, files in os.walk(toy):
        for f in files:
            dst = tmp_path / "bench" / os.path.relpath(os.path.join(d, f),
                                                       toy)
            assert not dst.exists()
            dst.parent.mkdir(parents=True, exist_ok=True)
            shutil.copy(os.path.join(d, f), dst)
    bench = harness.load_json(tmp_path, "BENCHMARK.json")
    added = harness.load_json(TOY, "workloads.json")
    for kind in ("configs", "workloads"):
        bench[kind] += added[kind]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    small_cell(monkeypatch)
    return str(tmp_path), before


@pytest.mark.parametrize("broken", [False, True])
def test_toy_deployment_runs_from_new_files(tree, monkeypatch, broken):
    root, before = tree
    if broken:
        path = harness.module(root, "bench/paths/toy_sum.py")
        add = path.add
        monkeypatch.setattr(path, "add", lambda s, k, v: add(s, k, 2 * v))
    res = harness.run_cell("toy.sum", SEED, 1.0, False, 0.0,
                           require_chip=False, root=root)
    assert res["correct"] is not broken, res["check"]
    assert (res["attempted"], res["failed"]) == (19968, 0)  # 39 batches
    assert set(res["metrics"]) == {"persist_bytes_per_event",
                                   "peak_device_bytes", "setup_s"}
    after = digests(root)
    before.pop("BENCHMARK.json")
    assert {p: after[p] for p in before} == before


def test_toy_control_is_not_correct(tree):
    from bench import compare, control

    root, _ = tree
    _, _, config, traffic = harness.cell_spec("toy.sum", root)
    limits = compare.load_limits(os.path.join(root, "bench"), "toy.sum")
    numbers = control.readings(config, traffic, SEED, 19968, limits,
                               root=root)
    correct, table = compare.judge(numbers, limits)
    assert not correct, table
