"""Helpers shared by the benchmark's CPU tests: import paths, and the
cells the tests drive, cut to the size their files' ``"cpu"`` blocks give
(same layers, same code paths, seconds on the CPU)."""
import copy
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


def all_cells(root=ROOT) -> dict:
    """BENCHMARK.json's cells, and those left out of it whose files are
    kept (``kept_cells.json``), by name."""
    bench = harness.load_json(root, "BENCHMARK.json")
    kept = harness.load_json(HERE, "kept_cells.json")
    return {w["name"]: w for w in bench["workloads"] + kept}


CELLS = sorted(all_cells())


def cpu_cut(doc: dict) -> dict:
    """``doc`` with its ``"cpu"`` block laid over it, group by group."""
    def lay(into, over):
        for k, v in over.items():
            if isinstance(v, dict):
                lay(into[k], v)
            else:
                into[k] = v
    out = copy.deepcopy(doc)
    lay(out, doc["cpu"])
    return out


def small_spec(name, root=ROOT):
    """``harness.cell_spec`` at the CPU size."""
    bench = harness.load_json(root, "BENCHMARK.json")
    cell = all_cells(root)[name]
    files = {c["name"]: c["file"] for c in bench["configs"]}
    config = harness.load_json(root, files[cell["config"]])
    traffic = harness.load_json(root, "bench", "traffic",
                                f"{cell['traffic']}.json")
    return bench, cell, cpu_cut(config), cpu_cut(traffic)


def small_cell(monkeypatch):
    """Make ``harness.cell_spec`` hand out CPU-sized cells."""
    monkeypatch.setattr(harness, "cell_spec", small_spec)
