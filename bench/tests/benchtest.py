"""Helpers shared by the benchmark's CPU tests: import paths and cells cut
to a size the CPU runs in seconds (same layers, same code paths)."""
import copy
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402

SMALL_KEYS = 4000
SMALL_EVENTS = 200_000          # per second of the window
# the cells the tests drive, by their configuration and traffic files
CELLS = {"iiot800k.ingest": ("iiot800k", "ingest"),
         "iiot800k.ingest_filled": ("iiot800k", "ingest_filled"),
         "iiot800k.serve": ("iiot800k", "serve_iiot800k")}


def small_cell(monkeypatch, rate=20000.0):
    """Make ``harness.cell_spec`` hand out CPU-sized cells."""
    def small(name, root=harness.ROOT):
        bench = harness.load_json(root, "BENCHMARK.json")
        cfg, trf = CELLS[name]
        cell = {"name": name, "config": cfg, "traffic": trf, "chips": 1}
        config = copy.deepcopy(harness.load_json(
            harness.BENCH, "configs", f"{cfg}.json"))
        traffic = harness.load_json(harness.BENCH, "traffic", f"{trf}.json")
        config["stream"].update(n_keys=SMALL_KEYS)
        config["engine"].update(batch=512, sink_group=4)
        if traffic["driver"] == "ingest":
            traffic.update(chunk_groups=2, window_events_per_s=SMALL_EVENTS)
        else:
            traffic.update(rate_per_s=rate, warmup_requests=256, batch=64)
        return bench, cell, config, traffic

    monkeypatch.setattr(harness, "cell_spec", small)


def cell_files(name):
    """(config, traffic) of a cell, read from its files."""
    cfg, trf = CELLS[name]
    return (harness.load_json(harness.BENCH, "configs", f"{cfg}.json"),
            harness.load_json(harness.BENCH, "traffic", f"{trf}.json"))
