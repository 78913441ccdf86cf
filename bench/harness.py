"""One run of one cell: everything between the command line and the result.

The harness is driven by ``BENCHMARK.json``, and finds everything a cell
needs by name, in files under the root it is given:

- A cell names a configuration (the file its ``configs`` entry gives) and
  a traffic mix (``bench/traffic/<traffic>.json``).
- The traffic's ``"driver"`` names the timed path, a module
  ``bench/paths/<driver>.py`` with ``events(config, traffic, seconds)``, the
  stream's events or requests (a window may take the stream more than
  once: ``attempted`` is the window's own count); ``prepare(config,
  traffic, stream, followed, *, seed, seed32, rng, seconds, devices,
  tmp)``, the set-up before the window opens (``devices`` are the cell's
  chips, ``tmp`` a directory the run removes); and ``window(run,
  stream)``, the timed window, which returns a ``drivers.Window``.
- The configuration names, by path, its ``"generator"``, with
  ``make_stream(config, n_events, seed)``; its plain ``"reference"``, with
  ``engine_key_data(seed)`` (the 32-bit key data of the program's random
  draws and the reference's) and ``CONTROL`` (the precision of the check's
  control); and its ``"check"``, with ``follow(config, traffic, stream,
  seed)`` (what the comparison follows, handed to the driver's
  ``prepare``), ``check(reference, config, traffic, window, stream, seed32,
  followed, limits)`` (the numbers ``compare.judge`` holds to the limits,
  and the diagnostics) and ``control(reference, config, traffic, stream,
  seed, followed, limits)`` (the same numbers, with the reference in its
  control precision in the program's place).
- Every metric is read by a reader of its own
  (``bench/metrics/<metric>.py``, a function ``read(rec)`` that returns the
  value or ``None`` where it finds nothing to read), and every cell's
  correctness limits are in ``bench/limits/<cell>.json``.

Adding a cell, configuration, traffic mix, driver or metric adds files,
and edits none.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
import re
import sys
import tempfile
import time
from typing import Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def cell_spec(name: str, root: str = ROOT) -> tuple:
    """(benchmark, cell, config, traffic) for the cell ``name``."""
    bench = load_json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(root, configs[cell["config"]]["file"])
    traffic = load_json(root, "bench", "traffic", f"{cell['traffic']}.json")
    return bench, cell, config, traffic


def module(root: str, path: str):
    """The module in the file ``path`` under ``root``.  Inside this
    checkout, where each part of the path is a name, it is imported by its
    dotted name, so that it is the module the benchmark's own imports
    share; otherwise it is loaded from its file."""
    full = os.path.realpath(os.path.join(root, path))
    stem, ext = os.path.splitext(os.path.relpath(full, os.path.realpath(
        ROOT)))
    if ext != ".py":
        raise ValueError(f"{path} is not a Python file")
    parts = stem.split(os.sep)
    if all(p.isidentifier() for p in parts):
        return importlib.import_module(".".join(parts))
    name = "bench_file_" + re.sub(r"\W", "_", full)
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, full)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[name]
            raise
    return sys.modules[name]


def driver(root: str, traffic: dict):
    """The timed path the traffic names."""
    return module(root, os.path.join("bench", "paths",
                                     f"{traffic['driver']}.py"))


def deployment(root: str, config: dict) -> tuple:
    """The (generator, reference, check) modules the configuration names."""
    return tuple(module(root, config[k])
                 for k in ("generator", "reference", "check"))


def metrics_of(bench: dict, cell: str, traced: bool) -> list:
    """The cell's end-to-end metrics, or with a trace its per-layer ones."""
    group = bench["per_layer" if traced else "end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def reader(name: str, root: str = ROOT):
    return module(root, os.path.join("bench", "metrics", f"{name}.py")).read


@dataclasses.dataclass
class Record:
    """What a metric reader may read."""
    cell: dict
    config: dict
    traffic: dict
    window: object                 # drivers.Window
    setup_s: float
    peak_bytes: int
    device_kind: str
    trace: Optional[object] = None  # trace_reduce.Summary, traced runs


def check_chips(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"the first JAX device is {devs[0].platform}, not a "
                     f"TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX finds "
                     f"{len(devs)}")
    return devs


def run_cell(name: str, seed: int, seconds: float, traced: bool,
             t_start: float, require_chip: bool = True,
             root: str = ROOT) -> dict:
    import jax

    bench, cell, config, traffic = cell_spec(name, root)
    chips = int(cell["chips"])
    devs = check_chips(chips) if require_chip else jax.devices()
    from repro.launch.compile_cache import enable_compile_cache

    from bench import compare, drivers, trace_reduce

    enable_compile_cache()
    path = driver(root, traffic)
    generator, reference, chk = deployment(root, config)
    seed32 = reference.engine_key_data(seed)
    rng = jax.random.PRNGKey(seed32)
    n_events = path.events(config, traffic, seconds)
    t_gen = time.perf_counter()
    with drivers.annotate("bench.generate"):
        stream = generator.make_stream(config, n_events, seed)
    t_warm = time.perf_counter()
    followed = chk.follow(config, traffic, stream, seed)
    limits = compare.load_limits(os.path.join(root, "bench"), name)

    with tempfile.TemporaryDirectory(prefix="bench-") as tmp:
        run = path.prepare(config, traffic, stream, followed, seed=seed,
                           seed32=seed32, rng=rng, seconds=seconds,
                           devices=devs[:chips], tmp=tmp)
        setup_s = time.perf_counter() - t_start
        parts = {"start_s": t_gen - t_start, "generate_s": t_warm - t_gen,
                 "warmup_s": setup_s - (t_warm - t_start)}

        if traced:
            trace_dir = os.path.join(tmp, "trace")
            trace_reduce.start(trace_dir)
        win = path.window(run, stream)
        if traced:
            jax.profiler.stop_trace()
        peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devs[:chips])
        del run

        numbers, info = chk.check(reference, config, traffic, win, stream,
                                  seed32, followed, limits)
        summary = None
        if traced:
            summary = trace_reduce.summarize(trace_reduce.load(
                trace_reduce.find_xplane(trace_dir)))

    rec = Record(cell=cell, config=config, traffic=traffic, window=win,
                 setup_s=setup_s, peak_bytes=peak,
                 device_kind=devs[0].device_kind, trace=summary)
    correct, table = compare.judge(numbers, limits)
    metrics = {}
    for m in metrics_of(bench, name, traced):
        value = reader(m["name"], root)(rec)
        if value is None:
            if not traced:
                raise RuntimeError(f"end-to-end metric {m['name']} has no "
                                   f"value")
            continue
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": chips, "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": int(win.events),
              "failed": int(win.events - win.completed),
              "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {
            "device_ops": [[n, t] for n, t in summary.device_ops],
            "idle_gaps": [[n, t] for n, t in summary.idle_gaps]}
    result["diagnostics"] = dict(
        info, setup=parts, wchar_bytes=int(win.bytes_written),
        store_bytes=int(win.store_bytes))
    result["check"] = table
    return result


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start)
    except NoChip as e:
        print(f"no chip: {e}", file=sys.stderr, flush=True)
        return 3
    from bench import compare

    compare.print_lines(result["check"])
    print(json.dumps(result), flush=True)
    return 0
