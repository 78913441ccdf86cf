"""One run of one cell: everything between the command line and the result.

The harness is driven by ``BENCHMARK.json``.  A cell names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); every metric is read by a reader of its
own (``bench/metrics/<metric>.py``, a function ``read(rec)`` that returns
the value or ``None`` where it finds nothing to read); every cell's
correctness limits are in ``bench/limits/<cell>.json``.  Adding a cell,
configuration, traffic mix or metric adds files, and edits none.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
import tempfile
import time
from typing import Optional

import numpy as np

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
    traffic = load_json(BENCH, "traffic", f"{cell['traffic']}.json")
    return bench, cell, config, traffic


def metrics_of(bench: dict, cell: str, traced: bool) -> list:
    """The cell's end-to-end metrics, or with a trace its per-layer ones."""
    group = bench["per_layer" if traced else "end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def reader(name: str):
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


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
             t_start: float, require_chip: bool = True) -> dict:
    import jax

    bench, cell, config, traffic = cell_spec(name)
    devs = check_chips(int(cell["chips"])) if require_chip else \
        jax.devices()
    from repro.launch.compile_cache import enable_compile_cache

    from bench import compare, drivers, reference, streamgen, trace_reduce

    enable_compile_cache()
    seed32 = reference.engine_key_data(seed)
    rng = jax.random.PRNGKey(seed32)
    st = config["stream"]
    kind = traffic["driver"]
    if kind == "ingest":
        chunk = (int(config["engine"]["batch"])
                 * int(config["engine"]["sink_group"])
                 * int(traffic["chunk_groups"]))
        n_events = chunk * max(2, -(-int(round(
            float(traffic["window_events_per_s"]) * seconds)) // chunk))
    elif kind == "serve":
        n_events = int(round(float(traffic["rate_per_s"]) * seconds))
    else:
        raise ValueError(f"unknown driver {kind!r}")
    spec = streamgen.StreamSpec.from_config(st, n_events)
    t_gen = time.perf_counter()
    with drivers.annotate("bench.generate"):
        stream = streamgen.generate(spec, seed)
    t_warm = time.perf_counter()
    keys_followed = compare.sample_keys(
        seed, spec.n_keys, float(traffic["key_share"]), stream.key)
    sampled = np.zeros(spec.n_keys, bool)
    sampled[keys_followed] = True

    with tempfile.TemporaryDirectory(prefix="bench-") as tmp:
        store_dir = os.path.join(tmp, "store")
        if kind == "ingest":
            run = drivers.prepare_ingest(config, traffic, stream, rng,
                                         os.path.join(tmp, "warm"),
                                         store_dir)
        else:
            run = drivers.prepare_serve(config, traffic, stream, rng,
                                        seed32, os.path.join(tmp, "warm"))
            due = drivers.arrivals(n_events, seconds, seed)
        setup_s = time.perf_counter() - t_start
        parts = {"start_s": t_gen - t_start, "generate_s": t_warm - t_gen,
                 "warmup_s": setup_s - (t_warm - t_start)}

        if traced:
            trace_dir = os.path.join(tmp, "trace")
            trace_reduce.start(trace_dir)
        if kind == "ingest":
            win = drivers.ingest(run, stream, sampled, store_dir)
        else:
            win = drivers.serve(run, stream, due, sampled, store_dir)
        if traced:
            jax.profiler.stop_trace()
        peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devs[:int(cell["chips"])])
        scorer = getattr(getattr(run, "pipe", None), "scorer", None)
        scorer = None if scorer is None else reference.Scorer(
            **{f: np.asarray(getattr(scorer, f), np.float64)
               for f in scorer._fields})
        del run

        numbers, info = check(config, win, stream, seed32, keys_followed,
                              scorer, compare.load_limits(BENCH, name))
        summary = None
        if traced:
            summary = trace_reduce.summarize(trace_reduce.load(
                trace_reduce.find_xplane(trace_dir)))

    rec = Record(cell=cell, config=config, traffic=traffic, window=win,
                 setup_s=setup_s, peak_bytes=peak,
                 device_kind=devs[0].device_kind, trace=summary)
    limits = compare.load_limits(BENCH, name)
    correct, table = compare.judge(numbers, limits)
    metrics = {}
    for m in metrics_of(bench, name, traced):
        value = reader(m["name"])(rec)
        if value is None:
            if not traced:
                raise RuntimeError(f"end-to-end metric {m['name']} has no "
                                   f"value")
            continue
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": int(cell["chips"]), "memory_peak_bytes": peak}
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


def check(config: dict, win, stream, seed32: int, keys_followed, scorer,
          limits: dict) -> tuple:
    """The comparison's numbers, and what the diagnostics line shows."""
    import jax
    from repro.streaming.durable import open_partition_stores
    from repro.streaming.persistence import hydrate_state

    from bench import compare, reference

    eng = reference.EngineParams.from_config(config["engine"])
    pos = win.sample_pos
    keys, q, t = stream.key[pos], stream.q[pos], stream.t[pos]
    u = reference.uniforms(seed32, keys, t)
    ref = reference.FastReference(eng, keys_followed)
    dec = ref.run(keys, q, t, u, win.batch_id)
    rows = ref.rows(keys)
    fol = compare.follow(dec, u, rows, len(keys_followed), win.z,
                         limits["p_rel_err"])
    numbers = compare.decisions(dec, fol, win.p, win.z, win.lam, u,
                                limits["p_rel_err"])
    whole = compare.follow(dec, u, rows, len(keys_followed), win.z,
                           limits["p_rel_err"], writes=np.inf)
    whole = compare.decisions(dec, whole, win.p, win.z, win.lam, u,
                              limits["p_rel_err"])

    n_keys = int(config["stream"]["n_keys"])
    n_taus = len(eng.taus)
    stores = open_partition_stores(win.store_dir, 1)
    try:
        with jax.default_device(jax.devices("cpu")[0]):
            hyd = hydrate_state(stores, n_keys, n_taus)
            hyd = {f: np.asarray(getattr(hyd, f)) for f in hyd._fields}
    finally:
        for s in stores:
            s.close()
    at = {f: v[keys_followed] for f, v in hyd.items()}
    numbers["store_rel_err"] = compare.rows_gap(
        ref, fol, at, names=("last_t", "v_f", "agg"))
    if win.state is not None:
        final = {f: v[keys_followed] for f, v in win.state.items()}
        numbers["state_rel_err"] = compare.rows_gap(ref, fol, final)
        numbers["full_rel_err"] = compare.full_gap(ref, final)
        off = np.zeros(n_keys, bool)
        for f in ("last_t", "v_f", "agg"):
            a, b = hyd[f], win.state[f]
            diff = a.view(np.uint32) != b.view(np.uint32)
            off |= diff.reshape(n_keys, -1).any(axis=1)
        numbers["store_rows_off"] = int(off.sum())
    if win.scores is not None:
        numbers["score_err"] = compare.scores(dec, fol, scorer, win.scores)
        numbers["order_off"] = int(win.order_off)
    info = {"compared_events": int(fol.event_in.sum()),
            "sampled_events": int(len(pos)),
            "followed_keys": int(len(keys_followed)),
            "keys_left": fol.keys_left, "flips_forgiven": fol.forgiven,
            "whole_history": whole}
    return numbers, info


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
