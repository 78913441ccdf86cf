"""End-to-end engine throughput: events/s for {exact, fast} x policy x skew.

Drives the vectorized JAX engine (repro.core.engine) over synthetic streams
with uniform and Zipf-skewed key distributions, through the donated-buffer
``run_stream`` driver.  Four suites:

* ``engine``  — local engine.  Exact mode runs under its default
  segment-compacted round schedule; a ``masked`` baseline row (the
  O(exact_rounds x B) reference schedule) is recorded alongside so the JSON
  shows the compaction win directly.
* ``sharded`` — ``ShardedFeatureEngine.run_stream`` on a ``data`` mesh over
  every accelerator device, or, on the CPU backend, an 8-way fake-device
  mesh (subprocess, so the forced device count never leaks into the caller's
  jax).  The 8 CPU "devices" share the same cores, so a CPU number records
  dispatch overhead, not scale-out speedup.
* ``skew``    — the ``layout="block"`` vs ``layout="virtual"`` pair
  (distributed/rebalance.py) over the Table 2 workload regimes
  (streaming/workload.py), recording each layout's padded-vs-useful block
  slot fraction and throughput on the same mesh.
* ``persist`` — the *durable* fast path: ``run_stream`` with a write-behind
  ``WriteBehindSink`` (streaming/persistence.py) vs the no-persistence
  baseline, at the paper's write budget (Lambda * h = 0.1).  Records
  puts/events (Table 3's >= 90% write exclusion, now at vectorized
  throughput), bytes written, SerDe seconds, modeled IO, WAF, and the
  throughput cost of persistence (write-behind overlap, not serial
  flushes).
* ``residency`` — bounded state residency (streaming/residency.py): the
  slot-based resident set swept from resident fraction 1.0 down to 0.1 on
  the Zipf workload, against the dense sink-path driver as baseline.
  Records hit rate, unique-miss rate, hydrate gets/event (must not exceed
  the unique-miss rate — no thrash), hydrate bytes, modeled read seconds
  and throughput per resident fraction.  ``--smoke`` shrinks the stream
  for CI.

Every row also carries a peak-memory watermark column
(``benchmarks.common.memory_watermark``: device allocator stats where the
backend reports them, host peak RSS on the CPU backend only) so donation/zero-copy
regressions are visible between JSON snapshots.

Results land both on stdout (``emit`` rows) and in ``BENCH_engine.json`` at
the repo root so successive PRs record a throughput trajectory.

    PYTHONPATH=src python benchmarks/bench_engine.py --suite engine
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

if __package__ in (None, ""):
    # executed as `python benchmarks/bench_engine.py`: put the repo root and
    # src/ on the path so benchmarks.common / repro import without env setup
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for _p in (_root, os.path.join(_root, "src")):
        if _p not in sys.path:
            sys.path.insert(0, _p)

import jax
import numpy as np

from benchmarks.common import emit, memory_watermark
from repro.core import EngineConfig
from repro.features.engine import ShardedFeatureEngine

_OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_engine.json")


def _make_stream(rng, n_events: int, n_keys: int, skew: float):
    """skew=0 -> uniform keys; skew>0 -> Zipf-weighted keys."""
    if skew > 0:
        w = 1.0 / np.arange(1, n_keys + 1, dtype=np.float64) ** skew
        w /= w.sum()
        keys = rng.choice(n_keys, size=n_events, p=w)
    else:
        keys = rng.integers(0, n_keys, size=n_events)
    t = np.cumsum(rng.exponential(0.05, size=n_events))
    q = rng.lognormal(3.0, 1.0, size=n_events)
    return (keys.astype(np.int32), q.astype(np.float32),
            t.astype(np.float32))


def _drive(cfg: EngineConfig, mode: str, keys, qs, ts, batch: int,
           n_keys: int, repeats: int = 3, exact_impl: str = "compact"
           ) -> float:
    """Best-of-repeats events/s over the full stream (compile excluded)."""
    from repro.core import init_state
    from repro.core.stream import run_stream

    n = (len(keys) // batch) * batch

    def once():
        state = init_state(n_keys, len(cfg.taus))
        state, _ = run_stream(
            cfg, state, keys[:n], qs[:n], ts[:n], batch=batch,
            mode=mode, rng=jax.random.PRNGKey(0), collect_info=False,
            exact_impl=exact_impl)
        jax.block_until_ready(state.agg)
        return state

    once()  # compile + warm caches
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        once()
        best = min(best, time.perf_counter() - t0)
    return n / best


def _run_engine_suite(rng, n_events, n_keys, batch, exact_rounds):
    rows = []
    for skew_name, skew in (("uniform", 0.0), ("zipf", 1.2)):
        keys, qs, ts = _make_stream(rng, n_events, n_keys, skew)
        for policy in ("pp", "pp_vr", "unfiltered"):
            cfg = EngineConfig(taus=(60.0, 3600.0, 86400.0), h=600.0,
                               budget=0.05, alpha=1.0, policy=policy,
                               exact_rounds=exact_rounds)
            variants = [("exact", "compact"), ("fast", None)]
            if policy == "pp":   # masked baseline once per skew: the row
                variants.insert(1, ("exact", "masked"))  # pair shows the win
            for mode, impl in variants:
                eps = _drive(cfg, mode, keys, qs, ts, batch, n_keys,
                             exact_impl=impl or "compact")
                row = {"mode": mode, "policy": policy, "skew": skew_name,
                       "batch": batch, "n_events": n_events,
                       "events_per_s": round(eps, 1)}
                if impl is not None:
                    row["impl"] = impl
                row.update(memory_watermark())
                rows.append(row)
                emit("engine", row)
    return rows


def _data_mesh():
    """A ``data`` mesh over every device this process sees: the real chips
    on an accelerator, 8 forced host devices in the CPU child."""
    n_dev = len(jax.devices())
    return jax.make_mesh((n_dev,), ("data",)), n_dev


def _mesh_label(n_dev: int) -> str:
    return f"{n_dev}x{jax.devices()[0].platform}"


def _sharded_rows(n_events, n_keys, batch, exact_rounds, seed):
    """Sharded ``run_stream`` throughput rows over a ``data`` mesh."""
    mesh, n_dev = _data_mesh()
    rng = np.random.default_rng(seed)
    rows = []
    for skew_name, skew in (("uniform", 0.0), ("zipf", 1.2)):
        keys, qs, ts = _make_stream(rng, n_events, n_keys, skew)
        cfg = EngineConfig(taus=(60.0, 3600.0, 86400.0), h=600.0,
                           budget=0.05, policy="pp",
                           exact_rounds=exact_rounds)
        for mode in ("exact", "fast"):
            eng = ShardedFeatureEngine(cfg, n_keys, mesh=mesh, mode=mode)

            def once():
                st, _ = eng.run_stream(eng.init_state(), keys, qs, ts,
                                       batch_per_shard=batch // n_dev,
                                       rng=jax.random.PRNGKey(0),
                                       collect_info=False)
                jax.block_until_ready(st.agg)

            once()
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                once()
                best = min(best, time.perf_counter() - t0)
            row = {"mode": mode, "policy": "pp", "skew": skew_name,
                   "batch": batch, "n_events": n_events,
                   "mesh": _mesh_label(n_dev),
                   "events_per_s": round(n_events / best, 1)}
            row.update(memory_watermark())
            rows.append(row)
    return rows


def _skew_rows(regimes, n_events, batch, seed):
    """block-vs-virtual layout rows over the Table 2 regimes."""
    from repro.streaming.workload import generate_regime
    mesh, n_dev = _data_mesh()
    rows = []
    for regime in regimes:
        stream = generate_regime(regime, seed=seed, n_events=n_events)
        weights = np.bincount(stream.key, minlength=stream.spec.n_keys)
        for layout in ("block", "virtual"):
            eng = ShardedFeatureEngine(
                EngineConfig(taus=(60.0, 3600.0, 86400.0), h=600.0,
                             budget=0.05, policy="pp"),
                stream.spec.n_keys, mesh=mesh, mode="fast", layout=layout,
                key_weights=weights if layout == "virtual" else None)
            stats = eng.stream_layout_stats(stream.key, batch // n_dev)

            def once():
                st, _ = eng.run_stream(eng.init_state(), stream.key,
                                       stream.q, stream.t,
                                       batch_per_shard=batch // n_dev,
                                       rng=jax.random.PRNGKey(0),
                                       collect_info=False)
                jax.block_until_ready(st.agg)

            once()
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                once()
                best = min(best, time.perf_counter() - t0)
            row = {"suite": "skew", "regime": regime, "layout": layout,
                   "mode": "fast", "batch": batch, "n_events": n_events,
                   "mesh": _mesh_label(n_dev), "n_blocks": stats["n_blocks"],
                   "padded_fraction": round(stats["padded_fraction"], 4),
                   "useful_fraction":
                       round(1.0 - stats["padded_fraction"], 4),
                   "events_per_s": round(n_events / best, 1)}
            row.update(memory_watermark())
            rows.append(row)
    return rows


def _run_persist_suite(n_events, n_keys, batch, seed):
    """Durable fast path: write-behind sink vs no-persistence baseline.

    Budget regime mirrors Table 3's pp row: Lambda * h = 0.1, so even a
    cold key's first event is included with p <= 0.1 and the expected
    write fraction sits at <= ~10% — the >= 90% exclusion the paper
    reports, here sustained at vectorized fast-path throughput with the
    bytes actually landing in partition stores.
    """
    import shutil
    import tempfile

    from repro.core import init_state
    from repro.core.stream import run_stream
    from repro.streaming.durable import open_partition_stores
    from repro.streaming.persistence import WriteBehindSink

    h = 3600.0
    budget = 0.1 / h
    # own generator: the stream must not depend on which other suites ran
    # first in this invocation (rows are compared across partial runs)
    keys, qs, ts = _make_stream(np.random.default_rng(seed + 17),
                                n_events, n_keys, skew=1.2)
    rows = []
    for policy in ("pp", "pp_vr", "unfiltered"):
        cfg = EngineConfig(taus=(60.0, 3600.0, 86400.0), h=h, budget=budget,
                           alpha=1.0, policy=policy)

        def once(sink=None):
            state = init_state(n_keys, len(cfg.taus))
            t0 = time.perf_counter()
            state, _ = run_stream(cfg, state, keys, qs, ts, batch=batch,
                                  mode="fast", rng=jax.random.PRNGKey(0),
                                  collect_info=False, sink=sink)
            if sink is not None:
                sink.flush()        # trailing blocks count toward the wall
            jax.block_until_ready(state.agg)
            return time.perf_counter() - t0

        once()                      # compile + warm caches
        # interleave the three variants so they ride the same container
        # noise; best-of-7 each.  serial = queue_depth 0 (flush inline on
        # the driver thread), the strawman write-behind exists to beat.
        base = best = serial = float("inf")
        stats = None
        for _ in range(7):
            base = min(base, once())
            with WriteBehindSink(cfg, n_partitions=4) as sink:
                dt = once(sink)
                if dt < best:
                    best, stats = dt, sink.snapshot()
            with WriteBehindSink(cfg, n_partitions=4,
                                 queue_depth=0) as ssink:
                serial = min(serial, once(ssink))
        # modeled end-to-end rates: the storage service time is modeled
        # (never slept), so fold it in arithmetically — serial pays
        # compute + IO (one thread does everything); write-behind is a
        # pipeline of compute, the dispatcher's pack stage (flush_s) and
        # the per-partition store workers (each store's put busy +
        # modeled IO run concurrently across partitions, so the stage is
        # bounded by the slowest store — store_path_s_max), and its rate
        # is set by the slowest stage.  serde/pack time is NOT added on
        # top: both walls already include it.
        # measured pass: same stream through the real WAL+compaction
        # backend (streaming/durable.py), bytes actually fsynced to disk,
        # then a timed reopen-from-disk (the recovery path).  Modeled
        # columns above stay in the row for side-by-side comparison.
        tdir = tempfile.mkdtemp(prefix=f"bench-persist-{policy}-")
        try:
            with WriteBehindSink(cfg, n_partitions=4, backend="durable",
                                 store_dir=tdir) as dsink:
                t_dur = once(dsink)
                dsnap = dsink.snapshot()
            t0 = time.perf_counter()
            recovered = open_partition_stores(tdir, 4)
            recovery_s = time.perf_counter() - t0
            recovered_batches = sum(s.durable.recovered_batches
                                    for s in recovered)
            for s in recovered:
                s.close()
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        meas = dsnap["measured"]
        io = stats["modeled_io_s"]
        modeled_serial = n_events / (serial + io)
        modeled_wb = n_events / max(best, stats["flush_s"],
                                    stats["store_path_s_max"])
        row = {"suite": "persist", "mode": "fast", "policy": policy,
               "batch": batch, "n_events": n_events,
               "budget_x_h": round(budget * h, 3),
               "events_per_s": round(n_events / best, 1),
               "events_per_s_nosink": round(n_events / base, 1),
               "events_per_s_serialflush": round(n_events / serial, 1),
               "sink_overhead_pct": round(100.0 * (best - base) / base, 2),
               "modeled_serial_events_per_s": round(modeled_serial, 1),
               "modeled_writebehind_events_per_s": round(modeled_wb, 1),
               "puts": stats["puts"],
               "puts_per_event": round(stats["puts"] / n_events, 4),
               "selected_per_event": round(stats["selected"] / n_events, 4),
               "dedup_saved": stats["dedup_saved"],
               "bytes_written": stats["bytes_written"],
               "waf": round(stats["waf"], 3),
               "serde_s": round(stats["serde_s"], 4),
               "modeled_io_s": round(stats["modeled_io_s"], 4),
               "flush_s": round(stats["flush_s"], 4),
               "submit_wait_s": round(stats["submit_wait_s"], 4),
               "host_pack_s": round(stats["host_pack_s"], 4),
               "device_wait_s": round(stats["device_wait_s"], 4),
               "overlap_frac": round(stats["overlap_frac"], 4),
               # measured columns (real durable backend, same stream)
               "events_per_s_durable": round(n_events / t_dur, 1),
               "measured_bytes_written": meas["measured_bytes_written"],
               "measured_waf": round(meas["measured_waf"], 3),
               "measured_fsyncs": meas["fsyncs"],
               "measured_wal_bytes": meas["wal_bytes"],
               "measured_seg_bytes": meas["seg_bytes"],
               "compactions": meas["compactions"],
               "measured_io_write_s": round(meas["io_write_s"], 4),
               "measured_io_sync_s": round(meas["io_sync_s"], 4),
               "recovery_s": round(recovery_s, 4),
               "recovered_batches": recovered_batches}
        row.update(memory_watermark())
        rows.append(row)
        emit("engine_persist", row)
    rows.append(_run_persist_fault_row(n_events, n_keys, batch,
                                       keys, qs, ts, h, budget))
    rows += _run_persist_compaction_rows(n_events, n_keys, batch,
                                         keys, qs, ts, h, budget)
    return rows


class _TimedSink:
    """Sink proxy recording per-``submit`` wall latency (the serial
    sink flushes inline, so each sample is one flush group's end-to-end
    path — including any inline compaction riding it)."""

    def __init__(self, sink):
        self._sink = sink
        self.lat: list = []

    def submit(self, *a, **kw):
        t0 = time.perf_counter()
        self._sink.submit(*a, **kw)
        self.lat.append(time.perf_counter() - t0)

    def __getattr__(self, name):
        return getattr(self._sink, name)


def _run_persist_compaction_rows(n_events, n_keys, batch, keys, qs, ts,
                                 h, budget):
    """Inline-vs-background compaction A/B under slept-IO, one row each.

    Serial sink (queue_depth=0) on a single slept-IO durable store, so
    every ``submit`` *is* the flush path: under ``compaction="inline"``
    the periodic segment rewrite rides it (visible as flush-latency
    spikes and ``compaction_stall_s``), under ``"background"`` the
    compactor thread absorbs it and the stall column must be exactly
    zero — asserted here, so a regression fails the bench (CI runs this
    suite with ``--smoke``).  The two variants are interleaved rep by
    rep to ride the same container noise.

    The stream uses even entity ids only; after each run the store is
    reopened lazily and probed with odd (absent) ids — a pure point-miss
    workload.  The background variant compacts with a 10-bit/key bloom
    trailer, the inline variant with the byte-compatible default (none),
    so the two rows' ``miss_blocks_read`` columns show what the filter
    saves on the exact same probe set."""
    import shutil
    import tempfile

    from repro.core import init_state
    from repro.core.stream import run_stream
    from repro.streaming.durable import DurableStore
    from repro.streaming.kvstore import StorageModel
    from repro.streaming.persistence import WriteBehindSink

    cfg = EngineConfig(taus=(60.0, 3600.0, 86400.0), h=h, budget=budget,
                       alpha=1.0, policy="unfiltered")
    even = keys.astype(np.int64) * 2
    variants = {
        "inline": dict(compaction="inline", bloom_bits_per_key=0),
        "background": dict(compaction="background", bloom_bits_per_key=10,
                           compact_rate_bytes_per_s=64e6),
    }

    def once(mode, tdir):
        # seg_block_rows=64: enough blocks that the point-miss probe
        # phase has something for the bloom filter to save
        store = DurableStore(tdir, model=StorageModel(sleep_io=True),
                             compact_threshold_bytes=1 << 16,
                             seg_block_rows=64, **variants[mode])
        sink = WriteBehindSink(cfg, stores=[store], queue_depth=0)
        tsink = _TimedSink(sink)
        state = init_state(2 * n_keys, len(cfg.taus))
        t0 = time.perf_counter()
        state, _ = run_stream(cfg, state, even, qs, ts, batch=batch,
                              mode="fast", rng=jax.random.PRNGKey(0),
                              collect_info=False, sink=tsink)
        sink.flush()
        jax.block_until_ready(state.agg)
        wall = time.perf_counter() - t0
        if mode == "background":
            store.wait_for_compaction()
        d = store.durable
        out = {"wall": wall, "lat": tsink.lat,
               "stall": d.compaction_stall_s,
               "throttle": d.compact_throttle_s,
               "compactions": d.compactions,
               "tail_rewrites": d.wal_tail_rewrites,
               "submit_wait_s": sink.stats.submit_wait_s}
        store.compact()        # publish a segment for the probe phase
        sink.close()
        store.close()          # explicit stores= are not sink-owned
        return out

    def probe_misses(tdir, n_probe=2048):
        rng = np.random.default_rng(99)
        odd = rng.integers(0, n_keys, n_probe).astype(np.int64) * 2 + 1
        with DurableStore(tdir, lazy_recovery=True) as r:
            got = r.multi_get(odd)
            assert all(g is None for g in got)   # soundness at bench scale
            d = r.durable
            return {"miss_probes": int(d.seg_probes),
                    "miss_blocks_read": int(d.seg_blocks_read),
                    "bloom_probes": int(d.bloom_probes),
                    "bloom_skips": int(d.bloom_skips),
                    "bloom_false_positives": int(d.bloom_false_positives)}

    warm = tempfile.mkdtemp(prefix="bench-compact-warm-")
    try:
        once("inline", warm)                      # compile + warm caches
    finally:
        shutil.rmtree(warm, ignore_errors=True)
    acc = {m: {"lat": [], "best": None} for m in variants}
    dirs = {}
    try:
        for rep in range(3):
            for mode in ("inline", "background"):     # interleaved A/B
                tdir = tempfile.mkdtemp(prefix=f"bench-compact-{mode}-")
                res = once(mode, tdir)
                a = acc[mode]
                a["lat"] += res["lat"]
                if a["best"] is None or res["wall"] < a["best"]["wall"]:
                    a["best"] = res
                    if mode in dirs:
                        shutil.rmtree(dirs[mode], ignore_errors=True)
                    dirs[mode] = tdir
                else:
                    shutil.rmtree(tdir, ignore_errors=True)
        rows = []
        for mode in ("inline", "background"):
            best, lat = acc[mode]["best"], np.asarray(acc[mode]["lat"])
            if mode == "background":
                assert best["stall"] == 0.0, (
                    "background compaction rode the flush path: "
                    f"compaction_stall_s={best['stall']}")
            row = {"suite": "persist", "mode": "fast",
                   "policy": "unfiltered",
                   "variant": f"compaction-{mode}", "batch": batch,
                   "n_events": n_events,
                   "compaction": mode,
                   "bloom_bits_per_key":
                       variants[mode]["bloom_bits_per_key"],
                   "events_per_s": round(n_events / best["wall"], 1),
                   "flush_p50_ms": round(
                       float(np.percentile(lat, 50)) * 1e3, 4),
                   "flush_p99_ms": round(
                       float(np.percentile(lat, 99)) * 1e3, 4),
                   "compaction_stall_s": round(best["stall"], 4),
                   "compact_throttle_s": round(best["throttle"], 4),
                   "compactions": best["compactions"],
                   "wal_tail_rewrites": best["tail_rewrites"],
                   "submit_wait_s": round(best["submit_wait_s"], 4)}
            pr = probe_misses(dirs[mode])
            row.update(pr)
            row["bloom_skip_rate"] = round(
                pr["bloom_skips"] / max(pr["bloom_probes"], 1), 4)
            row.update(memory_watermark())
            rows.append(row)
            emit("engine_persist", row)
        return rows
    finally:
        for tdir in dirs.values():
            shutil.rmtree(tdir, ignore_errors=True)


def _run_persist_fault_row(n_events, n_keys, batch, keys, qs, ts, h,
                           budget):
    """Fault-injection row: transient OSErrors on WAL appends, the sink's
    bounded-backoff retry must complete the run, and the faulted store's
    durable contents must equal a clean durable run's (``data_loss``
    False) — the acceptance criterion, reported as a bench row so the
    trajectory records it at full stream scale, not just test scale."""
    from repro.core import init_state
    from repro.core.stream import run_stream
    from repro.streaming import faults
    from repro.streaming.durable import DurableStore
    from repro.streaming.persistence import RetryPolicy, WriteBehindSink
    import shutil
    import tempfile

    cfg = EngineConfig(taus=(60.0, 3600.0, 86400.0), h=h, budget=budget,
                       alpha=1.0, policy="pp")

    def once(sink):
        state = init_state(n_keys, len(cfg.taus))
        t0 = time.perf_counter()
        state, _ = run_stream(cfg, state, keys, qs, ts, batch=batch,
                              mode="fast", rng=jax.random.PRNGKey(0),
                              collect_info=False, sink=sink)
        sink.flush()
        jax.block_until_ready(state.agg)
        return time.perf_counter() - t0

    tdir = tempfile.mkdtemp(prefix="bench-persist-faults-")
    try:
        clean_store = DurableStore(os.path.join(tdir, "clean"))
        with WriteBehindSink(cfg, stores=[clean_store]) as csink:
            once(csink)
        # transient_at={1, 3}: deterministic faults that fire at smoke
        # scale too (one flush group => one WAL append)
        fops = faults.FaultyFileOps(
            faults.FaultPlan(transient_at=frozenset({1, 3})))
        faulty_store = DurableStore(os.path.join(tdir, "faulty"),
                                    fileops=fops)
        with WriteBehindSink(cfg, stores=[faulty_store],
                             retry=RetryPolicy(base_s=1e-3)) as fsink:
            t_f = once(fsink)
            fsnap = fsink.snapshot()
        data_loss = faulty_store.data != clean_store.data
        clean_store.close()
        faulty_store.close()
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    row = {"suite": "persist", "mode": "fast", "policy": "pp",
           "variant": "fault-injection", "batch": batch,
           "n_events": n_events, "budget_x_h": round(budget * h, 3),
           "events_per_s": round(n_events / t_f, 1),
           "injected_transients": fops.injected_transients,
           "retries": fsnap["retries"],
           "transient_errors": fsnap["transient_errors"],
           "flush_errors": fsnap["flush_errors"],
           "retry_wait_s": round(fsnap["retry_wait_s"], 4),
           "completed": True, "data_loss": bool(data_loss)}
    row.update(memory_watermark())
    emit("engine_persist", row)
    return row


def _run_residency_suite(n_events, n_keys, batch, seed):
    """Bounded residency: throughput + hydration cost vs resident fraction.

    Sweeps the slot budget from the full key space (resident fraction 1.0
    — hydration happens once per key, then pure hits) down to 0.1 of it on
    the Zipf stream, pp policy at the paper's budget regime.  The dense
    sink-path driver (same batch, same flush grouping, no slot plane)
    rides along as the ``impl="dense_sinkpath"`` baseline row: at fraction
    1.0 the slot engine must sit within noise of it.  The capacity floor
    (a flush group's distinct keys must fit the slots) is computed from
    the stream; budgets below it are clamped and flagged.

    Two extra regimes ride along (see benchmarks/README.md for columns):

    * ``variant="adversarial_churn"`` — a hot set referenced every group
      plus a cyclic cold scan sized far past the slot budget.  The scan
      sets every inserted slot's reference bit, so the clock policies
      thrash the hot set; ``eviction="priority"`` keeps it resident, and
      the host L2 tier (``l2=``) absorbs the scan's rehydration reads.
      Four rows: {second_chance, priority} x {l2 off, on}, with durable
      ``gets_per_event`` the headline column.
    * ``variant="oversized_group"`` — the slot budget is forced *below*
      the capacity floor, so flush groups must split
      (``split_oversized_group``); the row records ``splits`` and that
      the run completes where it used to raise ``ValueError``.
    """
    from repro.core import init_state
    from repro.core.stream import run_stream
    from repro.streaming.persistence import WriteBehindSink
    from repro.streaming.residency import ResidencyMap

    h = 3600.0
    budget = 0.1 / h
    group = 1                           # sink_group: smallest feasible S
    keys, qs, ts = _make_stream(np.random.default_rng(seed + 29),
                                n_events, n_keys, skew=1.2)
    cfg = EngineConfig(taus=(60.0, 3600.0, 86400.0), h=h, budget=budget,
                       alpha=1.0, policy="pp")
    n = (len(keys) // batch) * batch
    keys, qs, ts = keys[:n], qs[:n], ts[:n]
    # capacity floor: max distinct keys over any flush group of the sweep
    floor = max(np.unique(keys[lo:lo + group * batch]).size
                for lo in range(0, n, group * batch))

    def once(S=None):
        sink = WriteBehindSink(cfg, n_partitions=4)
        state = init_state(S if S is not None else n_keys, len(cfg.taus))
        rmap = ResidencyMap(n_keys, S) if S is not None else None
        t0 = time.perf_counter()
        state, _ = run_stream(cfg, state, keys, qs, ts, batch=batch,
                              mode="fast", rng=jax.random.PRNGKey(0),
                              collect_info=False, sink=sink,
                              sink_group=group, residency=rmap)
        sink.flush()
        jax.block_until_ready(state.agg)
        dt = time.perf_counter() - t0
        snap = sink.snapshot()
        sink.close()
        return dt, snap, rmap

    rows = []
    fracs = (1.0, 0.5, 0.25, 0.1)
    budgets = {f: max(int(f * n_keys), floor) for f in fracs}
    # compile + warm every variant that will be timed: jit programs
    # specialize on the slot count S, so each budget needs its own warm
    # pass (plus the dense sink-path baseline)
    once()
    for S in dict.fromkeys(budgets.values()):
        once(S)
    # interleave the baseline and every fraction so all variants ride the
    # same container noise (best-of-5 each, like the persist suite)
    base = float("inf")
    best = {f: (float("inf"), None, None) for f in fracs}
    for _ in range(5):
        base = min(base, once()[0])
        for f in fracs:
            dt, snap, rm = once(budgets[f])
            if dt < best[f][0]:
                best[f] = (dt, snap, rm)
    row = {"suite": "residency", "impl": "dense_sinkpath", "mode": "fast",
           "policy": "pp", "batch": batch, "n_events": n,
           "sink_group": group, "events_per_s": round(n / base, 1)}
    row.update(memory_watermark())
    rows.append(row)
    emit("engine_residency", row)
    for frac in fracs:
        S = budgets[frac]
        wall, stats, rmap = best[frac]
        rs = rmap.stats
        row = {"suite": "residency", "mode": "fast", "policy": "pp",
               "batch": batch, "n_events": n, "sink_group": group,
               "resident_fraction": round(S / n_keys, 4),
               "n_slots": S,
               "clamped": bool(S > int(frac * n_keys)),
               "events_per_s": round(n / wall, 1),
               "hit_rate": round(rs.hit_rate(), 4),
               "unique_miss_per_event": round(rs.misses / n, 4),
               "hydrate_gets_per_event": round(stats["gets"] / n, 4),
               "hydrate_bytes": stats["bytes_read"],
               "modeled_read_s": round(stats["modeled_read_s"], 4),
               "evictions": rs.evictions,
               "read_wait_s": round(stats["read_wait_s"], 4),
               "submit_wait_s": round(stats["submit_wait_s"], 4)}
        row.update(memory_watermark())
        rows.append(row)
        emit("engine_residency", row)

    # ---- adversarial churn: hot set + cyclic cold scan ------------------
    # Half the lanes hit a small hot set (re-referenced every group), the
    # rest walk a cyclic scan over a cold space far larger than the slot
    # budget.  Every scan insert sets its slot's reference bit, so the
    # clock hand keeps meeting "recently used" scan slots and evicts the
    # hot set along with them; priority eviction ranks hot slots by touch
    # frequency/recency and keeps them resident.  The host L2 tier absorbs
    # the scan's repeat hydrations (rows *and* cached absences), so with
    # l2=True durable gets collapse toward the first scan cycle only.
    rng_c = np.random.default_rng(seed + 71)
    # the hot set is sized so each hot key skips ~1/3 of groups (present
    # keys are pinned and unevictable under *any* policy; the interesting
    # case is the groups a key sits out)
    n_hot, n_scan = 256, 4096
    n_ckeys = n_hot + n_scan
    hot = rng_c.random(n) < 0.25
    ck = np.where(hot, rng_c.integers(0, n_hot, size=n),
                  n_hot + (np.arange(n) % n_scan)).astype(np.int32)
    cq = rng_c.lognormal(3.0, 1.0, size=n).astype(np.float32)
    ct = np.cumsum(rng_c.exponential(0.05, size=n)).astype(np.float32)
    cfloor = max(np.unique(ck[lo:lo + group * batch]).size
                 for lo in range(0, n, group * batch))
    S_churn = cfloor + n_hot // 2        # fits every group, << scan space

    def churn_once(eviction, l2):
        sink = WriteBehindSink(cfg, n_partitions=4, l2=l2)
        state = init_state(S_churn, len(cfg.taus))
        rmap = ResidencyMap(n_ckeys, S_churn, eviction=eviction)
        t0 = time.perf_counter()
        state, _ = run_stream(cfg, state, ck, cq, ct, batch=batch,
                              mode="fast", rng=jax.random.PRNGKey(0),
                              collect_info=False, sink=sink,
                              sink_group=group, residency=rmap)
        sink.flush()
        jax.block_until_ready(state.agg)
        dt = time.perf_counter() - t0
        snap = sink.snapshot()
        sink.close()
        return dt, snap, rmap

    variants = [("second_chance", None), ("second_chance", True),
                ("priority", None), ("priority", True)]
    churn_once("second_chance", None)               # compile/warm S_churn
    cbest = {v: (float("inf"), None, None) for v in variants}
    for _ in range(3):
        for v in variants:
            dt, snap, rm = churn_once(*v)
            if dt < cbest[v][0]:
                cbest[v] = (dt, snap, rm)
    for eviction, l2 in variants:
        wall, stats, rmap = cbest[(eviction, l2)]
        rs = rmap.stats
        row = {"suite": "residency", "variant": "adversarial_churn",
               "mode": "fast", "policy": "pp", "batch": batch,
               "n_events": n, "sink_group": group, "n_keys": n_ckeys,
               "n_slots": S_churn, "eviction": eviction,
               "l2": l2 is not None,
               "events_per_s": round(n / wall, 1),
               "hit_rate": round(rs.hit_rate(), 4),
               "evictions": rs.evictions,
               "gets_per_event": round(stats["gets"] / n, 4),
               "l2_hits": stats["l2_hits"],
               "l2_demotions": stats["l2_demotions"],
               "hydrate_bytes": stats["bytes_read"],
               "read_wait_s": round(stats["read_wait_s"], 4)}
        row.update(memory_watermark())
        rows.append(row)
        emit("engine_residency", row)

    # ---- oversized groups: slot budget below the capacity floor ---------
    # Used to raise ValueError at the first too-wide flush group; now the
    # drivers split such groups into key-complete sub-groups that fit.
    S_over = max(floor // 2, 1)
    sink = WriteBehindSink(cfg, n_partitions=4, l2=True)
    state = init_state(S_over, len(cfg.taus))
    rmap = ResidencyMap(n_keys, S_over, eviction="priority")
    t0 = time.perf_counter()
    state, _ = run_stream(cfg, state, keys, qs, ts, batch=batch,
                          mode="fast", rng=jax.random.PRNGKey(0),
                          collect_info=False, sink=sink, sink_group=group,
                          residency=rmap)
    sink.flush()
    jax.block_until_ready(state.agg)
    wall = time.perf_counter() - t0
    stats = sink.snapshot()
    sink.close()
    rs = rmap.stats
    row = {"suite": "residency", "variant": "oversized_group",
           "mode": "fast", "policy": "pp", "batch": batch, "n_events": n,
           "sink_group": group, "n_slots": S_over,
           "capacity_floor": floor, "eviction": "priority", "l2": True,
           "completed": True, "splits": rs.splits,
           "events_per_s": round(n / wall, 1),
           "hit_rate": round(rs.hit_rate(), 4),
           "gets_per_event": round(stats["gets"] / n, 4),
           "l2_hits": stats["l2_hits"]}
    row.update(memory_watermark())
    rows.append(row)
    emit("engine_residency", row)

    # ---- pipelined execution plane: depth-2 double buffering vs serial --
    # The A/B the pipelined driver exists for: the frac-1.0 regime over a
    # storage model whose modeled latencies actually elapse
    # (``sleep_io=True`` — reads cost real wall time, as a remote store's
    # would), so the serial driver stalls on every group's hydration
    # round-trip while the depth-2 driver packs/stages group g+1 and parks
    # its reads behind the epoch lane during group g's wait.  Interleaved
    # runs, ratio of medians; ``overlap_frac`` (measured wall-clock
    # intersection of host pack work and device/IO waits, not wall
    # arithmetic) is the mechanism column — the speedup should come from
    # overlap, not noise.
    from repro.streaming.kvstore import StorageModel

    n_pipe = min(n, 32_768)
    pk, pq, pt = keys[:n_pipe], qs[:n_pipe], ts[:n_pipe]

    def pipe_once(depth):
        storage = StorageModel(read_us=2000.0, write_us=150.0,
                               batch_row_us=1.0, sleep_io=True)
        sink = WriteBehindSink(cfg, n_partitions=4, storage=storage)
        state = init_state(n_keys, len(cfg.taus))
        rmap = ResidencyMap(n_keys, n_keys)
        t0 = time.perf_counter()
        state, _ = run_stream(cfg, state, pk, pq, pt, batch=batch,
                              mode="fast", rng=jax.random.PRNGKey(0),
                              collect_info=False, sink=sink,
                              sink_group=group, residency=rmap,
                              pipeline_depth=depth)
        sink.flush()
        jax.block_until_ready(state.agg)
        dt = time.perf_counter() - t0
        snap = sink.snapshot()
        sink.close()
        return dt, snap

    pipe_once(1)                        # warm both programs' jit caches
    pipe_once(2)
    walls = {1: [], 2: []}
    snaps = {1: None, 2: None}
    for _ in range(3):
        for depth in (1, 2):            # interleaved: same container noise
            dt, snap = pipe_once(depth)
            walls[depth].append(dt)
            if snaps[depth] is None or dt < snaps[depth][0]:
                snaps[depth] = (dt, snap)
    med = {d: float(np.median(walls[d])) for d in (1, 2)}
    for depth in (1, 2):
        _, snap = snaps[depth]
        row = {"suite": "residency", "variant": "pipelined",
               "mode": "fast", "policy": "pp", "batch": batch,
               "n_events": n_pipe, "sink_group": group,
               "resident_fraction": 1.0, "n_slots": n_keys,
               "storage": "slept-io r2000us/w150us",
               "pipeline_depth": depth,
               "events_per_s": round(n_pipe / med[depth], 1),
               "events_per_s_best": round(n_pipe / min(walls[depth]), 1),
               "host_pack_s": round(snap["host_pack_s"], 4),
               "device_wait_s": round(snap["device_wait_s"], 4),
               "overlap_s": round(snap["overlap_s"], 4),
               "overlap_frac": round(snap["overlap_frac"], 4),
               "epochs_staged": snap["epochs_staged"],
               "staged_reads": snap["staged_reads"],
               "parked_reads": snap["parked_reads"],
               "read_wait_s": round(snap["read_wait_s"], 4),
               "submit_wait_s": round(snap["submit_wait_s"], 4)}
        if depth == 2:
            row["speedup_vs_serial"] = round(med[1] / med[2], 3)
        row.update(memory_watermark())
        rows.append(row)
        emit("engine_residency", row)
    return rows


def _run_mesh_suite(rows_fn, args, table: str):
    """Run a mesh suite and emit its rows.  On an accelerator it runs in
    this process over the real devices.  On the CPU it runs in a child
    with 8 forced host devices, so the forced device count never leaks into
    the caller's jax; a failed child raises."""
    if jax.default_backend() != "cpu":
        rows = rows_fn(*args)
    else:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = {"PYTHONPATH": "src:" + root,
               "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
               "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
               "JAX_PLATFORMS": "cpu"}
        code = (f"import json\n"
                f"from benchmarks.bench_engine import {rows_fn.__name__}\n"
                f"print('ROWS', json.dumps({rows_fn.__name__}(*{args!r})))")
        r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, env=env, cwd=root)
        if r.returncode != 0:
            raise RuntimeError(f"{table} suite failed:\n{r.stderr[-2000:]}")
        rows = json.loads(r.stdout.split("ROWS", 1)[1])
    for row in rows:
        emit(table, row)
    return rows


def _run_sharded_suite(n_events, n_keys, batch, exact_rounds, seed):
    """Sharded run_stream throughput over a ``data`` mesh."""
    return _run_mesh_suite(
        _sharded_rows, (n_events, n_keys, batch, exact_rounds, seed),
        "engine_sharded")


def _run_skew_suite(n_events, batch, seed,
                    regimes=("fraud", "ibm", "iiot", "wikipedia")):
    """block-vs-virtual layout padding + throughput over the Table 2 Zipf
    regimes."""
    return _run_mesh_suite(
        _skew_rows, (tuple(regimes), n_events, batch, seed), "engine_skew")


def _suite_of_row(row: dict) -> str:
    """Which suite produced a JSON row (for partial-run merging)."""
    if row.get("suite") in ("skew", "persist", "residency", "serving"):
        return row["suite"]
    return "sharded" if "mesh" in row else "engine"


def write_rows(rows, suites) -> None:
    """Merge ``rows`` into BENCH_engine.json, keeping every row whose
    suite was NOT run this invocation — a partial run never clobbers the
    other suites' trajectories.  Shared with ``bench_serving``."""
    kept = []
    if os.path.exists(_OUT_PATH):
        with open(_OUT_PATH) as f:
            old = json.load(f).get("rows", [])
        kept = [r for r in old if _suite_of_row(r) not in suites]
    with open(_OUT_PATH, "w") as f:
        json.dump({"bench": "engine", "rows": kept + rows}, f, indent=1)


def run(n_events: int = 65_536, n_keys: int = 4_096, batch: int = 4_096,
        exact_rounds: int = 16, seed: int = 0, suites=("engine",),
        write_json: bool = True):
    rng = np.random.default_rng(seed)
    rows = []
    if "engine" in suites:
        rows += _run_engine_suite(rng, n_events, n_keys, batch, exact_rounds)
    if "sharded" in suites:
        rows += _run_sharded_suite(n_events, n_keys, batch, exact_rounds,
                                   seed)
    if "skew" in suites:
        rows += _run_skew_suite(n_events, batch, seed)
    if "persist" in suites:
        rows += _run_persist_suite(n_events, n_keys, batch, seed)
    if "residency" in suites:
        rows += _run_residency_suite(n_events, n_keys, min(batch, 1024),
                                     seed)
    if "serving" in suites:
        from benchmarks import bench_serving
        rows += bench_serving.run(seed=seed, write_json=False)
    if not write_json:          # CI-sized rows must never overwrite the
        return rows             # tracked full-scale trajectory
    write_rows(rows, suites)
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--suite", default="all",
                    choices=("engine", "sharded", "skew", "persist",
                             "residency", "serving", "all"),
                    help="engine: local throughput (+ masked-vs-compact "
                         "exact rows); sharded: data-mesh run_stream; "
                         "skew: block-vs-virtual layout padding over the "
                         "Table 2 regimes; persist: write-behind durable "
                         "fast path vs no-persistence baseline; residency: "
                         "slot-based hot set, throughput + hydration cost "
                         "vs resident fraction; serving: open-loop tail "
                         "latency vs offered load (bench_serving.py)")
    ap.add_argument("--n-events", type=int, default=65_536)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized stream (shrinks n_events; rows go to "
                         "stdout only, BENCH_engine.json is untouched)")
    args = ap.parse_args()
    suites = ("engine", "sharded", "skew", "persist", "residency",
              "serving") \
        if args.suite == "all" else (args.suite,)
    n_events = min(args.n_events, 8_192) if args.smoke else args.n_events
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run(n_events=n_events, suites=suites, write_json=not args.smoke)
