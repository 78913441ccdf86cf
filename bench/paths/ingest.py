"""Closed-loop durable ingest, fixed work.

The whole stream goes through ``core.stream.run_stream`` in consecutive
chunks of whole flush groups, the profile state carried from chunk to
chunk, into a durable ``WriteBehindSink``; then the sink is flushed, so
every event counted is durable.  That is one pass.  The window makes the
traffic's ``passes`` (1 where it names none) one after another, each from
a fresh state into a store of its own, over the same stream: every pass
is a deployment's first stretch of ingest, and more passes give a run
more work without changing what one pass does.  The window's events are
the traffic's ``window_events_per_s`` times the window's seconds, in whole
chunks, shared out among the passes: every run, and every version of the
program, does the same work and writes the same rows, and a faster
program closes the window sooner.  A store starts empty, or with
``filled_store`` holds a row for every key from set-up on, as a
deployment's store does once each key has been written: every compaction
in the window then rewrites a memtable of the deployment's size.

Set-up (the stores, warm-up of every shape the window uses) happens in
``prepare``, before the window opens.
"""
from __future__ import annotations

import dataclasses
import gc
import os
import time

import jax
import numpy as np

from bench.drivers import (Window, annotate, engine_config, store_counts,
                           written_bytes)
from repro.core.stream import run_stream
from repro.core.types import EngineConfig, init_state
from repro.streaming.durable import open_partition_stores
from repro.streaming.kvstore import SerDe
from repro.streaming.persistence import WriteBehindSink


def passes(traffic: dict) -> int:
    return int(traffic.get("passes", 1))


def events(config: dict, traffic: dict, seconds: float) -> int:
    """The stream's events, which each pass takes: ``window_events_per_s``
    times ``seconds`` over the passes, in whole chunks, at least two."""
    chunk = (int(config["engine"]["batch"])
             * int(config["engine"]["sink_group"])
             * int(traffic["chunk_groups"]))
    return chunk * max(2, -(-int(round(
        float(traffic["window_events_per_s"]) * seconds
        / passes(traffic))) // chunk))


@dataclasses.dataclass
class Ingest:
    cfg: EngineConfig
    n_keys: int
    batch: int
    group: int
    chunk: int                       # events per run_stream call
    rng: jax.Array
    stores: list                     # per pass, its durable store
    store_dirs: list
    sampled_key: np.ndarray          # [n_keys] bool: the keys followed


def prepare(config: dict, traffic: dict, stream, followed, *, seed, seed32,
            rng, seconds, devices, tmp: str) -> Ingest:
    eng = config["engine"]
    n_keys = int(config["stream"]["n_keys"])
    store_dirs = [os.path.join(tmp, f"store{k}")
                  for k in range(passes(traffic))]
    sampled = np.zeros(n_keys, bool)
    sampled[followed] = True
    run = Ingest(cfg=engine_config(eng), n_keys=n_keys,
                 batch=int(eng["batch"]), group=int(eng["sink_group"]),
                 chunk=int(eng["batch"]) * int(eng["sink_group"])
                 * int(traffic["chunk_groups"]), rng=rng,
                 stores=[filled_store(d, n_keys, len(eng["windows_s"]))
                         if traffic["filled_store"]
                         else open_partition_stores(d, 1)
                         for d in store_dirs],
                 store_dirs=store_dirs, sampled_key=sampled)
    if len(stream) < 2 * run.chunk:
        raise ValueError("the stream is shorter than two chunks")
    # warm-up: one whole chunk on a throwaway state and store compiles the
    # flush-group program and every shape run_stream uses for a chunk
    state = init_state(run.n_keys, len(run.cfg.taus))
    sink = WriteBehindSink(run.cfg, backend="durable",
                           store_dir=os.path.join(tmp, "warm"))
    state, info = run_stream(run.cfg, state, stream.key[:run.chunk],
                             stream.q[:run.chunk], stream.t[:run.chunk],
                             batch=run.batch, mode="fast", rng=rng,
                             sink=sink, sink_group=run.group)
    _sampled_outputs(info, np.arange(0, run.chunk, 97))
    sink.close()
    jax.block_until_ready(state)
    del state, info
    jax.block_until_ready(init_state(run.n_keys, len(run.cfg.taus)))
    return run


def filled_store(store_dir: str, n_keys: int, n_taus: int) -> list:
    """One durable partition holding every key's initial row: one batch
    through the WAL, which the store compacts into its first segment."""
    stores = open_partition_stores(store_dir, 1)
    init = init_state(n_keys, n_taus)
    rows = SerDe(n_taus).pack_rows(*(np.asarray(getattr(init, f))
                                     for f in init._fields))
    stores[0].multi_put(np.arange(n_keys), rows)
    return stores


def _sampled_outputs(info, idx):
    return (np.asarray(info.p)[idx], np.asarray(info.z)[idx],
            np.asarray(info.lam_hat)[idx])


def window(run: Ingest, stream) -> Window:
    sinks = [WriteBehindSink(run.cfg, stores=st) for st in run.stores]
    counts0 = [store_counts(sink.snapshot()) for sink in sinks]
    n_chunks = len(stream) // run.chunk
    done = n_chunks * run.chunk
    outs, stats = [], []
    gc.collect()
    w0 = written_bytes()
    t0 = time.perf_counter()
    with annotate("bench.window"):
        for sink in sinks:
            state = None             # one pass's state on the device at a time
            with annotate("bench.pass"):
                state, out, st = _pass(run, stream, sink, n_chunks)
                sink.close()         # its threads and buffers go too
            outs.append(out)
            stats.append(st)
    elapsed = time.perf_counter() - t0
    wrote = written_bytes() - w0
    for st in run.stores:
        for s in st:
            s.close()
    store_bytes = 0
    for st, (puts0, bytes0) in zip(stats, counts0):
        puts1, bytes1 = store_counts(st)
        st["puts"] = puts1 - puts0
        store_bytes += bytes1 - bytes0
    state_np = {f: np.asarray(getattr(state, f)) for f in state._fields}
    pos = np.flatnonzero(run.sampled_key[stream.key[:done]])
    p, z, lam = outs[-1]
    return Window(seconds=elapsed, events=done * len(sinks),
                  completed=done * len(sinks), bytes_written=wrote,
                  store_bytes=store_bytes, sample_pos=pos, p=p, z=z,
                  lam=lam, batch_id=pos // run.batch,
                  sink_stats=merge_stats(stats),
                  store_dir=run.store_dirs[-1], state=state_np,
                  passes=[dict(p=o[0], z=o[1], lam=o[2], store_dir=d)
                          for o, d in zip(outs[:-1], run.store_dirs)])


def _pass(run: Ingest, stream, sink, n_chunks: int) -> tuple:
    """One pass over the stream from a fresh state: the final state, the
    followed keys' outputs (p, z, lam) and the flushed sink's stats."""
    state = init_state(run.n_keys, len(run.cfg.taus))
    outs = []
    for c in range(n_chunks):
        lo, hi = c * run.chunk, (c + 1) * run.chunk
        with annotate("bench.run_stream"):
            state, info = run_stream(
                run.cfg, state, stream.key[lo:hi], stream.q[lo:hi],
                stream.t[lo:hi], batch=run.batch, mode="fast",
                rng=run.rng, sink=sink, sink_group=run.group)
        with annotate("bench.collect"):
            idx = np.flatnonzero(run.sampled_key[stream.key[lo:hi]])
            outs.append(_sampled_outputs(info, idx))
            del info
    with annotate("bench.flush"):
        stats = sink.flush()
    return state, tuple(np.concatenate([o[i] for o in outs])
                        for i in range(3)), stats


def merge_stats(parts: list) -> dict:
    """The passes' sink stats as one: counts and seconds summed, a ratio or
    a maximum (a name with ``waf``, ``frac`` or ``max``) the largest, lists
    joined, groups merged alike."""
    out = {}
    for part in parts:
        for k, v in part.items():
            if k not in out:
                out[k] = v
            elif isinstance(v, dict):
                out[k] = merge_stats([out[k], v])
            elif isinstance(v, list):
                out[k] = out[k] + v
            elif isinstance(v, bool) or not isinstance(v, (int, float)):
                out[k] = v
            elif any(w in k for w in ("waf", "frac", "max")):
                out[k] = max(out[k], v)
            else:
                out[k] = out[k] + v
    return out
