"""Closed-loop durable ingest, fixed work, of a stream whose events each
update several profiles: ``bench/paths/ingest.py``'s window over ``[N, K]``
keys with the stream's own per-event counters.

The stream (``key`` ``[N, K]`` table rows, ``q``, ``t``, ``ctr`` event ids
and ``n_rows`` the table's size) goes through ``core.stream.run_stream``
in chunks of whole flush groups into a durable ``WriteBehindSink``, the
traffic's ``passes`` times, each pass from a fresh state into an empty
store of its own.  ``Window.events`` and ``completed`` count events, not
lanes; the followed outputs are the lanes whose row is followed, in lane
order (lane ``e*K + k`` is event e's k-th profile).
"""
from __future__ import annotations

import dataclasses
import gc
import inspect
import os
import time

import jax
import numpy as np

from bench.drivers import (Window, annotate, engine_config, store_counts,
                           written_bytes)
from bench.paths import ingest
from repro.core.stream import run_stream
from repro.core.types import EngineConfig, init_state
from repro.streaming.durable import open_partition_stores
from repro.streaming.persistence import WriteBehindSink


def events(config: dict, traffic: dict, seconds: float) -> int:
    """The stream's events, as ``ingest.events``; a program whose
    ``run_stream`` takes no per-event counters cannot run this path."""
    if "counters" not in inspect.signature(run_stream).parameters:
        raise NotImplementedError(
            "run_stream takes no per-event counters: this program cannot "
            "run events that update several profiles")
    return ingest.events(config, traffic, seconds)


@dataclasses.dataclass
class IngestFanout:
    cfg: EngineConfig
    n_rows: int
    batch: int
    group: int
    chunk: int                       # events per run_stream call
    rng: jax.Array
    stores: list                     # per pass, its durable store
    store_dirs: list
    sampled_row: np.ndarray          # [n_rows] bool: the rows followed


def prepare(config: dict, traffic: dict, stream, followed, *, seed, seed32,
            rng, seconds, devices, tmp: str) -> IngestFanout:
    eng = config["engine"]
    store_dirs = [os.path.join(tmp, f"store{k}")
                  for k in range(ingest.passes(traffic))]
    sampled = np.zeros(stream.n_rows, bool)
    sampled[followed] = True
    run = IngestFanout(
        cfg=engine_config(eng), n_rows=stream.n_rows,
        batch=int(eng["batch"]), group=int(eng["sink_group"]),
        chunk=int(eng["batch"]) * int(eng["sink_group"])
        * int(traffic["chunk_groups"]), rng=rng,
        stores=[open_partition_stores(d, 1) for d in store_dirs],
        store_dirs=store_dirs, sampled_row=sampled)
    if len(stream) < 2 * run.chunk:
        raise ValueError("the stream is shorter than two chunks")
    # warm-up: one whole chunk on a throwaway state and store compiles the
    # flush-group program and every shape run_stream uses for a chunk
    state = init_state(run.n_rows, len(run.cfg.taus))
    sink = WriteBehindSink(run.cfg, backend="durable",
                           store_dir=os.path.join(tmp, "warm"))
    state, info = _run_chunk(run, stream, state, sink, 0, run.chunk)
    _lane_outputs(info, np.arange(0, run.chunk, 97))
    sink.close()
    jax.block_until_ready(state)
    del state, info
    jax.block_until_ready(init_state(run.n_rows, len(run.cfg.taus)))
    return run


def _run_chunk(run: IngestFanout, stream, state, sink, lo: int, hi: int):
    return run_stream(run.cfg, state, stream.key[lo:hi], stream.q[lo:hi],
                      stream.t[lo:hi], counters=stream.ctr[lo:hi],
                      batch=run.batch, mode="fast", rng=run.rng, sink=sink,
                      sink_group=run.group)


def _lane_outputs(info, idx):
    """(p, z, lam) of the lanes ``idx`` of a chunk's ``[n, K]`` outputs."""
    return tuple(np.asarray(x).reshape(-1)[idx]
                 for x in (info.p, info.z, info.lam_hat))


def window(run: IngestFanout, stream) -> Window:
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
    K = stream.key.shape[1]
    pos = np.flatnonzero(run.sampled_row[stream.key[:done].reshape(-1)])
    p, z, lam = outs[-1]
    return Window(seconds=elapsed, events=done * len(sinks),
                  completed=done * len(sinks), bytes_written=wrote,
                  store_bytes=store_bytes, sample_pos=pos, p=p, z=z,
                  lam=lam, batch_id=pos // K // run.batch,
                  sink_stats=ingest.merge_stats(stats),
                  store_dir=run.store_dirs[-1], state=state_np,
                  passes=[dict(p=o[0], z=o[1], lam=o[2], store_dir=d)
                          for o, d in zip(outs[:-1], run.store_dirs)])


def _pass(run: IngestFanout, stream, sink, n_chunks: int) -> tuple:
    """One pass over the stream from a fresh state: the final state, the
    followed lanes' outputs (p, z, lam) and the flushed sink's stats."""
    state = init_state(run.n_rows, len(run.cfg.taus))
    outs = []
    for c in range(n_chunks):
        lo, hi = c * run.chunk, (c + 1) * run.chunk
        with annotate("bench.run_stream"):
            state, info = _run_chunk(run, stream, state, sink, lo, hi)
        with annotate("bench.collect"):
            idx = np.flatnonzero(run.sampled_row[
                stream.key[lo:hi].reshape(-1)])
            outs.append(_lane_outputs(info, idx))
            del info
    with annotate("bench.flush"):
        stats = sink.flush()
    return state, tuple(np.concatenate([o[i] for o in outs])
                        for i in range(3)), stats
