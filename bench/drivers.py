"""The timed paths: how each traffic mix drives the program.

``ingest`` (closed loop, fixed work): the whole stream goes through
``core.stream.run_stream`` in consecutive chunks of whole flush groups, the
profile state carried from chunk to chunk, into a durable
``WriteBehindSink``; then the sink is flushed, so every event counted is
durable.  The stream holds the traffic's ``window_events_per_s`` times the
window's seconds: every run, and every version of the program, does the
same work and writes the same rows, and a faster program closes the window
sooner.  The store starts empty, or with ``filled_store`` holds a row for
every key from set-up on, as a deployment's store does once each key has
been written: every compaction in the window then rewrites a memtable of
the deployment's size.

``serve`` (open loop): a fixed number of requests, due at Poisson arrival
times over the window, go through ``ScoringPipeline.serve`` with a durable
sink; a request's latency is its completion time minus its due time.

Both return a ``Window``: what the comparison needs (the sampled events'
outputs, the final state where the path exposes it, the store's directory)
and what the metrics need (times, counts, the sink's and frontend's stats).
Set-up (stream, weights, warm-up of every shape the window uses, the filled
store) happens in ``prepare_*``, before the window opens.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.stream import run_stream
from repro.core.types import EngineConfig, init_state
from repro.features.spec import ProfileSpec
from repro.serving.pipeline import ScoringPipeline, ScorerParams
from repro.streaming.durable import open_partition_stores
from repro.streaming.kvstore import SerDe
from repro.streaming.persistence import WriteBehindSink


def annotate(name: str):
    return jax.profiler.TraceAnnotation(name)


def engine_config(eng: dict) -> EngineConfig:
    return EngineConfig(taus=tuple(float(x) for x in eng["windows_s"]),
                        h=float(eng["kde_bandwidth_s"]),
                        budget=float(eng["write_budget_per_s"]),
                        alpha=float(eng["variance_alpha"]),
                        policy=eng["policy"],
                        mu_tau_index=int(eng["mu_tau_index"]),
                        min_p=float(eng["min_p"]))


def written_bytes() -> int:
    """Bytes this process has handed to write calls (Linux
    ``/proc/self/io`` ``wchar``): the WAL appends and segment files."""
    with open("/proc/self/io") as fh:
        for line in fh:
            if line.startswith("wchar:"):
                return int(line.split()[1])
    raise RuntimeError("/proc/self/io has no wchar line")


@dataclasses.dataclass
class Window:
    seconds: float                   # window length on the host clock
    events: int                      # events or requests offered
    completed: int
    bytes_written: int               # wchar: every write of the process
    store_bytes: int                 # the store's own WAL + segment count
    sample_pos: np.ndarray           # stream positions of sampled events
    p: np.ndarray                    # program outputs at sample_pos
    z: np.ndarray
    lam: np.ndarray
    batch_id: np.ndarray             # micro-batch of each sampled event
    sink_stats: dict
    store_dir: str
    state: Optional[dict] = None     # final device rows (ingest), numpy
    scores: Optional[np.ndarray] = None
    features: Optional[np.ndarray] = None
    latency_s: Optional[np.ndarray] = None
    frontend: Optional[dict] = None
    order_off: int = 0


# ------------------------------------------------------------------ ingest
@dataclasses.dataclass
class Ingest:
    cfg: EngineConfig
    n_keys: int
    batch: int
    group: int
    chunk: int                       # events per run_stream call
    rng: jax.Array
    stores: list                     # the window's durable store


def prepare_ingest(config: dict, traffic: dict, stream, rng, warm_dir: str,
                   store_dir: str) -> Ingest:
    eng = config["engine"]
    n_keys = int(config["stream"]["n_keys"])
    run = Ingest(cfg=engine_config(eng), n_keys=n_keys,
                 batch=int(eng["batch"]), group=int(eng["sink_group"]),
                 chunk=int(eng["batch"]) * int(eng["sink_group"])
                 * int(traffic["chunk_groups"]), rng=rng,
                 stores=filled_store(store_dir, n_keys, len(eng["windows_s"]))
                 if traffic["filled_store"]
                 else open_partition_stores(store_dir, 1))
    if len(stream) < 2 * run.chunk:
        raise ValueError("the stream is shorter than two chunks")
    # warm-up: one whole chunk on a throwaway state and store compiles the
    # flush-group program and every shape run_stream uses for a chunk
    state = init_state(run.n_keys, len(run.cfg.taus))
    sink = WriteBehindSink(run.cfg, backend="durable", store_dir=warm_dir)
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


def _store_counts(stats: dict) -> tuple:
    return stats["puts"], stats["measured"]["measured_bytes_written"]


def _sampled_outputs(info, idx):
    return (np.asarray(info.p)[idx], np.asarray(info.z)[idx],
            np.asarray(info.lam_hat)[idx])


def ingest(run: Ingest, stream, sampled_key: np.ndarray, store_dir: str
           ) -> Window:
    state = init_state(run.n_keys, len(run.cfg.taus))
    jax.block_until_ready(state)
    sink = WriteBehindSink(run.cfg, stores=run.stores)
    puts0, bytes0 = _store_counts(sink.snapshot())
    n_chunks = len(stream) // run.chunk
    pos, outs = [], []
    done = 0
    gc.collect()
    w0 = written_bytes()
    t0 = time.perf_counter()
    with annotate("bench.window"):
        for c in range(n_chunks):
            lo, hi = c * run.chunk, (c + 1) * run.chunk
            with annotate("bench.run_stream"):
                state, info = run_stream(
                    run.cfg, state, stream.key[lo:hi], stream.q[lo:hi],
                    stream.t[lo:hi], batch=run.batch, mode="fast",
                    rng=run.rng, sink=sink, sink_group=run.group)
            with annotate("bench.collect"):
                idx = np.flatnonzero(sampled_key[stream.key[lo:hi]])
                outs.append(_sampled_outputs(info, idx))
                pos.append(idx + lo)
                del info
            done = hi
        with annotate("bench.flush"):
            stats = sink.flush()
    elapsed = time.perf_counter() - t0
    wrote = written_bytes() - w0
    sink.close()
    for st in run.stores:
        st.close()
    puts1, bytes1 = _store_counts(stats)
    stats["puts"] = puts1 - puts0
    state_np = {f: np.asarray(getattr(state, f)) for f in state._fields}
    pos = np.concatenate(pos)
    p, z, lam = (np.concatenate([o[i] for o in outs]) for i in range(3))
    return Window(seconds=elapsed, events=done, completed=done,
                  bytes_written=wrote, store_bytes=bytes1 - bytes0,
                  sample_pos=pos, p=p, z=z, lam=lam,
                  batch_id=pos // run.batch, sink_stats=stats,
                  store_dir=store_dir, state=state_np)


# ------------------------------------------------------------------- serve
class LazyWallClock:
    """Monotonic wall clock whose zero is its first reading: the frontend
    first reads it when it starts admitting, so request due times count
    from there and not from the pipeline's construction.

    Just before that zero, the garbage the whole request schedule left is
    collected.  ``serve`` builds one Python object per request before it
    admits any; otherwise the full collection that this burst triggers
    falls a little before or a little after the zero, by chance, and puts
    a ~100 ms stall into some runs' first second and not others'.  The
    collector stays on in the window."""

    def __init__(self) -> None:
        self._t0 = None

    def now(self) -> float:
        if self._t0 is None:
            gc.collect()
            self._t0 = time.monotonic()
        return time.monotonic() - self._t0

    def sleep(self, dt: float) -> None:
        if dt > 0:
            time.sleep(dt)


def make_scorer(seed32: int, feature_dim: int, hidden: int
                ) -> ScorerParams:
    """Scorer weights on the device in one jitted call from the seed,
    float32 as served."""
    @jax.jit
    def build(key):
        k1, k2 = jax.random.split(key)
        return ScorerParams(
            w1=jax.random.normal(k1, (feature_dim, hidden)) / feature_dim
            ** 0.5,
            b1=jnp.zeros((hidden,)),
            w2=jax.random.normal(k2, (hidden, 1)) / hidden ** 0.5,
            b2=jnp.zeros((1,)),
            mu=jnp.zeros((feature_dim,)),
            sd=jnp.ones((feature_dim,)))
    return build(jax.random.PRNGKey(seed32 ^ 0x5C0E))


@dataclasses.dataclass
class Serve:
    pipe: ScoringPipeline
    batch: int
    max_wait_s: float
    rng: jax.Array


def prepare_serve(config: dict, traffic: dict, stream, rng, seed32: int,
                  warm_dir: str) -> Serve:
    eng = config["engine"]
    spec = ProfileSpec(windows=tuple(float(x) for x in eng["windows_s"]),
                       kde_bandwidth=float(eng["kde_bandwidth_s"]),
                       variance_alpha=float(eng["variance_alpha"]),
                       policy=eng["policy"])
    cfg = engine_config(eng)
    pipe = ScoringPipeline.build(
        spec, int(config["stream"]["n_keys"]), mode="fast",
        budget=cfg.budget, mu_tau_index=cfg.mu_tau_index, min_p=cfg.min_p)
    if pipe.engine.cfg != cfg:
        raise ValueError("the serving engine's config differs from the "
                         "configuration file's")
    pipe.scorer = make_scorer(seed32, spec.feature_dim,
                              int(traffic["scorer_hidden"]))
    run = Serve(pipe=pipe, batch=int(traffic["batch"]),
                max_wait_s=float(traffic["max_wait_s"]), rng=rng)
    # warm-up burst, all due at once: full batches through the same
    # [1, batch] dispatch program, the scorer and the durable sink
    w = int(traffic["warmup_requests"])
    sink = pipe.make_sink(backend="durable", store_dir=warm_dir)
    pipe.serve(stream.key[:w], stream.q[:w], stream.t[:w],
               arrival_s=np.zeros(w), batch=run.batch,
               max_wait_s=run.max_wait_s, rng=rng, sink=sink)
    sink.close()
    return run


def arrivals(n: int, seconds: float, seed: int) -> np.ndarray:
    """``n`` due times of a Poisson process over ``[0, seconds)``, given
    its count: sorted uniform draws.  Every seed offers the same load."""
    rng = np.random.default_rng([seed, 0xA77])
    return np.sort(rng.uniform(0.0, seconds, n))


def serve(run: Serve, stream, arrival_s: np.ndarray,
          sampled_key: np.ndarray, store_dir: str) -> Window:
    n = len(arrival_s)
    sink = run.pipe.make_sink(backend="durable", store_dir=store_dir)
    clock = LazyWallClock()
    bytes0 = _store_counts(sink.snapshot())[1]
    w0 = written_bytes()
    with annotate("bench.window"):
        with annotate("bench.serve"):
            res = run.pipe.serve(
                stream.key[:n], stream.q[:n], stream.t[:n],
                arrival_s=arrival_s, batch=run.batch,
                max_wait_s=run.max_wait_s, clock=clock, rng=run.rng,
                sink=sink)
        with annotate("bench.flush"):
            stats = sink.flush()
    elapsed = clock.now()
    wrote = written_bytes() - w0
    sink.close()
    sizes = np.asarray([b.size for b in res.batches])
    batch_of = np.repeat(np.arange(len(sizes)), sizes)
    order_off = int((res.order != np.arange(n)).sum()) + abs(
        int(sizes.sum()) - n)
    idx = np.flatnonzero(sampled_key[stream.key[:n]])
    return Window(seconds=elapsed, events=n, completed=int(sizes.sum()),
                  bytes_written=wrote,
                  store_bytes=_store_counts(stats)[1] - bytes0,
                  sample_pos=idx, p=res.p[idx],
                  z=res.z[idx], lam=res.lam_hat[idx],
                  batch_id=batch_of[idx] if len(batch_of) == n
                  else np.zeros(len(idx), np.int64),
                  sink_stats=stats, store_dir=store_dir,
                  scores=res.scores[idx], features=res.features[idx],
                  latency_s=np.asarray(res.latency_s, np.float64),
                  frontend=res.stats.snapshot(), order_off=order_off)
