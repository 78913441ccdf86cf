"""Open-loop scoring.

A fixed number of requests, due at Poisson arrival times over the window,
go through ``ScoringPipeline.serve`` with a durable sink; a request's
latency is its completion time minus its due time.

Set-up (the scorer's weights, warm-up of every shape the window uses, the
schedule of due times) happens in ``prepare``, before the window opens.
"""
from __future__ import annotations

import dataclasses
import gc
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.drivers import (Window, annotate, engine_config, store_counts,
                           written_bytes)
from repro.features.spec import ProfileSpec
from repro.serving.pipeline import ScoringPipeline, ScorerParams


def events(config: dict, traffic: dict, seconds: float) -> int:
    """The window's requests: ``rate_per_s`` times ``seconds``."""
    return int(round(float(traffic["rate_per_s"]) * seconds))


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
    arrival_s: np.ndarray            # each request's due time
    store_dir: str
    sampled_key: np.ndarray          # [n_keys] bool: the keys followed


def prepare(config: dict, traffic: dict, stream, followed, *, seed, seed32,
            rng, seconds, devices, tmp: str) -> Serve:
    eng = config["engine"]
    n_keys = int(config["stream"]["n_keys"])
    sampled = np.zeros(n_keys, bool)
    sampled[followed] = True
    spec = ProfileSpec(windows=tuple(float(x) for x in eng["windows_s"]),
                       kde_bandwidth=float(eng["kde_bandwidth_s"]),
                       variance_alpha=float(eng["variance_alpha"]),
                       policy=eng["policy"])
    cfg = engine_config(eng)
    pipe = ScoringPipeline.build(
        spec, n_keys, mode="fast",
        budget=cfg.budget, mu_tau_index=cfg.mu_tau_index, min_p=cfg.min_p)
    if pipe.engine.cfg != cfg:
        raise ValueError("the serving engine's config differs from the "
                         "configuration file's")
    pipe.scorer = make_scorer(seed32, spec.feature_dim,
                              int(traffic["scorer_hidden"]))
    run = Serve(pipe=pipe, batch=int(traffic["batch"]),
                max_wait_s=float(traffic["max_wait_s"]), rng=rng,
                arrival_s=arrivals(len(stream), seconds, seed),
                store_dir=os.path.join(tmp, "store"), sampled_key=sampled)
    # warm-up burst, all due at once: full batches through the same
    # [1, batch] dispatch program, the scorer and the durable sink
    w = int(traffic["warmup_requests"])
    sink = pipe.make_sink(backend="durable",
                          store_dir=os.path.join(tmp, "warm"))
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


def window(run: Serve, stream) -> Window:
    n = len(run.arrival_s)
    sink = run.pipe.make_sink(backend="durable", store_dir=run.store_dir)
    clock = LazyWallClock()
    bytes0 = store_counts(sink.snapshot())[1]
    w0 = written_bytes()
    with annotate("bench.window"):
        with annotate("bench.serve"):
            res = run.pipe.serve(
                stream.key[:n], stream.q[:n], stream.t[:n],
                arrival_s=run.arrival_s, batch=run.batch,
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
    idx = np.flatnonzero(run.sampled_key[stream.key[:n]])
    scorer = run.pipe.scorer
    return Window(seconds=elapsed, events=n, completed=int(sizes.sum()),
                  bytes_written=wrote,
                  store_bytes=store_counts(stats)[1] - bytes0,
                  sample_pos=idx, p=res.p[idx],
                  z=res.z[idx], lam=res.lam_hat[idx],
                  batch_id=batch_of[idx] if len(batch_of) == n
                  else np.zeros(len(idx), np.int64),
                  sink_stats=stats, store_dir=run.store_dir,
                  scores=res.scores[idx], features=res.features[idx],
                  latency_s=np.asarray(res.latency_s, np.float64),
                  frontend=res.stats.snapshot(), order_off=order_off,
                  scorer={f: np.asarray(getattr(scorer, f), np.float64)
                          for f in scorer._fields})
