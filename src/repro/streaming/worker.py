"""Per-event feature-aggregation worker over a byte-backed KV store (§5).

Implements the paper's worker loop literally:
  (1) retrieve feature state + control statistics from storage (real SerDe)
  (2) materialize features for inference
  (3) derive an inclusion probability from disk-backed estimates only
  (4) sample a Bernoulli decision
  (5) execute a write-back only if selected
Inference happens for every event; persistence is gated.

This is the *measurement* engine for Table 3/4 benchmarks — per-event costs
(SerDe seconds, modeled IO seconds, write ops, bytes) are all observable —
and the **byte-level oracle** for the fast path's write-behind sink
(``streaming/persistence.py``): for the same stream, policy and rng, the
bytes this worker stores per key equal the bytes the sink stores.

Two design points make that parity exact rather than approximate:

* the worker holds no private decision math — steps (2)-(4) route through
  the same fused kernel as the vectorized engine (``ops.thinning_rmw`` on a
  single-event batch, with counter-based uniforms keyed on (entity,
  counter) by the rule of ``core.thinning.counters``),
  so decisions AND updated row values are bit-identical to the engine's
  (the kernel's reference path is compilation-context-invariant — see
  ``kernels/detmath.py``);
* under thinning policies the full-stream control column is not durable:
  stored rows carry the fresh (0.0, -inf) control column (a write-back
  cannot refresh state it does not maintain between writes), exactly like
  the sink.  Under 'full'/'unfiltered' every event writes back, so the
  stored control column stays current.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import thinning
from repro.core.types import EngineConfig
from repro.kernels import ops
from repro.streaming.kvstore import KVStore, SerDe, StorageModel

# Finite stand-in for -inf "never persisted" timestamps, matching
# core.engine._FRESH_SENTINEL (the kernel masks freshness on `< -1e30`).
_FRESH_SENTINEL = np.float32(-1e38)

_FULL_STREAM = ("full", "unfiltered")


# Uniforms in their own jitted program: the chain is integer fold-ins plus
# an exact bit-level float conversion, so its results are identical in any
# compilation context.  The fused RMW call below deliberately stays the
# plain ``ops.thinning_rmw`` jit entry — folding it into a bigger per-event
# program would compile the kernel in yet another context, which is exactly
# what the byte-parity contract must avoid (see kernels/detmath.py).
_uniform_jit = jax.jit(thinning.uniform_for_events)


@functools.lru_cache(maxsize=None)
def _event_step(cfg: EngineConfig):
    """Single-event decision+update via the shared fused kernel.

    Cached per config so every worker with the same policy shares the same
    compiled programs (B=1 shapes; EngineConfig is frozen/hashable).
    """
    taus = jnp.asarray(cfg.taus, jnp.float32)
    kw = dict(h=cfg.h, budget=cfg.budget, alpha=cfg.alpha, policy=cfg.policy,
              fixed_rate=cfg.fixed_rate, mu_tau_index=cfg.mu_tau_index,
              min_p=cfg.min_p)
    ones = jnp.ones((1,), jnp.float32)

    def step(rng, ent, ctr, last_t, v_f, agg, q, t, v_full, last_t_full):
        t1 = t[None]
        u = _uniform_jit(rng, ent[None], ctr[None])
        return ops.thinning_rmw(
            taus, last_t[None], v_f[None], agg.reshape(1, -1), q[None],
            t1, u, ones, v_full[None], last_t_full[None], **kw)

    return step


@dataclasses.dataclass
class WorkerMetrics:
    events: int = 0
    writes: int = 0
    score_calls: int = 0
    compute_s: float = 0.0
    # Per-event *worker-model* latency, appended by process(): real SerDe
    # time + modeled storage service time.  The oracle's jax dispatch
    # overhead (compute_s) is deliberately excluded — it stands in for
    # sub-microsecond scalar decision math in the paper's JVM worker and
    # would otherwise swamp the storage model that Table 3/4 ratios are
    # built on.
    latencies_s: Optional[list] = None

    def write_pct(self) -> float:
        return 100.0 * self.writes / max(self.events, 1)


class FeatureWorker:
    """One partition worker: KV store + persistence-path control.

    ``rng`` is the thinning RNG root (a jax PRNG key).  Decisions are
    counter-based on (entity id, event counter) — reproducible and
    order/batching-invariant, and identical to the vectorized engine's when
    the same root key is used (which is what the parity tests do).
    """

    def __init__(self, cfg: EngineConfig, store: Optional[KVStore] = None,
                 seed: int = 0, record_latency: bool = True,
                 rng: Optional[jax.Array] = None):
        self.cfg = cfg
        self.taus = np.asarray(cfg.taus, np.float64)
        self.store = store or KVStore(seed=seed)
        self.serde = SerDe(len(cfg.taus))
        self.rng = rng if rng is not None else jax.random.PRNGKey(seed + 17)
        self.metrics = WorkerMetrics(
            latencies_s=[] if record_latency else None)
        self._step = _event_step(cfg)
        self._full_stream = cfg.policy in _FULL_STREAM

    @staticmethod
    def _fin(x: float) -> np.float32:
        """-inf -> kernel freshness sentinel (finite, VPU-safe)."""
        return np.float32(x) if math.isfinite(x) else _FRESH_SENTINEL

    def process(self, key, q: float, t: float, ctr=None):
        """One event through the worker loop.  Returns observability dict.

        ``key`` is an entity id, or the K ids of the profiles the event
        updates, one after another (a list of dicts then); ``ctr`` is the
        event's RNG counter (``core.thinning.counters``).

        ``latency_s`` in the result (and ``metrics.latencies_s``) is the
        worker-model per-event latency: real SerDe seconds + modeled
        storage service seconds.  ``compute_s`` is the measured wall time
        of the oracle implementation (dominated by per-event jax dispatch)
        and is reported separately.
        """
        if np.ndim(key):
            return [self.process(int(k), q, t, ctr) for k in key]
        serde, store = self.serde, self.store
        t0 = time.perf_counter()
        io0 = store.counters.modeled_io_s
        sd0 = store.counters.serde_s

        # (1) retrieve + deserialize
        raw = store.get(int(key))
        ts0 = time.perf_counter()
        if raw is None:
            row = (-math.inf, 0.0, np.zeros((len(self.taus), 3), np.float32),
                   0.0, -math.inf)
        else:
            row = serde.unpack(raw, key=int(key))
        store.counters.serde_s += time.perf_counter() - ts0
        last_t, v_f, agg, v_full, last_t_full = row

        # (2)-(4) materialize + decide + Bernoulli: the fused engine kernel
        # on a single-event batch (no private decision math in this class).
        (nlt, nvf, nagg, z_, p_, feats, lam_, nvfull, nltf) = self._step(
            self.rng, jnp.asarray(int(key), jnp.uint32),
            thinning.counters(jnp.float32(t),
                              None if ctr is None else np.uint32(ctr)),
            jnp.asarray(self._fin(last_t)), jnp.asarray(np.float32(v_f)),
            jnp.asarray(agg, jnp.float32), jnp.asarray(np.float32(q)),
            jnp.asarray(np.float32(t)), jnp.asarray(np.float32(v_full)),
            jnp.asarray(self._fin(last_t_full)))
        z = bool(z_[0])
        p = float(p_[0])
        lam = float(lam_[0])
        features = np.asarray(feats[0])
        self.metrics.score_calls += 1

        # (5) conditional write-back (serialize + put).  Kernel outputs are
        # already z-masked (new == old on z=0 lanes), so the packed row is
        # the post-event durable row in either case.
        if z or self._full_stream:
            if z:
                self.metrics.writes += 1
            store_lt = float(nlt[0])
            if store_lt < -1e30:        # sentinel back to -inf for storage
                store_lt = -math.inf
            if self._full_stream:
                ctrl = (float(nvfull[0]), float(nltf[0]))
            else:
                # thinning policies do not maintain the control column
                # durably; stored rows carry the fresh column (sink parity)
                ctrl = (0.0, -math.inf)
            ts0 = time.perf_counter()
            raw = serde.pack(store_lt, float(nvf[0]),
                             np.asarray(nagg[0]).reshape(-1, 3), *ctrl)
            store.counters.serde_s += time.perf_counter() - ts0
            store.put(int(key), raw)

        self.metrics.events += 1
        compute = time.perf_counter() - t0
        self.metrics.compute_s += compute
        latency = (store.counters.serde_s - sd0) \
            + (store.counters.modeled_io_s - io0)
        if self.metrics.latencies_s is not None:
            self.metrics.latencies_s.append(latency)
        return {"p": p, "z": z, "lam": lam, "features": features,
                "compute_s": compute, "latency_s": latency}

    def features_at(self, key: int, t: float) -> np.ndarray:
        """Read-only feature materialization (scoring path, no write)."""
        raw = self.store.get(int(key))
        if raw is None:
            agg_now = np.zeros((len(self.taus), 3), np.float32)
        else:
            last_t, v_f, agg, *_ = self.serde.unpack(raw, key=int(key))
            dt = t - last_t
            agg_now = agg * np.exp(
                -np.clip(dt, 0, None) / self.taus)[:, None] \
                if math.isfinite(last_t) else np.zeros_like(agg)
        cnt = agg_now[:, 0]
        s = agg_now[:, 1]
        mean = s / np.maximum(cnt, 1e-12)
        var = np.maximum(agg_now[:, 2] / np.maximum(cnt, 1e-12) - mean ** 2,
                         0.0)
        return np.concatenate([cnt, s, mean, np.sqrt(var)]).astype(np.float32)
