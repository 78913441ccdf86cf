"""Shared benchmark plumbing: engine drivers, CSV output, CI helpers."""
from __future__ import annotations

import dataclasses
import sys
import time
from typing import Iterable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import EngineConfig, Event, init_state, make_step
from repro.streaming.workload import Stream


def ci95(xs) -> float:
    xs = np.asarray(xs, np.float64)
    if len(xs) < 2:
        return 0.0
    return 1.96 * xs.std(ddof=1) / np.sqrt(len(xs))


def memory_watermark() -> dict:
    """Peak-memory columns for bench rows: donation observability.

    Donating stream drivers should hold device memory flat at ~one state
    copy; a zero-copy regression shows up as a watermark jump between
    successive BENCH_engine.json snapshots.  Backends that report allocator
    stats (TPU/GPU) give ``peak_bytes_in_use`` per device; the CPU backend
    reports none, so there we fall back to the host's peak RSS (which still
    moves when donation breaks, since XLA:CPU buffers live in host memory).
    Any other backend that reports no peak is an error, never host RSS.

    Semantics: both sources are **process-lifetime cumulative peaks** — they
    never reset, so within one JSON snapshot later rows inherit earlier
    rows' peaks and rows are only comparable *across* snapshots (same row,
    previous commit), not against each other.  A per-row attribution would
    need one subprocess per row; the cross-snapshot trajectory is what the
    regression check needs.
    """
    dev = jax.local_devices()[0]
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    if peak:
        return {"mem_watermark_bytes": int(peak),
                "mem_watermark_src": "device"}
    if dev.platform != "cpu":
        raise RuntimeError(f"{dev.platform} device {dev.device_kind} reports "
                           f"no peak_bytes_in_use")
    import resource
    rss_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    return {"mem_watermark_bytes": int(rss_kb) * 1024,
            "mem_watermark_src": "host_rss"}


def emit(table: str, row: dict, file=None):
    """One CSV-ish line per result; benchmarks/run.py tees these."""
    kv = ",".join(f"{k}={v}" for k, v in row.items())
    print(f"[{table}] {kv}", file=file or sys.stdout, flush=True)


@dataclasses.dataclass
class EngineRun:
    """Output of driving the vectorized engine over a full stream."""
    write_pct: float
    features: np.ndarray      # [N, F] decision-time features (pre-update)
    z: np.ndarray             # [N] persisted?
    p: np.ndarray             # [N]
    state: object             # final ProfileState
    wall_s: float
    events_per_s: float


def drive_stream(stream: Stream, cfg: EngineConfig, *, batch: int = 4096,
                 seed: int = 0, mode: str = "fast") -> EngineRun:
    """Run the JAX vectorized engine over a stream (single shard)."""
    n_keys = int(stream.key.max()) + 1
    state = init_state(n_keys, len(cfg.taus))
    step = jax.jit(make_step(cfg, mode))
    rng = jax.random.PRNGKey(seed)

    n = len(stream)
    feats: List[np.ndarray] = []
    zs: List[np.ndarray] = []
    ps: List[np.ndarray] = []
    t0 = time.perf_counter()
    for i in range(0, n, batch):
        j = min(i + batch, n)
        pad = batch - (j - i)
        key = np.pad(stream.key[i:j], (0, pad))
        q = np.pad(stream.q[i:j], (0, pad))
        t = np.pad(stream.t[i:j], (0, pad))
        valid = np.pad(np.ones(j - i, bool), (0, pad))
        ev = Event(key=jnp.asarray(key), q=jnp.asarray(q),
                   t=jnp.asarray(t), valid=jnp.asarray(valid))
        state, info = step(state, ev, rng)
        feats.append(np.asarray(info.features[: j - i]))
        zs.append(np.asarray(info.z[: j - i]))
        ps.append(np.asarray(info.p[: j - i]))
    jax.block_until_ready(state.agg)
    wall = time.perf_counter() - t0
    z = np.concatenate(zs)
    return EngineRun(
        write_pct=100.0 * z.mean(),
        features=np.concatenate(feats),
        z=z, p=np.concatenate(ps), state=state, wall_s=wall,
        events_per_s=n / wall)


def true_decayed_sums(stream: Stream, taus, t_end: float) -> np.ndarray:
    """Ground-truth (unfiltered, exact) decayed sums per key at t_end."""
    taus = np.asarray(taus)
    n_keys = int(stream.key.max()) + 1
    out = np.zeros((n_keys, len(taus)))
    w = np.exp(-(t_end - stream.t)[:, None] / taus[None, :]) \
        * stream.q[:, None]
    np.add.at(out, stream.key, w)
    return out


def estimated_decayed_sums(state, taus, t_end: float) -> np.ndarray:
    """Engine-state decayed sums at t_end (lazy decay applied)."""
    from repro.core.types import AGG_SUM
    last_t = np.asarray(state.last_t)
    agg = np.asarray(state.agg)          # [E, T, 3]
    taus = np.asarray(taus)
    dt = np.clip(t_end - last_t, 0, None)[:, None]
    beta = np.where(np.isfinite(dt), np.exp(-dt / taus[None, :]), 0.0)
    return agg[..., AGG_SUM] * beta
