"""The benchmark's own event-stream generator.

A copy of the program's Table 2 workload generator, kept here so that a
change to the program cannot change the yardstick.  Two departures, both for
set-up time: the Zipf exponent that puts 80% of the volume on the stated
share of keys is read from the configuration file instead of being bisected
at every run, and the whole stream is drawn in one vectorised pass from the
seed.  With the same exponent and seed the stream equals the program's
(``tests/test_bench_streamgen.py`` checks it).
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class StreamSpec:
    n_events: int
    n_keys: int
    zipf_exponent: float        # calibrated so vol80_share of keys carry 80%
    anomaly_rate: float         # share of events labelled anomalous
    mark: str                   # uniform | lognormal
    mark_param: float           # lognormal sigma (unused for uniform)
    duration_s: float           # nominal horizon before bursts compress it
    burst_factor: float = 10.0
    mark_shift: float = 3.0
    anom_pool_frac: float = 0.003

    @classmethod
    def from_config(cls, stream: dict, n_events: int) -> "StreamSpec":
        """``n_events`` of the configuration's stream, at its density of
        one event per ``nominal_gap_s`` seconds before bursts."""
        return cls(n_events=int(n_events), n_keys=int(stream["n_keys"]),
                   zipf_exponent=float(stream["zipf_exponent"]),
                   anomaly_rate=float(stream["anomaly_rate"]),
                   mark=stream["mark"],
                   mark_param=float(stream.get("mark_param", 0.0)),
                   duration_s=float(stream["nominal_gap_s"]) * n_events,
                   burst_factor=float(stream.get("burst_factor", 10.0)),
                   mark_shift=float(stream.get("mark_shift", 3.0)),
                   anom_pool_frac=float(stream.get("anom_pool_frac", 0.003)))


@dataclasses.dataclass
class Stream:
    key: np.ndarray     # int32 [N]
    q: np.ndarray       # float32 [N]
    t: np.ndarray       # float32 [N], seconds from the stream origin
    label: np.ndarray   # int8 [N], 1 = anomalous

    def __len__(self) -> int:
        return len(self.key)


def zipf_weights(n_keys: int, a: float) -> np.ndarray:
    w = 1.0 / np.arange(1, n_keys + 1, dtype=np.float64) ** a
    return w / w.sum()


def vol80_share(weights: np.ndarray) -> float:
    """Share of keys (heaviest first) that carry 80% of the volume."""
    cum = np.cumsum(np.sort(weights)[::-1])
    return (int(np.searchsorted(cum, 0.80)) + 1) / len(weights)


def _marks(rng: np.random.Generator, dist: str, param: float,
           n: int) -> np.ndarray:
    if dist == "lognormal":
        return rng.lognormal(3.0, param, n)
    if dist == "uniform":
        return rng.uniform(10.0, 100.0, n)
    raise ValueError(f"unknown mark distribution {dist!r}")


def generate(spec: StreamSpec, seed: int) -> Stream:
    """Time-ordered stream of ``spec.n_events`` events from ``seed``.

    Keys follow a Zipf law under a random permutation; anomalous events go
    to a small pool of hot entities that burst (shorter gaps) and draw
    shifted marks."""
    rng = np.random.default_rng(seed)
    weights = zipf_weights(spec.n_keys, spec.zipf_exponent)
    perm = rng.permutation(spec.n_keys)
    keys = perm[rng.choice(spec.n_keys, size=spec.n_events,
                           p=weights)].astype(np.int32)

    n_anom = int(round(spec.anomaly_rate * spec.n_events))
    label = np.zeros(spec.n_events, np.int8)
    if n_anom > 0:
        idx = rng.choice(spec.n_events, size=n_anom, replace=False)
        pool = max(1, int(spec.n_keys * spec.anom_pool_frac))
        anom_keys = rng.choice(spec.n_keys, size=pool,
                               replace=False).astype(np.int32)
        keys[idx] = anom_keys[rng.choice(pool, size=n_anom,
                                         p=zipf_weights(pool, 1.2))]
        label[idx] = 1

    gaps = rng.exponential(spec.duration_s / spec.n_events, spec.n_events)
    gaps[label == 1] /= spec.burst_factor
    t = np.cumsum(gaps)

    q = _marks(rng, spec.mark, spec.mark_param, spec.n_events)
    q[label == 1] *= spec.mark_shift

    order = np.argsort(t, kind="stable")
    return Stream(key=keys[order], q=q[order].astype(np.float32),
                  t=t[order].astype(np.float32), label=label[order])
