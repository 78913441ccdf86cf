"""What the timed paths share: the ``Window`` each returns, and the helpers
they all use.

A timed path is a driver module of its own, ``bench/paths/<driver>.py``,
named by a traffic file's ``"driver"`` (see ``bench/harness.py`` for what
it provides).  Its ``Window`` holds what the comparison needs (the sampled
events' outputs, the final state where the path exposes it, the store's
directory) and what the metrics need (times, counts, the sink's and
frontend's stats).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import numpy as np

from repro.core.types import EngineConfig


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


def store_counts(stats: dict) -> tuple:
    """(rows put, bytes written) by the stores, from a sink's stats."""
    return stats["puts"], stats["measured"]["measured_bytes_written"]


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
    scorer: Optional[dict] = None    # the served scorer's weights, numpy
    # where the window makes several passes over one stream, the fields
    # above are the last pass's, and each earlier pass's outputs at
    # sample_pos and store directory are here: {p, z, lam, store_dir}
    passes: list = dataclasses.field(default_factory=list)
