"""Program spans (``core.spans``) and the counters beside them.

* the span helper adds its wall time to a stats field, counts, and nests;
* under a profiler trace the ingest path's spans are events on the host
  plane of the ``.xplane.pb``, the dispatcher's on another thread's line
  than the driver's;
* the device-to-host byte counters equal their formulas from the shapes;
* every compaction is timed, inline or on the background compactor;
* the sink's written-byte count covers every file the store writes;
* the sink step's compiled HLO names the engine's scopes;
* the serving frontend's queue wait is the due-to-dispatch time.
"""
import dataclasses
import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import EngineConfig, init_state
from repro.core import stream as core_stream
from repro.core.spans import span
from repro.core.stream import run_stream
from repro.core.types import Event
from repro.serving.frontend import (ServingFrontend, VirtualClock,
                                    make_requests)
from repro.streaming.durable import DurableStore, FileOps
from repro.streaming.persistence import WriteBehindSink

N_KEYS = 64


def _cfg(policy="pp", n_taus=6):
    return EngineConfig(taus=(60.0, 600.0, 3600.0, 21600.0, 86400.0,
                              604800.0)[:n_taus], h=600.0, budget=0.002,
                        alpha=1.0, policy=policy, mu_tau_index=1)


def _stream(n_events, seed=0):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, N_KEYS, n_events).astype(np.int32)
    ts = np.cumsum(rng.exponential(20.0, n_events)).astype(np.float32)
    qs = rng.lognormal(3.0, 1.0, n_events).astype(np.float32)
    return keys, qs, ts


@dataclasses.dataclass
class _Stats:
    outer_s: float = 0.0
    inner_s: float = 0.0
    outers: int = 0


def test_span_adds_time_and_count_and_nests():
    st = _Stats()
    with span("repro.test.outer", st, "outer_s", count="outers"):
        with span("repro.test.inner", st, "inner_s"):
            time.sleep(0.01)
        time.sleep(0.005)
    assert st.outers == 1
    assert st.inner_s >= 0.01
    assert st.outer_s >= st.inner_s + 0.005
    with pytest.raises(RuntimeError):
        with span("repro.test.outer", st, "outer_s", count="outers"):
            raise RuntimeError("the span still closes")
    assert st.outers == 2


def _host_events(trace_dir):
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name.startswith("repro."):
                        lines.setdefault(e.name, set()).add(
                            (plane.name, i))
    return lines


def test_ingest_spans_land_on_the_host_plane(tmp_path):
    cfg = _cfg()
    keys, qs, ts = _stream(2048)
    sink = WriteBehindSink(cfg, backend="durable",
                           store_dir=str(tmp_path / "store"),
                           store_kw={"compact_threshold_bytes": 2048})
    trace_dir = str(tmp_path / "trace")
    jax.profiler.start_trace(trace_dir)
    try:
        _, info = run_stream(cfg, init_state(N_KEYS, len(cfg.taus)),
                             keys, qs, ts, batch=128, mode="fast",
                             sink=sink, sink_group=2)
        jax.block_until_ready(info.z)
        sink.flush()
    finally:
        jax.profiler.stop_trace()
        sink.close()
    lines = _host_events(trace_dir)
    for name in ("repro.stream.pack", "repro.stream.dispatch",
                 "repro.stream.outputs", "repro.sink.submit",
                 "repro.sink.flush", "repro.sink.device_wait",
                 "repro.sink.rows_d2h", "repro.sink.serde",
                 "repro.store.put", "repro.store.wal_write",
                 "repro.store.fsync", "repro.store.compact"):
        assert name in lines, (name, sorted(lines))
    # driver, dispatcher and store worker each on a line of their own
    assert not lines["repro.stream.dispatch"] & lines["repro.sink.flush"]
    assert not lines["repro.sink.flush"] & lines["repro.store.put"]


@pytest.mark.parametrize("collect_info", [True, False])
def test_d2h_byte_counters_equal_their_formulas(collect_info):
    # 'full' persists every valid lane, so every group copies its rows
    cfg = _cfg("full")
    n_taus = len(cfg.taus)
    n, batch, group = 1000, 128, 3
    keys, qs, ts = _stream(n)
    sink = WriteBehindSink(cfg)
    run_stream(cfg, init_state(N_KEYS, n_taus), keys, qs, ts, batch=batch,
               mode="fast", sink=sink, sink_group=group,
               collect_info=collect_info)
    st = sink.flush()
    sink.close()
    blocks = -(-n // batch)
    lanes = blocks * batch
    # rows: scalars [4, lanes] + aggregates [lanes, T, 3], float32
    assert st["rows_d2h_bytes"] == lanes * (4 * 4 + n_taus * 3 * 4)
    # outputs: z (bool) + per-block writes (int32), and with collect_info
    # p, lam_hat (float32) and 4T features (float32)
    per_lane = 1 + (4 + 4 + 4 * n_taus * 4 if collect_info else 0)
    assert st["outputs_d2h_bytes"] == lanes * per_lane + blocks * 4
    assert st["outputs_s"] > 0.0 and st["rows_d2h_s"] > 0.0
    assert st["dispatch_s"] > 0.0 and st["blocks"] == -(-blocks // group)


@pytest.mark.parametrize("compaction", ["inline", "background"])
def test_every_compaction_is_timed(tmp_path, compaction):
    rows = [(k, bytes([k % 251]) * 64) for k in range(600)]
    with DurableStore(str(tmp_path / "s"), compaction=compaction,
                      compact_threshold_bytes=4096) as s:
        for i in range(0, len(rows), 50):
            ck = rows[i:i + 50]
            s.multi_put([k for k, _ in ck], [v for _, v in ck])
        s.wait_for_compaction()
        s.compact()
        d = s.durable
        assert d.compactions >= 2
        assert d.compaction_s > 0.0
        if compaction == "inline":
            assert d.compaction_stall_s > 0.0
        else:
            assert d.compaction_stall_s == 0.0


class _CountingOps(FileOps):
    """Counts every byte the store hands to a file's ``write``."""

    def __init__(self):
        self.written = 0

    def open(self, path, mode):
        f = super().open(path, mode)
        if "r" not in mode:
            orig = f.write

            def write(b):
                self.written += len(b)
                return orig(b)
            f.write = write
        return f


def test_measured_bytes_written_counts_every_file(tmp_path):
    ops = _CountingOps()
    store = DurableStore(str(tmp_path / "s"), fileops=ops,
                         compact_threshold_bytes=4096)
    cfg = _cfg()
    keys, qs, ts = _stream(4096)
    sink = WriteBehindSink(cfg, stores=[store])
    run_stream(cfg, init_state(N_KEYS, len(cfg.taus)), keys, qs, ts,
               batch=256, mode="fast", sink=sink, sink_group=2)
    m = sink.flush()["measured"]
    sink.close()
    store.close()
    assert m["compactions"] >= 1 and m["seg_index_bytes"] > 0
    assert m["measured_bytes_written"] == ops.written


def test_sink_step_hlo_names_the_engine_scopes():
    cfg = _cfg()
    n_taus = len(cfg.taus)
    group, batch = 2, 64
    step = core_stream.sink_step_for(
        core_stream.make_step(cfg, "fast"), donate=False)
    z = lambda dt: jnp.zeros((group, batch), dt)
    ev = Event(key=z(jnp.int32), q=z(jnp.float32), t=z(jnp.float32),
               valid=z(bool))
    text = step.lower(init_state(N_KEYS, n_taus), ev,
                      jax.random.PRNGKey(0),
                      jnp.zeros(group * batch, jnp.int32)).compile().as_text()
    for scope in ("decide", "fold", "fold_control", "sink_gather"):
        assert f"/{scope}/" in text, scope


def test_frontend_queue_wait_is_due_to_dispatch():
    cfg = _cfg(n_taus=2)
    keys, qs, ts = _stream(5)
    arrival = np.array([0.0, 0.1, 0.2, 0.3, 5.0])
    fe = ServingFrontend(cfg, init_state(N_KEYS, 2), batch=4,
                         max_wait_s=1.0, mode="fast", clock=VirtualClock())
    res = fe.run(make_requests(keys, qs, ts, arrival_s=arrival))
    st = res.stats
    # a full batch at 0.3 (waits 0.3, 0.2, 0.1, 0), then a partial batch
    # at its deadline 6.0 (waits 1.0)
    assert st.queue_wait_s == pytest.approx(1.6)
    assert st.queue_wait_max_s == pytest.approx(1.0)
    assert st.dispatch_s > 0.0 and st.materialize_s > 0.0
