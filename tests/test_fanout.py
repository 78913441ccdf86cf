"""Events that update several profiles, and per-event RNG counters.

A ``[N, K]``-keyed stream runs as the lane-expanded ``[K*N]`` stream does,
bit for bit, through the single-scan and sink drivers; exact mode agrees
with the per-event oracle; the stream's own counters tell tied (key, t)
events apart, where time bits cannot; and a flat stream without counters
decides as it always did.
"""
import numpy as np
import jax
import pytest

from repro.core import EngineConfig, init_state, run_stream
from repro.core.reference import ReferenceEngine
from repro.streaming.persistence import WriteBehindSink, hydrate_state

K = 2


def _bids(rng, n, n_bidders=40, n_auctions=60, hot=0.75, per_ms=10):
    """A small bid stream: [n, 2] keys (bidder row, auction row), one hot
    bidder, ``per_ms`` events a millisecond, event ids as counters."""
    bidder = np.where(rng.random(n) < hot, 3,
                      rng.integers(0, n_bidders, n))
    auction = n_bidders + rng.integers(0, n_auctions, n)
    keys = np.stack([bidder, auction], axis=1).astype(np.int32)
    ids = np.arange(n, dtype=np.int64) * 7 + 5
    ts = ((ids // per_ms) / 1000.0).astype(np.float32)
    qs = (10.0 ** (rng.random(n) * 3)).astype(np.float32)
    return keys, qs, ts, ids.astype(np.uint32), n_bidders + n_auctions


CFG = EngineConfig(taus=(10.0, 600.0, 86400.0), h=10.0, budget=0.01,
                   alpha=1.0, policy="pp_vr", mu_tau_index=1,
                   exact_rounds=64)


def _lanes(keys, qs, ts, ids):
    return (keys.reshape(-1), np.repeat(qs, K), np.repeat(ts, K),
            np.repeat(ids, K))


def _durable(sink_dir, n_rows):
    from repro.streaming.durable import open_partition_stores
    stores = open_partition_stores(sink_dir, 1)
    try:
        return hydrate_state(stores, n_rows, len(CFG.taus))
    finally:
        for s in stores:
            s.close()


@pytest.mark.parametrize("driver", ["scan", "sink", "pipelined"])
def test_fanout_equals_lane_expanded_stream(tmp_path, driver):
    """[N, 2] keys give, bit for bit, the decisions, state and durable
    rows of the [2N] stream of their lanes (fast mode)."""
    keys, qs, ts, ids, n_rows = _bids(np.random.default_rng(1), 1000)
    root = jax.random.PRNGKey(3)
    runs = {}
    for name, args, batch in (("fan", (keys, qs, ts, ids), 128),
                              ("lanes", _lanes(keys, qs, ts, ids), 256)):
        kw = {}
        if driver != "scan":
            kw = dict(sink=WriteBehindSink(
                CFG, backend="durable", store_dir=str(tmp_path / name)),
                sink_group=2, pipeline_depth=2 if driver == "pipelined"
                else 1)
        state, info = run_stream(CFG, init_state(n_rows, len(CFG.taus)),
                                 *args[:3], counters=args[3], batch=batch,
                                 mode="fast", rng=root, **kw)
        if kw:
            kw["sink"].close()
            runs[name + "_rows"] = _durable(str(tmp_path / name), n_rows)
        runs[name] = (state, info)
    (s_f, i_f), (s_l, i_l) = runs["fan"], runs["lanes"]
    assert np.asarray(i_f.z).shape == (1000, K)
    assert np.asarray(i_f.features).shape == (1000, K, 4 * len(CFG.taus))
    for f in ("z", "p", "lam_hat", "features"):
        a, b = np.asarray(getattr(i_f, f)), np.asarray(getattr(i_l, f))
        np.testing.assert_array_equal(a.reshape(b.shape), b, err_msg=f)
    assert int(i_f.writes) == int(i_l.writes) > 0
    for a, b, name in zip(s_f, s_l, s_f._fields):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), name)
    if driver != "scan":
        for a, b, name in zip(runs["fan_rows"], runs["lanes_rows"],
                              s_f._fields):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          name)


def test_fanout_exact_mode_matches_oracle():
    """Exact mode on [N, 2] keys with event-id counters equals the
    per-event oracle, which updates each event's two profiles in order;
    tied (key, t) events keep their order.  No key holds more events in a
    batch than ``exact_rounds``."""
    keys, qs, ts, ids, n_rows = _bids(np.random.default_rng(2), 384,
                                      hot=0.3)
    root = jax.random.PRNGKey(9)
    batch = 64
    counts = [np.bincount(keys[i:i + batch].ravel()).max()
              for i in range(0, len(qs), batch)]
    assert max(counts) <= CFG.exact_rounds
    state, info = run_stream(CFG, init_state(n_rows, len(CFG.taus)), keys,
                             qs, ts, counters=ids, batch=batch, mode="exact",
                             rng=root)
    ref = ReferenceEngine(CFG, n_rows, root)
    out = [ref.process(k, float(q), float(t), int(c))
           for k, q, t, c in zip(keys, qs, ts, ids)]
    p = np.array([[r[0] for r in o] for o in out])
    z = np.array([[r[1] for r in o] for o in out])
    np.testing.assert_array_equal(np.asarray(info.z), z)
    np.testing.assert_allclose(np.asarray(info.p), p, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(state.agg),
                               np.stack([e.agg for e in ref.ents]),
                               rtol=2e-4, atol=1e-3)
    np.testing.assert_allclose(np.asarray(state.v_f),
                               [e.v_f for e in ref.ents], rtol=2e-4,
                               atol=1e-5)
    assert ref.writes == int(z.sum()) > 0


def test_fanout_sink_bytes_equal_worker_bytes():
    """The byte-parity contract with K profiles per event: the sink stores
    what the per-event worker stores when it updates each event's two
    profiles in order, with the event's counter."""
    from repro.streaming.kvstore import KVStore
    from repro.streaming.worker import FeatureWorker

    keys, qs, ts, ids, n_rows = _bids(np.random.default_rng(8), 320,
                                      hot=0.3)
    root = jax.random.PRNGKey(13)
    sink = WriteBehindSink(CFG)
    _, info = run_stream(CFG, init_state(n_rows, len(CFG.taus)), keys, qs,
                         ts, counters=ids, batch=64, mode="exact", rng=root,
                         sink=sink)
    sink.flush()
    worker = FeatureWorker(CFG, KVStore(seed=0), rng=root)
    out = [worker.process(k, float(q), float(t), int(c))
           for k, q, t, c in zip(keys, qs, ts, ids)]
    assert [[r["z"] for r in o] for o in out] == np.asarray(info.z).tolist()
    assert sink.stores[0].data == worker.store.data
    assert worker.metrics.writes == int(info.writes) > 0
    sink.close()


def _tied_draws(counters: bool, seeds=200, runs=16, run_len=4, p=0.25):
    """One key's stream in ``runs`` runs of ``run_len`` events with equal
    (key, t), under the fixed-rate policy, over ``seeds`` thinning seeds:
    (mean HT count, its 3-sigma bound for independent draws, the share of
    runs whose draws all agree, that share for independent draws)."""
    n = runs * run_len
    keys = np.full(n, 2, np.int32)
    ts = np.repeat(np.arange(runs, dtype=np.float32) * 1e-3, run_len)
    qs = np.ones(n, np.float32)
    ids = np.arange(n, dtype=np.uint32) if counters else None
    cfg = EngineConfig(taus=(1e9,), policy="fixed", fixed_rate=p)
    counts, same = [], []
    for s in range(seeds):
        state, info = run_stream(cfg, init_state(4, 1), keys, qs, ts,
                                 counters=ids, batch=n, mode="fast",
                                 rng=jax.random.PRNGKey(s))
        counts.append(float(state.agg[2, 0, 0]))
        z = np.asarray(info.z).reshape(runs, run_len)
        same.extend(z.all(axis=1) | ~z.any(axis=1))
    sigma = np.sqrt(n * (1 - p) / p / seeds)
    return (np.mean(counts) - n, 3 * sigma, np.mean(same),
            p ** run_len + (1 - p) ** run_len, len(same))


@pytest.mark.parametrize("counters,independent", [(True, True),
                                                  (False, False)])
def test_tied_events_draw_independently_only_with_counters(counters,
                                                           independent):
    """Runs of equal (key, t): with event-id counters the HT count is
    unbiased within 3 sigma and a run's draws agree as often as
    independent Bernoulli draws do; keyed on time bits, a run's events
    draw one uniform and are written together or not at all."""
    bias, bound, same, want, n = _tied_draws(counters)
    tol = 4 * np.sqrt(want * (1 - want) / n)
    assert bool(abs(bias) <= bound and abs(same - want) <= tol) \
        is independent
    if not independent:
        assert same == 1.0


def test_flat_stream_without_counters_decides_as_before():
    """A [N] stream with no counters keys its draws on time bits, and its
    decisions are those the engine took before counters existed."""
    rng = np.random.default_rng(11)
    n, E = 384, 24
    keys = rng.integers(0, E, n).astype(np.int32)
    ts = np.cumsum(rng.exponential(20.0, n)).astype(np.float32)
    qs = rng.lognormal(3.0, 1.0, n).astype(np.float32)
    cfg = EngineConfig(taus=(60.0, 3600.0), h=600.0, budget=2e-4, alpha=1.0,
                       policy="pp_vr", mu_tau_index=1, exact_rounds=64)
    want = {"fast": (73, "451580a01080740010104041000100801049004780c00680"
                         "4a19c016061d0020c0300000035000000a12000180060130"),
            "exact": (68, "455590a510807408961740010040018200400047008000"
                          "004a0800d2041c010080200000011010000a56000000040"
                          "910")}
    bits = np.asarray(ts).view(np.uint32)
    for mode, (writes, packed) in want.items():
        for ctr in (None, bits):
            _, info = run_stream(cfg, init_state(E, 2), keys, qs, ts,
                                 counters=ctr, batch=128, mode=mode,
                                 rng=jax.random.PRNGKey(23))
            z = np.asarray(info.z)
            assert int(z.sum()) == writes
            assert np.packbits(z).tobytes().hex() == packed


def test_fanout_counters_reach_the_sink_stats(tmp_path):
    keys, qs, ts, ids, n_rows = _bids(np.random.default_rng(4), 512)
    sink = WriteBehindSink(CFG, backend="durable", store_dir=str(tmp_path))
    run_stream(CFG, init_state(n_rows, len(CFG.taus)), keys, qs, ts,
               counters=ids, batch=128, sink=sink, sink_group=2)
    st = sink.flush()
    sink.close()
    assert st["fanout_lanes"] == K * 512
    assert st["fanout_s"] > 0


def test_residency_refuses_fanout(tmp_path):
    keys, qs, ts, ids, n_rows = _bids(np.random.default_rng(5), 64)
    sink = WriteBehindSink(CFG, backend="durable", store_dir=str(tmp_path))
    with pytest.raises(NotImplementedError):
        run_stream(CFG, init_state(32, len(CFG.taus)), keys, qs, ts,
                   counters=ids, batch=32, sink=sink, residency=32)
    sink.close()


def test_fanout_d2h_counters_are_exact():
    """Lanes leave the device once: the rows and outputs counters equal
    their formulas over K lanes per event, under 'full' (every lane's row
    is copied)."""
    cfg = EngineConfig(taus=(10.0, 600.0), h=10.0, policy="full")
    n, batch, group = 500, 64, 3
    keys, qs, ts, ids, n_rows = _bids(np.random.default_rng(6), n)
    sink = WriteBehindSink(cfg)
    _, info = run_stream(cfg, init_state(n_rows, 2), keys, qs, ts,
                         counters=ids, batch=batch, sink=sink,
                         sink_group=group)
    st = sink.flush()
    sink.close()
    blocks = -(-n // batch)
    lanes = blocks * batch * K
    assert np.asarray(info.p).shape == (n, K)
    assert st["rows_d2h_bytes"] == lanes * (4 * 4 + 2 * 3 * 4)
    assert st["outputs_d2h_bytes"] == lanes * (1 + 4 + 4 + 4 * 2 * 4) \
        + blocks * 4


def test_fanout_span_lands_inside_the_pack_span(tmp_path):
    import glob
    import os
    from jax.profiler import ProfileData

    keys, qs, ts, ids, n_rows = _bids(np.random.default_rng(7), 512)
    sink = WriteBehindSink(CFG, backend="durable", store_dir=str(tmp_path))
    trace_dir = str(tmp_path / "trace")
    jax.profiler.start_trace(trace_dir)
    try:
        _, info = run_stream(CFG, init_state(n_rows, len(CFG.taus)), keys,
                             qs, ts, counters=ids, batch=128, sink=sink,
                             sink_group=2)
        jax.block_until_ready(info.z)
        sink.flush()
    finally:
        jax.profiler.stop_trace()
        sink.close()
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[-1]
    spans = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in ("repro.stream.pack", "repro.stream.fanout"):
                    spans.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    assert spans["repro.stream.fanout"]
    for lo, hi in spans["repro.stream.fanout"]:
        assert any(a <= lo and hi <= b
                   for a, b in spans["repro.stream.pack"])
