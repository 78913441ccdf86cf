"""The NEXmark bid deployment: its generator keeps the rules of Beam's
Nexmark generator at the default configuration, and its check sees a
program that keys tied bids' draws on time bits instead of event ids."""
import numpy as np
import pytest

from benchtest import harness, small_cell

from bench.nexmark import stream as nexmark

import repro.core.engine as engine
import repro.core.stream as core_stream

SEED = 2**31 + 1409
N = 200_000


@pytest.fixture(scope="module")
def config():
    return harness.load_json(harness.BENCH, "configs", "nexmark_bids.json")


@pytest.fixture(scope="module")
def bids(config):
    return nexmark.make_stream(config, N, SEED)


def test_cycle_of_50_ids(config, bids):
    """Each cycle of 50 event ids holds 1 person, 3 auctions and 46 bids:
    bid ids skip the first 4 of every 50."""
    st = config["stream"]
    ids = bids.ctr.astype(np.int64)
    assert np.all(ids % 50 >= st["person_proportion"]
                  + st["auction_proportion"])
    per_cycle = np.bincount(ids // 50)
    assert np.all(per_cycle[:-1] == st["bid_proportion"])
    assert per_cycle[-1] == N - (len(per_cycle) - 1) * st["bid_proportion"]
    assert np.all(np.diff(ids) > 0)


def test_hot_shares(config, bids):
    """Half the bids go to the hot auction and three quarters come from
    the hot bidder, within 4 binomial sigmas (cold draws add the share of
    the in-flight range that the hot id takes)."""
    st = config["stream"]
    ids = bids.ctr.astype(np.int64)
    hot_b = nexmark.last_person(st, ids) // 100 * 100 + 1
    hot_a = nexmark.last_auction(st, ids) // 100 * 100
    share_b = np.mean(bids.key[:, 0] == hot_b)
    share_a = np.mean(bids.key[:, 1] - bids.n_person_rows == hot_a)
    for share, p, spill in ((share_b, 3 / 4, 1 / 1010),
                            (share_a, 1 / 2, 1 / 111)):
        want = p + (1 - p) * spill
        assert abs(share - want) <= 4 * np.sqrt(want * (1 - want) / N) \
            + (1 - p) * spill


def test_millisecond_times_in_order_and_ids_in_range(config, bids):
    t_ms = bids.t.astype(np.float64) * 1000
    assert np.all(np.diff(bids.t) >= 0)
    np.testing.assert_allclose(t_ms, np.round(t_ms), atol=1e-3)
    np.testing.assert_array_equal(
        np.round(t_ms).astype(np.int64), bids.ctr.astype(np.int64) // 10)
    assert np.bincount(np.round(t_ms).astype(np.int64)).max() == 10
    b, a = bids.key[:, 0], bids.key[:, 1]
    assert b.min() >= 0 and b.max() < bids.n_person_rows
    assert a.min() >= bids.n_person_rows and a.max() < bids.n_rows
    assert bids.q.min() >= 0.01 and bids.q.max() <= 1e6


def test_deterministic_per_seed(config, bids):
    again = nexmark.make_stream(config, N, SEED)
    other = nexmark.make_stream(config, N, SEED + 1)
    for f in ("key", "q", "t", "ctr"):
        np.testing.assert_array_equal(getattr(bids, f), getattr(again, f))
    assert not np.array_equal(bids.key, other.key)
    np.testing.assert_array_equal(bids.t, other.t)


def test_refuses_times_past_float32_milliseconds(config):
    with pytest.raises(ValueError, match="float32"):
        nexmark.make_stream(config, 151_000_000, SEED)


def test_time_bits_counter_is_not_correct(monkeypatch):
    """A program that keys each lane's draw on its time bits, as before
    event-id counters, writes a millisecond's tied bids together: the
    check, whose reference keys draws on event ids, fails it."""
    orig = engine._step_fast

    def time_bits(cfg, state, ev, rng, rng_entity=None):
        return orig(cfg, state, ev._replace(ctr=None), rng, rng_entity)
    small_cell(monkeypatch)
    monkeypatch.setattr(engine, "_step_fast", time_bits)
    core_stream._sink_step.cache_clear()
    try:
        res = harness.run_cell("nexmark_bids.ingest", SEED, 1.0, False, 0.0,
                               require_chip=False)
    finally:
        core_stream._sink_step.cache_clear()
    assert not res["correct"], res["check"]
    assert res["check"]["z_flips"]["value"] > 0
    assert res["diagnostics"]["tied_events"] > 0


def test_lane_counts():
    """Ties on (row, t) and the most lanes of one row count within a
    micro-batch only; padding lanes count for none."""
    from bench.checks.fanout_profiles import lane_counts

    key = np.array([[5, 7], [5, 7], [5, 9],      # micro-batch 0
                    [5, 5], [1, 2]], np.int32)   # micro-batch 1, padded
    t = np.array([1.0, 1.0, 2.0, 1.0, 3.0], np.float32)
    assert lane_counts(key, t, batch=3) == {"tied_events": 3,
                                            "max_key_lanes": 3}


def _record(sink_stats, trace=None, events=1000):
    from bench import drivers
    win = drivers.Window(
        seconds=2.0, events=events, completed=events, bytes_written=0,
        store_bytes=0, sample_pos=None, p=None, z=None, lam=None,
        batch_id=None, sink_stats=sink_stats, store_dir="")
    eng = {"batch": 4096, "windows_s": [1.0] * 6, "policy": "pp_vr"}
    return harness.Record(cell={}, config={"engine": eng}, traffic={},
                          window=win, setup_s=0.0, peak_bytes=0,
                          device_kind="TPU v5 lite", trace=trace)


@pytest.mark.parametrize("metric", [
    "host_pack_us_per_event", "stack_outputs_us_per_event",
    "d2h_bytes_per_event", "sink_submit_wait_share",
    "store_compaction_share", "device_idle_share", "group_step_ms",
    "puts_per_event"])
def test_nexmark_readers_read_as_the_ingest_readers(metric):
    from bench.trace_reduce import Summary
    stats = {"host_pack_s": 0.003, "outputs_s": 0.004, "puts": 60,
             "rows_d2h_bytes": 88_000, "outputs_d2h_bytes": 105_000,
             "submit_wait_s": 0.5, "measured": {"compaction_s": 0.25}}
    trace = Summary(window_s=2.0, busy_s=1.5, idle_gaps=[], device_ops=[],
                    op_s={}, op_n={}, modules={"jit_run": [0.02, 0.03]},
                    n_chips=1)
    rec = _record(stats, trace)
    want = harness.reader(metric + ".ingest")(rec)
    assert want is not None
    assert harness.reader(metric + ".nexmark")(rec) == want


def test_kernel_roofline_prices_a_call_at_its_lanes():
    """A call of a two-profile cell runs batch * 2 lanes: priced so, a
    call at the bytes roof reads 100%; a window without the lanes counter
    reads nothing."""
    from bench import roofline
    from bench.trace_reduce import Summary
    _, nbytes = roofline.thinning_rmw_cost(8192, 6, "pp_vr")
    trace = Summary(window_s=2.0, busy_s=1.5, idle_gaps=[], device_ops=[],
                    op_s={"thinning_rmw.3": 10 * nbytes / 819e9},
                    op_n={"thinning_rmw.3": 10}, modules={}, n_chips=1)
    read = harness.reader("thinning_kernel_roofline.nexmark")
    assert read(_record({"fanout_lanes": 2000}, trace)) == \
        pytest.approx(100.0)
    assert read(_record({}, trace)) is None
