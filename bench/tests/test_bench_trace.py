"""The reduction from a profiler trace to idle share, gaps and kernel time."""
import os

import pytest

from benchtest import ROOT
from bench import trace_reduce as tr

FIXTURE = os.path.join(ROOT, "bench", "tests", "data", "fixture.xplane.pb")


def synthetic():
    # window 0..100 ns; ops cover 0-10, 5-20 (overlap), 30-40, 90-120
    ops = [("fusion.1", 0, 10), ("thinning_rmw.6", 5, 20),
           ("thinning_rmw.6", 30, 40), ("copy.2", 90, 120)]
    host = [("bench.window", 0, 100), ("bench.run_stream", 0, 60),
            ("bench.collect", 60, 80), ("bench.flush", 80, 100)]
    return tr.Trace(ops={"/device:TPU:0": ops},
                    modules={"/device:TPU:0": [("jit_run(7)", 0, 40)]},
                    host=host)


def test_busy_idle_and_gaps():
    s = tr.summarize(synthetic())
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx(40e-9)      # 0-20, 30-40, 90-100
    assert s.idle_gaps == [("bench.collect", pytest.approx(50e-9)),
                           ("bench.run_stream", pytest.approx(10e-9))]
    assert s.modules == {"jit_run": [pytest.approx(40e-9)]}
    assert tr.kernel_seconds(s, "thinning_rmw") == (pytest.approx(25e-9),
                                                    2)
    assert s.device_ops[0] == ("thinning_rmw.6", pytest.approx(25e-9))


def test_no_device_ops_is_an_error():
    t = synthetic()
    t.ops.clear()
    with pytest.raises(ValueError):
        tr.summarize(t)
    assert tr.kernel_seconds(None, "thinning_rmw") == (0.0, 0)


def test_recorded_chip_trace():
    """One flush group of iiot800k on a TPU v5e, traced by the harness's
    own ``trace_reduce.start`` (one ``jit_run`` of 8 blocks: 8 kernel
    calls)."""
    s = tr.summarize(tr.load(FIXTURE))
    assert s.n_chips == 1
    assert s.window_s == pytest.approx(0.388995843, rel=1e-9)
    assert s.busy_s == pytest.approx(0.104332945, rel=1e-9)
    assert 1 - s.busy_s / s.window_s == pytest.approx(0.7317890, rel=1e-6)
    assert tr.kernel_seconds(s, "thinning_rmw") == (
        pytest.approx(0.000223893, rel=1e-6), 8)
    assert [len(v) for k, v in s.modules.items() if k == "jit_run"] == [1]
    assert s.device_ops[0][0] == "fusion.106"
    assert all(n.startswith("bench.") for n, _ in s.idle_gaps)
