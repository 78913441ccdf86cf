"""The readers of the program's spans and counters: the number each
returns from a ``Record``, and ``None`` where a program without that span
or counter leaves its field out."""
import pytest

from benchtest import harness
from bench import drivers


def record(sink_stats, events=1000, seconds=2.0):
    win = drivers.Window(
        seconds=seconds, events=events, completed=events, bytes_written=0,
        store_bytes=0, sample_pos=None, p=None, z=None, lam=None,
        batch_id=None, sink_stats=sink_stats, store_dir="")
    return harness.Record(cell={}, config={}, traffic={}, window=win,
                          setup_s=0.0, peak_bytes=0, device_kind="")


FULL = {"outputs_s": 0.004, "rows_d2h_bytes": 88_000,
        "outputs_d2h_bytes": 105_000, "measured": {"compaction_s": 0.5}}

CASES = [
    ("stack_outputs_us_per_event.ingest", 4.0, "outputs_s"),
    ("d2h_bytes_per_event.ingest", 193.0, "rows_d2h_bytes"),
    ("d2h_bytes_per_event.ingest", 193.0, "outputs_d2h_bytes"),
    ("store_compaction_share.ingest", 25.0, "measured"),
]


@pytest.mark.parametrize("metric,want,_", CASES)
def test_reader_value(metric, want, _):
    assert harness.reader(metric)(record(dict(FULL))) == pytest.approx(want)


@pytest.mark.parametrize("metric,_,missing", CASES)
def test_reader_without_its_field(metric, _, missing):
    stats = {k: v for k, v in FULL.items() if k != missing}
    assert harness.reader(metric)(record(stats)) is None


def test_store_compaction_share_without_the_span():
    stats = dict(FULL, measured={"wal_bytes": 1})   # a store of the parent
    assert harness.reader("store_compaction_share.ingest")(
        record(stats)) is None


def test_passes_stats_merge():
    """A window of several passes reports its sinks' stats as one: counts
    and seconds summed, ratios and maxima the largest, lists joined."""
    from bench.paths.ingest import merge_stats

    a = {"puts": 10, "outputs_s": 0.5, "waf": 1.5, "store_path_s_max": 2.0,
         "measured": {"compaction_s": 0.25, "measured_waf": 3.0},
         "measured_per_partition": [{"fsyncs": 1}]}
    b = {"puts": 5, "outputs_s": 0.25, "waf": 1.25, "store_path_s_max": 3.0,
         "measured": {"compaction_s": 0.5, "measured_waf": 2.0},
         "measured_per_partition": [{"fsyncs": 2}]}
    assert merge_stats([a, b]) == {
        "puts": 15, "outputs_s": 0.75, "waf": 1.5, "store_path_s_max": 3.0,
        "measured": {"compaction_s": 0.75, "measured_waf": 3.0},
        "measured_per_partition": [{"fsyncs": 1}, {"fsyncs": 2}]}
    assert merge_stats([a]) == a
