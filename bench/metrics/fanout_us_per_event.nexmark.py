"""Host time the stream driver spent on per-lane work for events that
update several profiles (the ``repro.stream.fanout`` span, ``SinkStats.
fanout_s``), in microseconds per event."""


def read(rec):
    w = rec.window
    t = w.sink_stats.get("fanout_s")
    if t is None or w.events == 0:
        return None
    return 1e6 * t / w.events
