"""Host time the stream driver spent building flush groups (the sink's
``host_pack_s`` span), in microseconds per event."""


def read(rec):
    w = rec.window
    t = w.sink_stats.get("host_pack_s")
    if t is None or w.events == 0:
        return None
    return 1e6 * t / w.events
