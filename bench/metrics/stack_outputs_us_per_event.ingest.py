"""Host time the stream driver spent stacking the flush groups' per-event
outputs into one result (the program's ``repro.stream.outputs`` span,
``SinkStats.outputs_s``), in microseconds per event."""


def read(rec):
    w = rec.window
    t = w.sink_stats.get("outputs_s")
    if t is None or w.events == 0:
        return None
    return 1e6 * t / w.events
