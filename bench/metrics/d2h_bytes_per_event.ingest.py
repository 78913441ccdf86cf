"""Bytes the program copied from the device to the host per event: each
flush group's gathered rows (``SinkStats.rows_d2h_bytes``) and its stacked
per-event outputs (``outputs_d2h_bytes``), counted from shapes."""


def read(rec):
    w = rec.window
    rows = w.sink_stats.get("rows_d2h_bytes")
    outs = w.sink_stats.get("outputs_d2h_bytes")
    if rows is None or outs is None or w.events == 0:
        return None
    return (rows + outs) / w.events
