"""Bytes the process handed to write calls during the window, per event:
the WAL appends and segment files, taken by the benchmark from Linux's
per-process ``wchar`` and not from the store's own counters (which the
result's diagnostics print beside it, as ``store_bytes``)."""


def read(rec):
    return rec.window.bytes_written / rec.window.events
