"""Share of the window the driver spent blocked in the sink's ``submit``
(storage holding the engine back), %."""


def read(rec):
    t = rec.window.sink_stats.get("submit_wait_s")
    if t is None:
        return None
    return 100.0 * t / rec.window.seconds
