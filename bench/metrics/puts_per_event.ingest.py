"""Rows the sink put to the store per event: the thinning rate, a count."""


def read(rec):
    puts = rec.window.sink_stats.get("puts")
    if puts is None or rec.window.events == 0:
        return None
    return puts / rec.window.events
