"""Share of the traced window in which no operation ran on the chip, %."""


def read(rec):
    s = rec.trace
    if s is None or s.window_s <= 0:
        return None
    return 100.0 * (1.0 - s.busy_s / s.window_s)
