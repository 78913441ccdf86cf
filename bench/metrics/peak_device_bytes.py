"""The device allocator's ``peak_bytes_in_use`` after the window, on the
fullest chip."""


def read(rec):
    return int(rec.peak_bytes)
