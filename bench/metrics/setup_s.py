"""Seconds from the process's start to the window's: JAX and chip start-up,
stream and weights from the seed, and warm-up of every shape the window
uses (compiles, served from the persistent cache after a cell's first run)."""


def read(rec):
    return rec.setup_s
