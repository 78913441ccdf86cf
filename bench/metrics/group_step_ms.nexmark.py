"""Device time of one flush group of the NEXmark cell, read as
``group_step_ms.ingest`` reads it: the mean duration, in ms, of the
executions of ``jit_run``."""
from bench.harness import reader

read = reader("group_step_ms.ingest")
