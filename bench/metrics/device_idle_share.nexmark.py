"""Share of the NEXmark cell's traced window in which no operation ran on
the chip, %, read as ``device_idle_share.ingest`` reads it."""
from bench.harness import reader

read = reader("device_idle_share.ingest")
