"""Bytes the program copied from the device to the host per bid of the
NEXmark cell (two lanes a bid), read as ``d2h_bytes_per_event.ingest``
reads it."""
from bench.harness import reader

read = reader("d2h_bytes_per_event.ingest")
