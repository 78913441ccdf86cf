"""Share of the NEXmark cell's window the driver spent blocked in the
sink's ``submit``, %, read as ``sink_submit_wait_share.ingest`` reads it."""
from bench.harness import reader

read = reader("sink_submit_wait_share.ingest")
