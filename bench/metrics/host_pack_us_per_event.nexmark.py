"""Host time the stream driver spent building the NEXmark cell's flush
groups, in microseconds per bid, read as ``host_pack_us_per_event.ingest``
reads it."""
from bench.harness import reader

read = reader("host_pack_us_per_event.ingest")
