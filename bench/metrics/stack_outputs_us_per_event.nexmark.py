"""Host time the stream driver spent stacking the NEXmark cell's per-lane
outputs, in microseconds per bid, read as
``stack_outputs_us_per_event.ingest`` reads it."""
from bench.harness import reader

read = reader("stack_outputs_us_per_event.ingest")
