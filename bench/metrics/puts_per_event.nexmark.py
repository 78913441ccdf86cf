"""Rows the sink put to the store per bid of the NEXmark cell (each bid
updates two profiles), read as ``puts_per_event.ingest`` reads it."""
from bench.harness import reader

read = reader("puts_per_event.ingest")
