"""Share of the NEXmark cell's window the durable stores spent compacting,
%, read as ``store_compaction_share.ingest`` reads it."""
from bench.harness import reader

read = reader("store_compaction_share.ingest")
