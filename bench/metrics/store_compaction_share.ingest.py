"""Share of the window's seconds the durable store spent compacting (the
program's ``repro.store.compact`` span, ``DurableCounters.compaction_s``,
summed over partitions), %.  The stores' counters run from their opening,
so a compaction the set-up made counts too."""


def read(rec):
    t = rec.window.sink_stats.get("measured", {}).get("compaction_s")
    if t is None or rec.window.seconds <= 0:
        return None
    return 100.0 * t / rec.window.seconds
