"""Program spans: the one way the ingest and serve paths time themselves.

``span(name, stats, field)`` is a context manager.  It adds the wall time
of its block, by ``time.perf_counter``, to the float ``stats.<field>`` of a
stats dataclass (``SinkStats``, ``DurableCounters``, ``FrontendStats``),
adds 1 to ``stats.<count>`` when a ``count`` is named, and opens a
``jax.profiler.TraceAnnotation`` called ``name``.  Under a profiler trace
the block is then an event on its own thread's line of the host plane, on
the clock of the device's operations, so an idle gap on the device can be
put down to what each host thread was doing; with no trace running the
annotation costs about a microsecond.

Names read ``repro.<layer>.<what>`` (``repro.stream.pack``,
``repro.sink.device_wait``, ``repro.store.compact``,
``repro.serve.dispatch``, ...).  Spans open per flush group, per store
batch or per served batch, never per event.  A span adds no device sync
and no host copy: it times work that happens anyway, and the byte counters
beside some of them are computed from shapes at a conversion the code
makes regardless.

``edges`` (optional) is a pair of callables ``(on_enter, on_exit)``
handed the span's enter and exit times: the sink's overlap meter computes
the host/device overlap from the very times its two spans record.
"""
from __future__ import annotations

import contextlib
import time
from typing import Optional

import jax

__all__ = ["span"]


@contextlib.contextmanager
def span(name: str, stats, field: str, count: Optional[str] = None,
         edges=None):
    with jax.profiler.TraceAnnotation(name):
        t0 = time.perf_counter()
        if edges is not None:
            edges[0](t0)
        try:
            yield
        finally:
            t1 = time.perf_counter()
            setattr(stats, field, getattr(stats, field) + (t1 - t0))
            if count is not None:
                setattr(stats, count, getattr(stats, count) + 1)
            if edges is not None:
                edges[1](t1)
