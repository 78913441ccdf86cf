"""From a profiler trace to the benchmark's device numbers.

The JAX profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it.  On a TPU each chip is a plane ``/device:TPU:<n>``; its line
``XLA Ops`` holds one event per device operation, named by the operation's
HLO text (``%fusion.106 = f32[...] fusion(...)``, reduced here to
``fusion.106``), and its line ``XLA Modules`` one event per execution of a
compiled program, named after the program (``jit_run(...)`` for the
engine's flush-group step).  A ``while`` (a ``lax.scan``) is an operation
whose events enclose those of its body: it counts towards busy time, and
its body's operations, not the loop, are what the op totals rank.  The
benchmark's own host spans (``jax.profiler.TraceAnnotation`` named
``bench.*``) are on the host plane, on the same clock.

Busy time is the union of the op intervals inside the window; the idle
share is one minus busy over the window, averaged over the chips.  Each
idle gap is labelled with the innermost ``bench.*`` span that covers its
midpoint, so the gaps say what the host was doing.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

Span = Tuple[str, float, float]         # (name, start_ns, end_ns)

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."
CONTAINERS = ("while", "conditional", "call")


def op_name(raw: str) -> str:
    """``%fusion.106 = f32[...] fusion(...)`` -> ``fusion.106``."""
    head = raw.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def is_container(name: str) -> bool:
    return name.split(".")[0] in CONTAINERS


def start(trace_dir: str) -> None:
    """Start tracing the device and the host's ``TraceAnnotation`` spans,
    without Python function tracing (which would slow the host path)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Span]]          # per device plane
    modules: Dict[str, List[Span]]
    host: List[Span]                    # bench.* annotations


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, modules, host = {}, {}, []
    for plane in pd.planes:
        name = plane.name
        if name.startswith(DEVICE_PREFIX) and name[len(DEVICE_PREFIX):] \
                .isdigit():
            for line in plane.lines:
                dst = {OPS_LINE: ops, MODULES_LINE: modules}.get(line.name)
                if dst is not None:
                    dst.setdefault(name, []).extend(
                        (op_name(e.name), e.start_ns,
                         e.start_ns + e.duration_ns) for e in line.events)
        elif name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events
                            if e.name.startswith(HOST_PREFIX))
    return Trace(ops=ops, modules=modules, host=host)


def clip(spans: List[Span], lo: float, hi: float) -> List[Span]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in spans
            if e > lo and s < hi]


def union(spans: List[Span]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for _, s, e in sorted(spans, key=lambda x: x[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(spans: List[Span], lo: float, hi: float) -> float:
    return sum(e - s for s, e in union(clip(spans, lo, hi)))


def gaps(spans: List[Span], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    out, cur = [], lo
    for s, e in union(clip(spans, lo, hi)):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


def label(gap: Tuple[float, float], host: List[Span]) -> str:
    """Innermost (shortest) host span covering the gap's midpoint."""
    mid = 0.5 * (gap[0] + gap[1])
    covering = [(e - s, n) for n, s, e in host if s <= mid <= e]
    return min(covering)[1] if covering else "no bench span"


def op_totals(spans: List[Span], lo: float, hi: float
              ) -> Dict[str, Tuple[float, int]]:
    """Per op name: (summed duration in ns, number of events)."""
    tot: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0])
    for n, s, e in clip(spans, lo, hi):
        tot[n][0] += e - s
        tot[n][1] += 1
    return {n: (t, int(c)) for n, (t, c) in tot.items()}


def window(trace: Trace, name: str = "bench.window"
           ) -> Tuple[float, float]:
    spans = [(s, e) for n, s, e in trace.host if n == name]
    if not spans:
        raise ValueError(f"the trace holds no {name!r} span")
    return min(s for s, _ in spans), max(e for _, e in spans)


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                       # averaged over the chips
    idle_gaps: List[Tuple[str, float]]  # longest first, seconds
    device_ops: List[Tuple[str, float]]  # most time first, seconds
    op_s: Dict[str, float]              # every op name, summed over chips
    op_n: Dict[str, int]                # events per op name
    modules: Dict[str, List[float]]     # program name -> durations, s
    n_chips: int


def summarize(trace: Trace, top: int = 10) -> Summary:
    lo, hi = window(trace)
    chips = sorted(trace.ops)
    if not chips:
        raise ValueError("the trace holds no device operations")
    busy = [busy_ns(trace.ops[c], lo, hi) for c in chips]
    all_gaps = []
    op_s: Dict[str, float] = defaultdict(float)
    op_n: Dict[str, int] = defaultdict(int)
    for c in chips:
        all_gaps += [(label(g, trace.host), (g[1] - g[0]) * 1e-9)
                     for g in gaps(trace.ops[c], lo, hi)]
        for n, (t, k) in op_totals(trace.ops[c], lo, hi).items():
            if not is_container(n):
                op_s[n] += t * 1e-9
                op_n[n] += k
    modules: Dict[str, List[float]] = defaultdict(list)
    for c in chips:
        for n, s, e in clip(trace.modules.get(c, []), lo, hi):
            modules[n.split("(")[0]].append((e - s) * 1e-9)
    all_gaps.sort(key=lambda x: -x[1])
    ranked = sorted(op_s.items(), key=lambda x: -x[1])
    return Summary(window_s=(hi - lo) * 1e-9,
                   busy_s=sum(busy) / len(busy) * 1e-9,
                   idle_gaps=all_gaps[:top], device_ops=ranked[:top],
                   op_s=dict(op_s), op_n=dict(op_n),
                   modules=dict(modules),
                   n_chips=len(chips))


def kernel_seconds(summary: Optional[Summary], prefix: str
                   ) -> Tuple[float, int]:
    """Total device seconds and event count of ops named ``prefix`` or
    ``prefix.<n>`` (the HLO instruction names of one kernel's calls)."""
    if summary is None:
        return 0.0, 0
    names = [n for n in summary.op_s
             if n == prefix or n.startswith(prefix + ".")]
    return (sum(summary.op_s[n] for n in names),
            sum(summary.op_n[n] for n in names))
