"""Device-idle milliseconds that fall inside the host spans whose name
matches ``span``, per ``per``, averaged over the chips.  Idle is the gaps
between the busy intervals of the ``XLA Ops`` line (as ``run.py`` computes
them for ``breakdown.idle_gaps``); the spans are the program's own
``jax.profiler.TraceAnnotation``s on the host plane of the same trace
(``chipbench/host_spans.py``), counted once where they nest or overlap.
Nothing where the trace has no such span (a program without them)."""

import re

from chipbench import host_spans
from chipbench.trace import OPS, Event, busy_intervals


def idle_gaps(events) -> list:
    busy = busy_intervals(events)
    return [(a[1], b[0]) for a, b in zip(busy, busy[1:])]


def reduce(ctx, span: str, per: str):
    lines = ctx.trace.line(OPS)
    n = ctx.window.get(per)
    rx = re.compile(span)
    # host_spans are sorted by start, as busy_intervals wants its events
    open_ = busy_intervals([
        Event(s.name, s.start_ns, s.end_ns - s.start_ns)
        for s in host_spans.of(ctx) if rx.search(s.name)
    ])
    if not lines or not n or not open_:
        return None
    total_ns = sum(
        max(0.0, min(end, hi) - max(start, lo))
        for events in lines
        for start, end in idle_gaps(events)
        for lo, hi in open_
    )
    return 1e-6 * total_ns / len(lines) / n
