"""What the host was doing, from the trace's ``/host:CPU`` plane.

The program's spans (``trainer.*``, ``consensus.*``:
``jax.profiler.TraceAnnotation``) lie on that plane beside JAX's and the
runtime's own events, on the timebase of the device planes: a span can
be laid over a device idle gap with no clock arithmetic.
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple, Sequence, Tuple

from chipbench.hlo_scopes import xplane_of

HOST_PLANE = "/host:CPU"
#: the label of a gap during which no span of the program's was open: the
#: time belongs to whoever called the program (here, the harness)
CALLER = "caller"


class Span(NamedTuple):
    name: str
    start_ns: float
    end_ns: float
    thread: str


def load(xplane_path: str) -> List[Span]:
    """Every event of the host plane, sorted by start (an enclosing span
    before the spans inside it)."""
    from jax.profiler import ProfileData

    spans = [
        Span(e.name, e.start_ns, e.start_ns + e.duration_ns, line.name)
        for plane in ProfileData.from_file(xplane_path).planes
        if plane.name == HOST_PLANE
        for line in plane.lines
        for e in line.events
    ]
    return sorted(spans, key=lambda s: (s.start_ns, -s.end_ns))


def of(ctx) -> List[Span]:
    """``load`` of this run's trace, read once per run (kept on ``ctx``)."""
    cached = getattr(ctx, "_host_spans", None)
    if cached is None:
        path = xplane_of(ctx)
        cached = ctx._host_spans = load(path) if path else []
    return cached


def owners_ns(start: float, end: float,
              spans: Sequence[Span]) -> Dict[str, float]:
    """The nanoseconds of ``[start, end)`` by the innermost (shortest) of
    ``spans`` open at each instant, under ``CALLER`` where none is."""
    inside = [s for s in spans if s.start_ns < end and s.end_ns > start]
    cuts = sorted({start, end} | {
        min(max(t, start), end) for s in inside for t in (s.start_ns, s.end_ns)
    })
    out: Dict[str, float] = {}
    for a, b in zip(cuts, cuts[1:]):
        open_ = [s for s in inside if s.start_ns <= a and s.end_ns >= b]
        name = (min(open_, key=lambda s: s.end_ns - s.start_ns).name
                if open_ else CALLER)
        out[name] = out.get(name, 0.0) + (b - a)
    return out


def label_gaps(gaps: Sequence[Tuple[float, float]],
               spans: Sequence[Span]) -> List[str]:
    """For each device idle gap ``(start_ns, end_ns)``, the innermost of
    ``spans`` that covers most of it (``owners_ns``' largest share), or
    ``CALLER``.  ``spans`` are the program's: the caller of this filters
    the host plane by name."""
    labels = []
    for start, end in gaps:
        owners = owners_ns(start, end, spans)
        labels.append(max(owners, key=owners.get) if owners else CALLER)
    return labels
