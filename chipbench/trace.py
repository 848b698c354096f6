"""Profiler trace -> plain event lists, with ``jax.profiler.ProfileData``.

A TPU trace has one plane per chip, ``/device:TPU:<n>``, whose line
``XLA Modules`` holds one event per program launch and whose line
``XLA Ops`` holds the operations inside them, nested where an operation
(a ``while``) contains others; ``Async XLA Ops`` holds the spans of
asynchronous copies and collectives from their start to their done.
Everything here is per device plane; the reducers average over the
planes.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, NamedTuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULES, OPS, ASYNC_OPS = "XLA Modules", "XLA Ops", "Async XLA Ops"


class Event(NamedTuple):
    name: str
    start_ns: float
    dur_ns: float


class Trace(NamedTuple):
    #: device plane name -> line name -> events sorted by start
    devices: Dict[str, Dict[str, List[Event]]]

    def line(self, line: str) -> List[List[Event]]:
        """The named line of every device plane that has it."""
        return [p[line] for p in self.devices.values() if p.get(line)]


def newest_xplane(logdir: str) -> str:
    paths = glob.glob(
        os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb")
    )
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return max(paths, key=os.path.getmtime)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    devices = {}
    for plane in ProfileData.from_file(path).planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        devices[plane.name] = {
            line.name: sorted(
                (Event(e.name, e.start_ns, e.duration_ns) for e in line.events),
                key=lambda e: e.start_ns,
            )
            for line in plane.lines
            if line.name in (MODULES, OPS, ASYNC_OPS)
        }
    return Trace(devices)


def matching_seconds(events: List[Event], pattern: str) -> float:
    rx = re.compile(pattern)
    return sum(e.dur_ns for e in events if rx.search(e.name)) * 1e-9


def busy_intervals(events: List[Event]) -> List[tuple]:
    """Union of the events' intervals, as sorted disjoint (start, end)."""
    merged: List[list] = []
    for e in events:
        end = e.start_ns + e.dur_ns
        if merged and e.start_ns <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([e.start_ns, end])
    return [tuple(m) for m in merged]


def self_seconds(events: List[Event]) -> Dict[str, float]:
    """Seconds by operation name, a parent's time less its children's.  The
    name is XLA's own (``%fusion.18``): the event carries the whole HLO
    instruction, of which only the part before `` = `` is kept."""
    total: Dict[str, float] = {}
    stack: List[list] = []  # [name, end, self_ns]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _end, self_ns = stack.pop()
            total[name] = total.get(name, 0.0) + self_ns * 1e-9

    for e in events:
        close(e.start_ns)
        if stack:
            stack[-1][2] -= e.dur_ns
        stack.append([e.name.split(" = ")[0], e.start_ns + e.dur_ns, e.dur_ns])
    close(float("inf"))
    return total
