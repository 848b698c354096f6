"""Device milliseconds during which at least one operation whose name
matches ``op`` was in flight on the trace line ``line`` (``Async XLA Ops``
for collectives and copies, from start to done), per ``per``, averaged
over the chips: the union of the matching events' intervals, not their
sum, so that a hundred messages sent side by side count the time once.
Nothing where no event matches or the count is absent."""

import re

from chipbench.trace import ASYNC_OPS, busy_intervals


def reduce(ctx, op: str, per: str, line: str = ASYNC_OPS):
    rx = re.compile(op)
    lines = ctx.trace.line(line)
    n = ctx.window.get(per)
    total = sum(
        end - start for events in lines
        for start, end in busy_intervals(
            [e for e in events if rx.search(e.name)])
    ) * 1e-9
    return 1e3 * total / len(lines) / n if lines and total > 0 and n else None
