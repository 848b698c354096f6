"""Program launches on the device per ``per`` of the traced window."""

from chipbench.trace import MODULES


def reduce(ctx, per: str):
    lines = ctx.trace.line(MODULES)
    n = ctx.window.get(per)
    if not lines or not n:
        return None
    return sum(len(events) for events in lines) / len(lines) / n
