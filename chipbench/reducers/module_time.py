"""Device milliseconds of the program launches whose name matches
``module``, per ``per`` (a count of the traced window: steps, gossips,
rounds, calls), averaged over the chips."""

from chipbench.trace import MODULES, matching_seconds


def ms_per(ctx, line: str, pattern: str, per: str):
    """Milliseconds of the events on ``line`` matching ``pattern``, per
    ``per``; nothing where no event matches or the count is absent."""
    lines = ctx.trace.line(line)
    total = sum(matching_seconds(events, pattern) for events in lines)
    n = ctx.window.get(per)
    return 1e3 * total / len(lines) / n if lines and total > 0 and n else None


def reduce(ctx, module: str, per: str):
    return ms_per(ctx, MODULES, module, per)
