"""Device milliseconds of the operations whose name (as XLA prints it)
matches ``op`` on the trace line ``line`` (``XLA Ops``, or ``Async XLA
Ops`` for a collective's span from start to done), per ``per``, averaged
over the chips."""

from chipbench.reducers.module_time import ms_per
from chipbench.trace import OPS


def reduce(ctx, op: str, per: str, line: str = OPS):
    return ms_per(ctx, line, op, per)
