"""Device milliseconds of the operations the program named ``scope``, per
``per``, averaged over the chips: the self time (``trace.self_seconds``'
rule, a parent less its children) of the ``XLA Ops`` events inside the
launches matching ``module`` whose instruction's ``op_name`` matches
``scope`` and not ``exclude``.  The ``op_name`` is the ``jax.named_scope``
path JAX wrote into the module's HLO (``chipbench/hlo_scopes.py``); a
fusion counts whole under its own (its root's), so the split is by fusion,
not by flop.  Nothing where no operation carries the scope (a program
without it, or an executable compiled before it had it)."""

import re

from chipbench import hlo_scopes
from chipbench.trace import MODULES, OPS, self_seconds


def by_op_name(ctx, module: str) -> list:
    """Per device plane, ``{op_name or None: seconds}`` of the operations
    inside the launches matching ``module`` (kept on ``ctx``: several
    metrics split one module)."""
    kept = vars(ctx).setdefault("_scope_seconds", {})
    if module not in kept:
        kept[module] = _by_op_name(ctx, module)
    return kept[module]


def _by_op_name(ctx, module: str) -> list:
    scopes = hlo_scopes.of(ctx)
    rx = re.compile(module)
    planes = []
    for plane in ctx.trace.devices.values():
        inside: dict = {}  # launch name -> its operations
        launches = [e for e in plane.get(MODULES, []) if rx.search(e.name)]
        for op in plane.get(OPS, []):
            for launch in launches:
                if launch.start_ns <= op.start_ns < launch.start_ns + launch.dur_ns:
                    inside.setdefault(launch.name, []).append(op)
                    break
        seconds: dict = {}
        for launch_name, ops in inside.items():
            for instruction, s in self_seconds(ops).items():
                op_name = scopes.get((launch_name, instruction.lstrip("%")))
                seconds[op_name] = seconds.get(op_name, 0.0) + s
        planes.append(seconds)
    return planes


def reduce(ctx, module: str, scope: str, per: str, exclude: str = None):
    n = ctx.window.get(per)
    planes = by_op_name(ctx, module)
    want = re.compile(scope)
    skip = re.compile(exclude) if exclude else None
    total = sum(
        s for seconds in planes for op_name, s in seconds.items()
        if op_name and want.search(op_name)
        and not (skip and skip.search(op_name))
    )
    return 1e3 * total / len(planes) / n if planes and total > 0 and n else None
