"""Work the algorithm requires over device time over the chip's peak, in
percent (the peak times the chips used).  ``work`` names a quantity the harness computed from shapes
(``flops_per_step`` from ``chipbench/flops/``, ``bytes_per_round`` from the
state's shapes), ``peak`` a column of ``peaks.json``; the time is that of
the launches matching ``module``, per ``per``."""

from chipbench.reducers.module_time import reduce as module_ms


def reduce(ctx, module: str, per: str, work: str, peak: str):
    ms = module_ms(ctx, module, per)
    amount = ctx.work.get(work)
    if ms is None or not amount:
        return None
    if ctx.kind not in ctx.peaks:
        raise KeyError(f"peaks.json has no device kind {ctx.kind!r}")
    return 100.0 * amount / (ms * 1e-3) / (ctx.peaks[ctx.kind][peak] * ctx.chips)
