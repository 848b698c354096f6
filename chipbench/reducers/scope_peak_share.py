"""A named scope's share of its roofline, in percent: the least time the
chip could take for the work the scope's algorithm requires (the larger
of ``flops`` over the peak FLOP/s and ``bytes`` over the peak bytes/s,
both quantities the harness computed from shapes, per ``per``) over the
device time of the operations the program named ``scope``
(``scope_time``: a fusion counts whole under its root's name).  Nothing
where no operation carries the scope or the work is not given."""

from chipbench.reducers.scope_time import reduce as scope_ms


def reduce(ctx, module: str, scope: str, per: str, flops: str, bytes: str,
           exclude: str = None):
    ms = scope_ms(ctx, module, scope, per, exclude)
    need_flops, need_bytes = ctx.work.get(flops), ctx.work.get(bytes)
    if ms is None or not need_flops or not need_bytes:
        return None
    if ctx.kind not in ctx.peaks:
        raise KeyError(f"peaks.json has no device kind {ctx.kind!r}")
    peak = ctx.peaks[ctx.kind]
    least_s = max(need_flops / peak["bf16_flops_per_s"],
                  need_bytes / peak["hbm_bytes_per_s"]) / ctx.chips
    return 100.0 * least_s / (ms * 1e-3)
