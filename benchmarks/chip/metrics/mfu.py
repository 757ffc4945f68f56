"""Model step: FLOPs of the plain algorithm for the queries finished in
the window (``bench.flops``), per second of window, as a share of the
bf16 peak of the cell's chips (``bench/peaks.json`` times the device
count)."""


def read(ctx):
    if not ctx.flops:
        return None
    peak = ctx.peak["bf16_flops_per_s"] * ctx.device["count"]
    return 100.0 * ctx.flops / ctx.rec.seconds / peak
