"""Model step: FLOPs of the plain algorithm for the queries finished in
the window (``bench.flops``), per second of window, as a share of the
chip's bf16 peak (``bench/peaks.json``)."""


def read(ctx):
    if not ctx.flops:
        return None
    return 100.0 * ctx.flops / ctx.rec.seconds / ctx.peak["bf16_flops_per_s"]
