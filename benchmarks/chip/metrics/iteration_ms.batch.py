"""Engine: the window's length over the pump iterations that ended in it
(host clock): one fused megastep plus the host's scheduling around it."""


def read(ctx):
    it = ctx.rec.iterations
    return 1e3 * ctx.rec.seconds / it if it else None
