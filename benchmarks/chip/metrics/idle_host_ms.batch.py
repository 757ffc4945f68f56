"""Engine: device idle time under every other ``serve/*`` span of the
pump (scheduling and admission, the megastep's dispatch, the bundle's
host-side sync, eviction, results), per pump iteration, from the trace."""

READOUT = ("serve/wait", "serve/readout")


def read(ctx):
    it = ctx.rec.iterations
    if ctx.trace is None or not it:
        return None
    return 1e3 * sum(s for name, s in ctx.trace["idle"]
                     if name.startswith("serve/")
                     and name not in READOUT) / it
