"""Engine: device idle time under the pump's ``serve/wait`` and
``serve/readout`` spans (the host waiting for the megastep's output
bundle, then copying it), per pump iteration, from the trace."""

SPANS = ("serve/wait", "serve/readout")


def read(ctx):
    it = ctx.rec.iterations
    if ctx.trace is None or not it:
        return None
    idle = dict(ctx.trace["idle"])
    return 1e3 * sum(idle.get(s, 0.0) for s in SPANS) / it
