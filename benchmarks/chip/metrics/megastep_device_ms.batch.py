"""Model step: mean device time of one megastep program, from the trace's
program (module) events whose name starts with ``PROGRAM``."""

PROGRAM = "jit__megastep_impl"


def read(ctx):
    if ctx.trace is None:
        return None
    durs = [d for name, v in ctx.trace["modules"].items()
            if name.startswith(PROGRAM) for d in v]
    return 1e3 * sum(durs) / len(durs) if durs else None
