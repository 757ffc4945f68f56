"""Engine: jitted dispatches per pump iteration in the window, from the
engine's ``loop_stats()`` count (1 when only the megastep runs)."""


def read(ctx):
    it = ctx.loop.get("iterations", 0)
    return ctx.loop["dispatches"] / it if it else None
