"""Process start to the window's start: JAX start-up, training the
weights on the device, building the engine and warming its programs."""


def read(ctx):
    return ctx.setup_s
