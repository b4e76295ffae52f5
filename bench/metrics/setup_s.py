"""Process start to the window's opening: build, compile or cache load,
warm-up, and the ramp of the arrival schedule."""


def read(ctx):
    return ctx.setup_s
