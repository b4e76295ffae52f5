"""Output tokens whose host time falls inside the window, over the window."""


def read(ctx):
    n = sum(1 for r in ctx.requests for t in ctx.token_times(r) if ctx.inside(t))
    return n / ctx.seconds
