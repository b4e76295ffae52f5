"""Host-to-device rate of expert writes: the bytes over the seconds of the
swap windows the program times to completion (the samples that feed its
bandwidth estimate), inside the window."""


def read(ctx):
    s = [(b, t) for at, b, t in ctx.bw if ctx.inside(at)]
    secs = sum(t for _, t in s)
    return sum(b for b, _ in s) / secs / 1e9 if secs > 0 else None
