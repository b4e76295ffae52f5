"""95th percentile of every gap between two consecutive tokens of one
request whose later token came inside the window, pooled over requests."""
from bench.lib.harness import percentile


def read(ctx):
    gaps = []
    for r in ctx.requests:
        ts = ctx.token_times(r)
        gaps += [b - a for a, b in zip(ts, ts[1:]) if ctx.inside(b)]
    p = percentile(gaps, 95)
    return None if p is None else 1e3 * p
