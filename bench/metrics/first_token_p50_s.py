"""Median time from a request's scheduled arrival to its first token, over
the requests whose first token came inside the window (the program's
`first_token_s` stamps). A window holds about six first tokens, so this
median swings too widely between runs to be held to a bound end to end."""
from bench.lib.harness import percentile


def read(ctx):
    return percentile([r.first_token_s - r.arrival_s for r in ctx.requests
                       if r.first_token_s >= 0 and ctx.inside(r.first_token_s)], 50)
