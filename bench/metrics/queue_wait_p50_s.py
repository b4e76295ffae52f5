"""Median wait from scheduled arrival to admission (a batch slot), over the
requests admitted inside the window."""
from bench.lib.harness import percentile


def read(ctx):
    return percentile([r.admitted_s - r.arrival_s for r in ctx.requests
                       if r.admitted_s >= 0 and ctx.inside(r.admitted_s)], 50)
