"""Median host time of one prefill chunk, from the call into the engine
until its caches (and last-chunk logits) are ready, inside the window."""
from bench.lib.harness import percentile


def read(ctx):
    p = percentile([e - s for n, s, e in ctx.spans
                    if n == "prefill_chunk" and ctx.inside(s)], 50)
    return None if p is None else 1e3 * p
