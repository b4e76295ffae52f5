"""Share of the traced window in which the host waits, dispatching nothing,
for the slot-pool writes that the bandwidth sampler times: the `swap.drain`
and `swap.sample_wait` spans, over the window."""
import os

from bench.lib import program_trace

WAITS = ("swap.drain", "swap.sample_wait")


def read(ctx):
    r = program_trace.load(ctx, os.path.dirname(__file__))
    if r is None or not program_trace.count(r, "engine.decode_step"):
        return None
    lo, hi = r["lo"], r["hi"]
    return 100.0 * program_trace.covered_seconds(r["program_spans"], WAITS,
                                                 lo, hi) / ((hi - lo) / 1e9)
