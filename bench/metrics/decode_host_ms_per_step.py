"""Host time of a decode step in which the host does not wait on the
device: each `engine.decode_step` span that starts inside the traced
window, less what its blocking mask pulls (`engine.mask_pull`) and its
bandwidth sampler's waits (`swap.drain`, `swap.sample_wait`) cover; the
mean over those steps."""
import os

from bench.lib import program_trace

WAITS = ("engine.mask_pull", "swap.drain", "swap.sample_wait")


def read(ctx):
    r = program_trace.load(ctx, os.path.dirname(__file__))
    if r is None:
        return None
    own = program_trace.self_seconds(r["program_spans"], "engine.decode_step",
                                     WAITS, r["lo"], r["hi"])
    return 1e3 * sum(own) / len(own) if own else None
