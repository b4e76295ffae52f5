"""Device time of the decode steps' MoE FFN (the program's
`jit_moe_ffn_decode`, all layers) inside the traced window, per decode step
(`engine.decode_step` spans that start in it)."""
import os

from bench.lib import program_trace


def read(ctx):
    return program_trace.module_ms_per_span(
        program_trace.load(ctx, os.path.dirname(__file__)),
        "jit_moe_ffn_decode", "engine.decode_step")
