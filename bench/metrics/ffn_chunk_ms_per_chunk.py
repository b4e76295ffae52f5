"""Device time of the prefill chunks' MoE FFN (the program's
`jit_moe_ffn_chunk`, all layers) inside the traced window, per prefill chunk
(`engine.prefill_chunk` spans that start in it)."""
import os

from bench.lib import program_trace


def read(ctx):
    return program_trace.module_ms_per_span(
        program_trace.load(ctx, os.path.dirname(__file__)),
        "jit_moe_ffn_chunk", "engine.prefill_chunk")
