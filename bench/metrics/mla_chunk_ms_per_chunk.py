"""Device time of the prefill chunks' latent attention (the program's
`jit_pre_prefill_chunk_mla*`: the chunk's latent rows written, the cached
prefix re-expanded and attended, norm and routing, every MoE layer) inside
the traced window, per prefill chunk (`engine.prefill_chunk` spans that
start in it). None on a program whose modules carry no `_mla` name."""
import os

from bench.lib import program_trace


def read(ctx):
    return program_trace.module_ms_per_span(
        program_trace.load(ctx, os.path.dirname(__file__)),
        "jit_pre_prefill_chunk_mla", "engine.prefill_chunk")
