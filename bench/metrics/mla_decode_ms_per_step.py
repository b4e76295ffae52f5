"""Device time of the decode steps' latent attention (the program's
`jit_pre_decode_mla*`: attention, norm and routing of every MoE layer)
inside the traced window, per decode step (`engine.decode_step` spans that
start in it). None on a program whose modules carry no `_mla` name."""
import os

from bench.lib import program_trace


def read(ctx):
    return program_trace.module_ms_per_span(
        program_trace.load(ctx, os.path.dirname(__file__)),
        "jit_pre_decode_mla", "engine.decode_step")
