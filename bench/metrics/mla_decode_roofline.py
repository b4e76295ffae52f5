"""Share of the HBM roofline that the decode steps' latent attention
(`jit_pre_decode_mla*`) reaches inside the traced window: the bytes the
window's decode steps and decoded tokens need
(`bench/models/deepseek_v2_bytes.py`) over those modules' device seconds,
over the chip's HBM bandwidth. The work is bound by bytes: a decode step
multiplies each weight by a few rows. None where the trace has no such
module or the chip no published peak."""
import os

from bench.lib import program_trace
from bench.models import deepseek_v2_bytes


def read(ctx):
    if ctx.peaks is None or "kv_lora_rank" not in ctx.cfg:
        return None
    r = program_trace.load(ctx, os.path.dirname(__file__))
    ms = program_trace.module_ms_per_span(r, "jit_pre_decode_mla",
                                          "engine.decode_step")
    if ms is None:
        return None
    steps = program_trace.count(r, "engine.decode_step")
    need = deepseek_v2_bytes.decode_bytes(ctx.cfg, steps, ctx.ctx_len)
    return 100.0 * need / (ms * steps / 1e3) / ctx.peaks["hbm_byte_s"]
